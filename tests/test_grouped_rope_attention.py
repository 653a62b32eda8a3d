"""Grouped KV heads (`kv_heads` < heads), rotary positions on half-split
pairs and the key multiplier on the DENSE attention op
(ops/attention.py, ops/rope.py), in all three of its entries, against the
plain attention the benchmark's reference keeps
(benchmark/reference/hybrid_ssm_lm.py `attention`); and `kv_heads = heads`
with no rotation against the contractions as they were before grouped heads
existed, bit for bit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flexflow_tpu as ff
from benchmark.reference import hybrid_ssm_lm as ref
from flexflow_tpu.core.op import LoweringContext
from flexflow_tpu.ffconst import CompMode
from flexflow_tpu.ops import attention as attn_mod
from flexflow_tpu.ops import rope

E, HEADS, D = 48, 6, 8


def _op(kv_heads, use_rope, length, batch=1):
    config = ff.FFConfig()
    config.allow_mixed_precision = False
    m = ff.FFModel(config)
    x = m.create_tensor([batch, length, E])
    m.multihead_attention(
        x, x, x, E, HEADS, kdim=D, vdim=D, bias=False, causal=True,
        kv_heads=kv_heads,
        rope_parameters={"rope_theta": 1e4} if use_rope else None,
        key_multiplier=0.37, name="attn")
    return m, m.ops[-1]


def _weights(kv_heads, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    n = lambda key, *s: jax.random.normal(key, s) * 0.3
    return {"wq": n(k[0], E, HEADS, D), "wk": n(k[1], E, kv_heads, D),
            "wv": n(k[2], E, kv_heads, D), "wo": n(k[3], HEADS, D, E)}


def _lower(m, op, x, w, caches=None, pos=None, fill=False):
    ctx = LoweringContext(m.config, CompMode.COMP_MODE_INFERENCE)
    ctx.decode_pos, ctx.fill_kv_cache = pos, fill
    for k, v in (caches or {}).items():
        ctx.state[(op.name, k)] = v
    out = op.lower(ctx, [x, x, x], w)[0]
    return out, {k: ctx.state_updates.get((op.name, k), v)
                 for k, v in (caches or {}).items()}


def _want(x, w, kv_heads, use_rope):
    """The reference's attention of one sequence; without rotation its
    tables are swapped for the identity's (cos 1, sin 0)."""
    cfg = {"num_attention_heads": HEADS, "num_key_value_heads": kv_heads,
           "head_dim": D, "key_multiplier": 0.37, "rope_theta": 1e4}
    if not use_rope:
        orig = ref.rope_tables
        ref.rope_tables = lambda pos, dim, theta: (
            jnp.ones((pos.shape[0], dim // 2)),
            jnp.zeros((pos.shape[0], dim // 2)))
        try:
            return ref.attention(x, w, cfg, "float32")
        finally:
            ref.rope_tables = orig
    return ref.attention(x, w, cfg, "float32")


CASES = [(2, True), (3, False), (1, True), (6, True)]


@pytest.mark.parametrize("kv_heads,use_rope", CASES)
def test_whole_sequence_entry(kv_heads, use_rope):
    length = 16
    m, op = _op(kv_heads, use_rope, length, batch=2)
    w = _weights(kv_heads)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, length, E))
    caches = {k: jnp.zeros((2, 24, kv_heads * D)) for k in
              ("k_cache", "v_cache")}
    out, new = _lower(m, op, x, w, caches, fill=True)
    for b in range(2):
        np.testing.assert_allclose(out[b], _want(x[b], w, kv_heads, use_rope),
                                   rtol=2e-5, atol=2e-5)
    # the cache row is kv_heads x head_dim wide and holds the ROTATED key
    assert op.kv_cache_arrays() == {"k_cache": kv_heads * D,
                                    "v_cache": kv_heads * D}
    k = jnp.einsum("ble,ehd->blhd", x, w["wk"]) * 0.37
    if use_rope:
        cos, sin = rope.cos_sin(jnp.arange(length), D, {"rope_theta": 1e4})
        k = rope.rotate_half_split(k, cos[None, :, None], sin[None, :, None])
    np.testing.assert_allclose(new["k_cache"][:, :length],
                               k.reshape(2, length, -1), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kv_heads,use_rope", CASES)
def test_chunk_offset_and_decode_entries(kv_heads, use_rope):
    """A prompt in chunks of 5 at offsets 0, 5, 10 of a batch-1 cache
    (scalar position, C queries: head by head on the reshaped view), then
    one-token steps over three slots at DIFFERENT positions (a vector of
    positions: all heads at once on the packed row, the queries
    block-diagonal over their KV head's lanes): every output is the
    reference's row of one causal pass."""
    total, prefill, chunk = 21, 15, 5
    w = _weights(kv_heads, seed=3)
    x = jax.random.normal(jax.random.PRNGKey(2), (total, E))
    want = _want(x, w, kv_heads, use_rope)
    mc, opc = _op(kv_heads, use_rope, chunk)
    caches = {k: jnp.zeros((1, 32, kv_heads * D)) for k in
              ("k_cache", "v_cache")}
    for off in range(0, prefill, chunk):
        out, caches = _lower(mc, opc, x[None, off:off + chunk], w, caches,
                             pos=jnp.int32(off))
        np.testing.assert_allclose(out[0], want[off:off + chunk], rtol=2e-5,
                                   atol=2e-5)
    # three slots: the sequence itself, the same one a token behind, and an
    # idle row that must not reach the others
    md, opd = _op(kv_heads, use_rope, 1, batch=3)
    pool = {k: jnp.concatenate([v, v, jnp.full_like(v, 7.0)])
            for k, v in caches.items()}
    for t in range(prefill, total - 1):
        pos = jnp.asarray([t + 1, t, 0], jnp.int32)
        # slot 0 needs row t filled first: write it through slot 1's step
        if t == prefill:
            _, pool0 = _lower(md, opd, jnp.stack([x[t], x[t], x[0]])[:, None],
                              w, pool, pos=jnp.asarray([t, t, 0], jnp.int32))
            pool = pool0
        step = jnp.stack([x[t + 1], x[t], x[0]])[:, None]
        out, pool = _lower(md, opd, step, w, pool, pos=pos)
        np.testing.assert_allclose(out[0, 0], want[t + 1], rtol=2e-5,
                                   atol=2e-5)
        np.testing.assert_allclose(out[1, 0], want[t], rtol=2e-5, atol=2e-5)


def test_half_split_pairs_are_interleaved_pairs_of_permuted_channels():
    """ops/rope.py keeps two pair layouts (the latent attention op rotates
    interleaved pairs, this op half-split ones): they are one rotation
    under a permutation of the channels."""
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 5, D))
    cos, sin = rope.cos_sin(jnp.arange(5), D, {"rope_theta": 1e4})
    perm = np.arange(D).reshape(2, D // 2).T.reshape(-1)  # [0, 4, 1, 5, ...]
    half = rope.rotate_half_split(x, cos, sin)
    inter = rope.rotate_interleaved(x[..., perm], cos, sin)
    np.testing.assert_allclose(inter, half[..., perm], rtol=1e-6, atol=1e-6)


def test_default_rope_table_by_hand():
    cos, sin = rope.cos_sin(jnp.asarray([0, 3]), 4, {"rope_theta": 100.0,
                                                      "rope_type": "default"})
    # pair j turns at theta^(-2j/dim): 1 and 0.1 rad a token
    np.testing.assert_allclose(cos[1], np.cos([3.0, 0.3]), rtol=1e-6)
    x = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    got = rope.rotate_half_split(x, cos[1], sin[1])
    c, s = np.cos([3.0, 0.3]), np.sin([3.0, 0.3])
    np.testing.assert_allclose(
        got, [1 * c[0] - 3 * s[0], 2 * c[1] - 4 * s[1],
              1 * s[0] + 3 * c[0], 2 * s[1] + 4 * c[1]], rtol=1e-6)


# -- kv_heads = heads, no rotation: what it was ---------------------------------
def _old_scores(q, kc, together):
    b, c, heads, d = q.shape
    if not together:
        return jnp.einsum("bqhd,bkhd->bhqk", q, kc.reshape(b, -1, heads, d),
                          preferred_element_type=jnp.float32)
    own = jnp.eye(heads, dtype=bool)[:, :, None]
    qb = jnp.where(own, q[:, :, :, None, :], 0)
    logits = jnp.einsum("bxe,bme->bxm", qb.reshape(b, c * heads, heads * d),
                        kc, preferred_element_type=jnp.float32)
    return logits.reshape(b, c, heads, -1).transpose(0, 2, 1, 3)


def _old_context(probs, vc, together):
    b, heads, c, m = probs.shape
    if not together:
        return jnp.einsum("bhqk,bkhd->bqhd", probs,
                          vc.reshape(b, m, heads, -1))
    own = jnp.eye(heads, dtype=bool)[:, :, None]
    wide = jnp.einsum("bxm,bme->bxe",
                      probs.transpose(0, 2, 1, 3).reshape(b, c * heads, m),
                      vc).reshape(b, c, heads, heads, -1)
    return jnp.sum(jnp.where(own, wide, 0), axis=3)


@pytest.mark.parametrize("together", [True, False])
def test_full_heads_contract_bit_identically_to_before(together):
    key = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(key[0], (3, 2, HEADS, D))
    kc = jax.random.normal(key[1], (3, 20, HEADS * D))
    probs = jax.nn.softmax(jax.random.normal(key[2], (3, HEADS, 2, 20)), -1)
    np.testing.assert_array_equal(attn_mod._scores(q, kc, together),
                                  _old_scores(q, kc, together))
    np.testing.assert_array_equal(
        attn_mod._context(probs, kc, together, HEADS),
        _old_context(probs, kc, together))


def test_plain_attention_keeps_its_parameters_and_program():
    """`kv_heads = heads`, no rotation, multiplier 1: the op carries no new
    parameter (cost-cache and strategy keys are what they were) and lowers
    to the same program as a call that names none of them."""
    def build(**kw):
        config = ff.FFConfig()
        config.allow_mixed_precision = False
        m = ff.FFModel(config)
        x = m.create_tensor([2, 8, E])
        m.multihead_attention(x, x, x, E, HEADS, causal=True, name="attn",
                              **kw)
        return m, m.ops[-1]

    m0, plain = build()
    m1, named = build(kv_heads=HEADS, rope_parameters=None,
                      key_multiplier=1.0)
    assert plain.params == named.params
    assert not {"kv_heads", "rope_parameters",
                "key_multiplier"} & set(plain.params)
    w = {**_weights(HEADS), "bq": jnp.zeros((HEADS, D)),
         "bk": jnp.zeros((HEADS, D)), "bv": jnp.zeros((HEADS, D)),
         "bo": jnp.zeros((E,))}
    x = jnp.zeros((2, 8, E))
    hlo = lambda m, op: jax.jit(
        lambda x, w: _lower(m, op, x, w)[0]).lower(x, w).as_text()
    assert hlo(m0, plain) == hlo(m1, named)


def test_flash_and_sequence_parallel_refuse_grouped_or_rotated():
    config = ff.FFConfig()
    m = ff.FFModel(config)
    x = m.create_tensor([2, 8, E])
    with pytest.raises(ValueError, match="use_flash"):
        m.multihead_attention(x, x, x, E, HEADS, kdim=D, vdim=D, kv_heads=2,
                              use_flash=True)
    with pytest.raises(ValueError, match="sequence_parallel"):
        m.multihead_attention(x, x, x, E, HEADS, kdim=D, vdim=D,
                              rope_parameters={"rope_theta": 1e4},
                              sequence_parallel=True)
    with pytest.raises(ValueError, match="multiple"):
        m.multihead_attention(x, x, x, E, HEADS, kdim=D, vdim=D, kv_heads=4)
