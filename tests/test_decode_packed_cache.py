"""The decode step over the PACKED KV cache — (rows, max_len, heads*head_dim),
the shape the pool stores (serving/sched/kvpool.py `zero_kv_caches`) —
against a plain per-head jax.numpy attention that keeps its own
(rows, max_len, heads, head_dim) caches.

Every entry of `MultiHeadAttentionOp._decode_step` is covered: the
continuous batcher's vector form with one query a slot (the block-diagonal
contraction on the cache as stored) and with C = 4 (speculative verify,
one slot speculating past the cache's edge), the lockstep scalar form, and
the scalar chunk-offset prefill entry — at lane-multiple widths
(heads*head_dim = 128, 256) and at one that is not (96), through the einsum
chain and through the Pallas decode kernels in interpret mode. Between
them the cases contract all heads at once and head by head
(`_contract_heads_together`).
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.core.op import LoweringContext
from flexflow_tpu.ffconst import CompMode, OpType
from flexflow_tpu.kernels.registry import KERNELS
from flexflow_tpu.ops.attention import _contract_heads_together

B, M, EMBED = 3, 64, 24
# name -> (positions: vector or scalar, C)
FORMS = {
    "vector_c1": (np.array([0, 7, M - 1], np.int32), 1),
    # slot 2 speculates past the cache's edge: rows M-2, M-1 land, the
    # other two candidates are dropped
    "vector_c4_edge": (np.array([0, 5, M - 2], np.int32), 4),
    "scalar_c1": (np.int32(9), 1),
    "scalar_chunk_c48": (np.int32(4), 48),
}
# heads*head_dim -> (heads, head_dim)
WIDTHS = {128: (2, 64), 96: (3, 32), 256: (4, 64)}


def _attention_op(heads, head_dim, c):
    config = ff.FFConfig()
    config.batch_size = B
    config.allow_mixed_precision = False
    model = ff.FFModel(config)
    x = model.create_tensor([B, c, EMBED], ff.DataType.DT_FLOAT)
    model.multihead_attention(x, x, x, EMBED, heads, kdim=head_dim,
                              vdim=head_dim, causal=True, name="attn")
    op, = (o for o in model.ops if o.op_type == OpType.MULTIHEAD_ATTENTION)
    return config, op


def _reference(x, w, k4, v4, pos, scale):
    """Plain per-head attention over (B, M, h, d) caches: write the new
    rows (a row past the cache is dropped), attend rows <= pos + j."""
    hi = jax.lax.Precision.HIGHEST
    q, k, v = (jnp.einsum("ble,ehd->blhd", x, w["w" + n], precision=hi)
               + w["b" + n] for n in "qkv")
    c = x.shape[1]
    qpos = np.broadcast_to(np.asarray(pos).reshape(-1, 1), (B, 1)) \
        + np.arange(c)[None, :]                                   # (B, C)
    for b in range(B):
        for j in range(c):
            if qpos[b, j] < M:
                k4 = k4.at[b, qpos[b, j]].set(k[b, j])
                v4 = v4.at[b, qpos[b, j]].set(v[b, j])
    mask = np.arange(M)[None, None, :] <= qpos[:, :, None]        # (B, C, M)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k4, precision=hi) * scale
    probs = jax.nn.softmax(jnp.where(mask[:, None], logits, -1e30), axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v4, precision=hi)
    out = jnp.einsum("bqhd,hde->bqe", ctx, w["wo"], precision=hi) + w["bo"]
    return out, k4, v4, qpos


@pytest.mark.parametrize("kernel", ["einsum", "pallas"])
@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("form", sorted(FORMS))
def test_decode_step_on_packed_cache_matches_per_head_reference(
        form, width, kernel):
    pos, c = FORMS[form]
    heads, head_dim = WIDTHS[width]
    # all heads at once, but for the 48-query chunk of the 3- and 4-head
    # widths (144 and 192 query rows: more than one MXU pass), head by head
    assert _contract_heads_together(c, heads) == (c < 48 or heads == 2)
    config, op = _attention_op(heads, head_dim, c)
    rng = np.random.RandomState(26)
    f32 = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)
    w = {s.name: f32(*s.dims) * 0.3 for s in op.weight_specs()}
    x = f32(B, c, EMBED)
    k4, v4 = f32(B, M, heads, head_dim), f32(B, M, heads, head_dim)
    scale = 1.0 / np.sqrt(head_dim)
    want, want_k, want_v, qpos = _reference(x, w, k4, v4, pos, scale)

    ctx = LoweringContext(config, CompMode.COMP_MODE_INFERENCE)
    ctx.decode_pos = jnp.asarray(pos)
    ctx.state = {("attn", "k_cache"): k4.reshape(B, M, width),
                 ("attn", "v_cache"): v4.reshape(B, M, width)}
    with contextlib.ExitStack() as st:
        st.enter_context(jax.default_matmul_precision("highest"))
        for fam in ("attention_decode", "attention_decode_mq"):
            st.enter_context(KERNELS.override(
                fam, "pallas" if kernel == "pallas" else "reference"))
        got, = op.lower(ctx, [x, x, x], w)

    # a query past the cache's edge is never accepted: compare the rest
    live = qpos < M
    assert float(jnp.max(jnp.abs(got - want)[live])) <= 1e-5
    for part, ref in (("k_cache", want_k), ("v_cache", want_v)):
        new = ctx.state_updates[("attn", part)]
        assert new.shape == (B, M, width)  # stays as stored
        np.testing.assert_array_equal(
            np.asarray(new), np.asarray(ref.reshape(B, M, width)))
