"""A WINDOW on the dense attention op, its ring cache, the per-head gate,
partial rotation and the sigmoid-scored router (ops/attention.py,
ops/rope.py, ops/moe.py, serving/sched/kvpool.py), each against the plain
reference the benchmark keeps (benchmark/reference/swa_moe_lm.py), and a
tiny model of both layer kinds served through ContinuousBatcher — chunked
prefill, then decode through the rings — against that reference's full
forward pass."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flexflow_tpu as ff
from benchmark import harness
from benchmark.reference import swa_moe_lm as ref
from benchmark.tests.tiny_lgx import tiny_lgx_context
from flexflow_tpu.core.op import LoweringContext
from flexflow_tpu.ffconst import CompMode
from flexflow_tpu.ops import attention as attn_mod
from flexflow_tpu.ops import rope
from flexflow_tpu.serving.generate import GenerativeSession
from flexflow_tpu.serving.sched import kvpool
from flexflow_tpu.serving.sched.continuous import ContinuousBatcher
from tests.conftest import served_both_ways_counts_add_up

E, HEADS, KVH, D, W = 48, 6, 2, 8, 8
YARN = {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 16, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5}
PLAIN = {"rope_type": "default", "rope_theta": 10000,
         "partial_rotary_factor": 1}
CFG = {"num_key_value_heads": KVH, "head_dim": D, "sliding_window": W,
       "ring_rows": W, "rope_parameters": {"full_attention": YARN,
                                           "sliding_attention": PLAIN}}


@pytest.fixture(autouse=True)
def _true_float32():
    with jax.default_matmul_precision("highest"):
        yield


def _op(kind, length, batch=1):
    config = ff.FFConfig()
    config.allow_mixed_precision = False
    m = ff.FFModel(config)
    x = m.create_tensor([batch, length, E])
    m.multihead_attention(
        x, x, x, E, HEADS, kdim=D, vdim=D, bias=False, causal=True,
        kv_heads=KVH, rope_parameters=CFG["rope_parameters"][kind],
        window=W if kind == "sliding_attention" else 0, head_gate=True,
        name="attn")
    return m, m.ops[-1]


def _weights(seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    n = lambda key, *s: jax.random.normal(key, s) * 0.3
    return {"wq": n(k[0], E, HEADS, D), "wk": n(k[1], E, KVH, D),
            "wv": n(k[2], E, KVH, D), "wo": n(k[3], HEADS, D, E),
            "wg": n(k[4], E, HEADS)}


def _lower(m, op, x, w, caches=None, pos=None, fill=False, valid=None):
    ctx = LoweringContext(m.config, CompMode.COMP_MODE_INFERENCE)
    ctx.decode_pos, ctx.fill_kv_cache, ctx.valid_len = pos, fill, valid
    for k, v in (caches or {}).items():
        ctx.state[(op.name, k)] = v
    out = op.lower(ctx, [x, x, x], w)[0]
    return out, {k: ctx.state_updates.get((op.name, k), v)
                 for k, v in (caches or {}).items()}


def _garbage_ring(batch=1, rows=W):
    """A ring a previous tenant left full of large finite values: whatever
    reads a row it should not moves the output by far more than 1e-4."""
    return {k: jnp.full((batch, rows, KVH * D), 37.0)
            for k in ("k_cache", "v_cache")}


@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
def test_whole_sequence_entry_is_the_references_attention(kind):
    """The band mask, the gate and each layer kind's own rotation (YaRN on
    half the head with the table scale given outright; the default on the
    whole head), in one comparison with the reference."""
    length = 32
    m, op = _op(kind, length)
    w = _weights()
    x = jax.random.normal(jax.random.PRNGKey(1), (1, length, E))
    got, _ = _lower(m, op, x, w)
    want = ref.attention(x[0], w, kind, CFG, "float32")
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    # and the gate, the window and the partial rotation each move it
    for drop in ("wg",):
        other = ref.attention(x[0], {**w, drop: w[drop] * 0}, kind, CFG,
                              "float32")
        assert float(jnp.abs(other - want).max()) > 1e-2


@pytest.mark.parametrize("chunk", [3, W, 20])
def test_chunks_then_decode_through_the_ring_equal_the_whole_sequence(chunk):
    """Prefill in chunks smaller than, equal to and larger than the window
    on a batch-1 ring that starts full of a previous tenant's rows, the
    last chunk padded; install the ring into a slot of a 3-slot pool (full
    of garbage too); then decode one token at a time until the ring has
    wrapped more than twice. Every output row is the whole-sequence
    reference's, so no stale or unwritten row was ever read."""
    plen, total = 11, 11 + 2 * W + 5
    m, op = _op("sliding_attention", chunk)
    assert op.kv_ring_rows() == W
    w = _weights(2)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, total, E))
    want = ref.attention(x[0], w, "sliding_attention", CFG, "float32")
    small = _garbage_ring()
    off = 0
    while off < plen:
        n = min(chunk, plen - off)
        xs = jnp.zeros((1, chunk, E)).at[:, :n].set(x[:, off:off + n])
        out, small = _lower(m, op, xs, w, small, pos=jnp.int32(off),
                            valid=None if n == chunk else jnp.int32(n))
        np.testing.assert_allclose(out[0, :n], want[off:off + n], atol=2e-5)
        off += n
    pool = {k: kvpool.write_slot_span(v, small[k], 1)
            for k, v in _garbage_ring(batch=3).items()}
    m1, op1 = _op("sliding_attention", 1, batch=3)
    for p in range(plen, total):
        xs = jnp.zeros((3, 1, E)).at[1].set(x[0, p])
        pos = jnp.array([0, p, 0], jnp.int32)
        out, pool = _lower(m1, op1, xs, w, pool, pos=pos)
        np.testing.assert_allclose(out[1, 0], want[p], atol=2e-5)


@pytest.mark.parametrize("prefix_rows", [4, 10**6])
def test_a_chunk_at_an_offset_attends_a_prefix_of_a_long_cache(
        prefix_rows, monkeypatch):
    """A FULL layer's chunks on a batch-1 cache of 37 rows that a previous
    tenant left full: with prefixes of 4, 8, 16, 32 rows and the whole
    (each is taken by some chunk), and with the cache attended whole, every
    output row is the whole-sequence reference's and the cache holds the
    same rows."""
    monkeypatch.setattr(attn_mod, "PREFIX_ROWS", prefix_rows)
    rows, chunk, total = 37, 3, 36
    assert attn_mod._prefixes(rows) == (
        [4, 8, 16, 32, 37] if prefix_rows == 4 else [37])
    m, op = _op("full_attention", chunk)
    w = _weights(4)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, total, E))
    want = ref.attention(x[0], w, "full_attention", CFG, "float32")
    small = _garbage_ring(rows=rows)
    step = jax.jit(lambda xs, small, pos: _lower(m, op, xs, w, small,
                                                 pos=pos))
    for off in range(0, total, chunk):
        out, small = step(x[:, off:off + chunk], small, jnp.int32(off))
        np.testing.assert_allclose(out[0], want[off:off + chunk], atol=2e-5)
    assert float(jnp.abs(small["k_cache"][0, :total]).max()) < 30.0
    np.testing.assert_array_equal(small["k_cache"][0, total:], 37.0)


def test_prefixes_leave_a_short_cache_whole():
    """The benchmark's other served configurations (batch-1 holders of up
    to 2,048 rows and their slack) are attended whole, as before;
    `laguna_xs2_1chip`'s 12,799 rows in four lengths."""
    assert attn_mod._prefixes(2048) == [2048]
    assert attn_mod._prefixes(2 * attn_mod.PREFIX_ROWS) == [4096]
    assert attn_mod._prefixes(12799) == [2048, 4096, 8192, 12799]


def test_one_shot_fill_leaves_the_ring_holding_the_prompts_tail():
    """The whole-sequence entry with `fill_kv_cache`: of a padded prompt's
    rows only the real ones are placed, the last R at their ring places."""
    length, plen = 24, 19
    m, op = _op("sliding_attention", length)
    w = _weights(4)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, length, E))
    _, ring = _lower(m, op, x, w, _garbage_ring(), fill=True,
                     valid=jnp.int32(plen))
    _, linear = _lower(m, op, x, w,
                       {k: jnp.zeros((1, length, KVH * D))
                        for k in ("k_cache", "v_cache")},
                       pos=jnp.int32(0))          # chunk entry, ring of 24
    for part in ("k_cache", "v_cache"):
        for p in range(plen - W, plen):
            np.testing.assert_allclose(ring[part][0, p % W],
                                       linear[part][0, p], atol=1e-6)


def test_partial_rotation_and_the_table_scale():
    """`partial_rotary_factor` rotates the leading half of a head with the
    tables of a head that wide and passes the rest; `attention_factor`
    scales cos and sin; with neither the tables and the rotation are what
    they were."""
    pos = jnp.arange(40)
    x = jax.random.normal(jax.random.PRNGKey(0), (40, 3, 16))
    assert rope.rotary_dim(16, YARN) == 8 and rope.rotary_dim(16, None) == 16
    cos, sin = rope.cos_sin(pos, 8, YARN)
    rcos, rsin = ref.rope_tables(pos, 16, YARN)
    np.testing.assert_allclose(cos, rcos, rtol=1e-6)
    np.testing.assert_allclose(sin, rsin, rtol=1e-6, atol=1e-6)
    got = rope.rotate_half_split(x, cos[:, None], sin[:, None])
    np.testing.assert_allclose(got, ref.rotate(x, rcos, rsin), atol=1e-6)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    assert rope.table_scale(YARN) == YARN["attention_factor"]
    no_factor = {k: v for k, v in YARN.items() if k != "attention_factor"}
    assert rope.table_scale(no_factor) == pytest.approx(
        YARN["attention_factor"], rel=1e-6)       # 0.1 ln(64) + 1


def test_sigmoid_router_is_the_references():
    config = ff.FFConfig()
    config.allow_mixed_precision = False
    m = ff.FFModel(config)
    x = m.create_tensor([1, 10, 32])
    m.moe_router(x, 16, 4, scale=2.5, scoring="sigmoid", name="router")
    op = m.ops[-1]
    kernel = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
    xs = jax.random.normal(jax.random.PRNGKey(2), (1, 10, 32))
    ctx = LoweringContext(m.config, CompMode.COMP_MODE_INFERENCE)
    w, idx = op.lower(ctx, [xs], {"kernel": kernel})
    rw, ridx, _ = ref.route(xs[0] @ kernel, 4, 2.5)
    np.testing.assert_array_equal(idx[0], ridx)
    np.testing.assert_allclose(w[0], rw, rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(w, -1), 2.5, rtol=1e-6)
    with pytest.raises(ValueError, match="scoring"):
        m.moe_router(x, 16, 4, scoring="tanh")


def test_flash_and_sequence_parallel_refuse_a_window_and_a_gate():
    config = ff.FFConfig()
    m = ff.FFModel(config)
    x = m.create_tensor([1, 16, E])
    for extra in ({"window": 4}, {"head_gate": True}):
        with pytest.raises(ValueError, match="use_flash"):
            m.multihead_attention(x, x, x, E, HEADS, causal=True,
                                  use_flash=True, **extra)
    with pytest.raises(ValueError, match="causal"):
        m.multihead_attention(x, x, x, E, HEADS, window=4)


# -- a model of both layer kinds, served ------------------------------------
@pytest.fixture(scope="module")
def served():
    with jax.default_matmul_precision("highest"):
        # one period after the dense layer: both layer kinds and rotations
        ctx = tiny_lgx_context(tensor_dtype="float32", num_hidden_layers=5)
        cfg = {**ctx.config,
               "deployment": {**ctx.config["deployment"], **ctx.sizes}}
        builder = harness.module_of("configs", cfg["builder"])
        model = builder.build_model(cfg, 7)
        yield cfg, builder, model


def _gaps(cfg, builder, prompt, toks):
    """How far each served token lies below the reference's best, in
    logits, by the reference's full forward pass over prompt + served."""
    full = np.concatenate([prompt, toks])
    t = np.zeros(ref.pad_length(len(full), 128), np.int32)
    t[:len(full)] = full
    z = np.asarray(ref.forward(lambda n: builder.make_group(cfg, 7, n),
                               cfg, t))
    rows = np.arange(len(prompt) - 1, len(full) - 1)
    return z[rows].max(-1) - z[rows, toks]


@pytest.mark.parametrize("chunk", [4, 32])
def test_served_tokens_are_the_references_best(served, chunk):
    """Chunked prefill (chunks smaller and larger than the window of 8),
    then decode through the rings: a sequence shorter than the window, one
    whose ring wraps more than twice, and — ONE slot — each later request
    in the slot the one before it left (a reused slot answers as a fresh
    one). Float32 on both sides: every served token is the reference's own
    best, gap 0."""
    cfg, builder, model = served
    rng = np.random.default_rng(chunk)
    cb = ContinuousBatcher(model, max_len=128, num_slots=1, page_size=8,
                           prefill_chunk_tokens=chunk, prefix_cache_pages=0)
    assert cb._rings == {f"l{i}_swa": 8 for i in (1, 2, 3)}
    cb.start()
    try:
        for plen, new in ((3, 4), (40, 30), (5, 3)):
            prompt = rng.integers(0, 128, plen, dtype=np.int32)
            toks = np.asarray(cb.submit(prompt, new).result(timeout=300))
            assert np.all(_gaps(cfg, builder, prompt, toks) == 0.0)
    finally:
        cb.stop()


def test_full_layers_decode_kernel_beside_a_ring(monkeypatch):
    """A full layer and a window layer of 128-wide heads (the width the
    registry admits the decode kernel for), float32: with
    `attention_decode` forced each way the batcher serves the same tokens,
    the reference's own best; the full layer counts the rows it filled and
    read, the window layer — whose ring returns before the kernel choice —
    counts nothing."""
    from flexflow_tpu.kernels.pallas import latent_decode

    monkeypatch.setattr(latent_decode, "BLOCK_ROWS", 16)
    with jax.default_matmul_precision("highest"):
        ctx = tiny_lgx_context(tensor_dtype="float32", num_hidden_layers=2,
                               head_dim=128)
        cfg = {**ctx.config,
               "deployment": {**ctx.config["deployment"], **ctx.sizes}}
        builder = harness.module_of("configs", cfg["builder"])
        model = builder.build_model(cfg, 7)
        rng = np.random.default_rng(12)
        jobs = [(rng.integers(0, 128, 21, dtype=np.int32), 14),
                (rng.integers(0, 128, 9, dtype=np.int32), 30)]
        slots, max_len = 3, 64
        counts, tokens = served_both_ways_counts_add_up(
            lambda: ContinuousBatcher(
                model, max_len=max_len, num_slots=slots, page_size=8,
                prefill_chunk_tokens=12, prefix_cache_pages=0),
            jobs, ("l0_attn",), slots, max_len, 16)
        assert "l1_swa" not in counts
        for (p, _), toks in zip(jobs, tokens):
            assert np.all(_gaps(cfg, builder, p, toks) == 0.0)


def test_lockstep_session_equals_the_batcher(served):
    """GenerativeSession's one-shot fill and scalar-position steps through
    the same rings give the batcher's tokens."""
    cfg, builder, model = served
    prompt = np.random.default_rng(9).integers(0, 128, 21, dtype=np.int32)
    lock = GenerativeSession(model, max_len=128).generate(prompt[None], 25)
    cb = ContinuousBatcher(model, max_len=128, num_slots=2, page_size=8,
                           prefill_chunk_tokens=16, prefix_cache_pages=0)
    cb.start()
    try:
        toks = np.asarray(cb.submit(prompt, 25).result(timeout=300))
    finally:
        cb.stop()
    np.testing.assert_array_equal(lock[0], toks)


def test_ring_geometry_sizing_and_gauges(served):
    cfg, builder, model = served
    spec = {c.op: c for c in kvpool.kv_cache_spec(model)}
    assert spec["l1_swa"].ring == 8 and spec["l0_attn"].ring is None
    assert spec["l1_swa"].token_rows(128) == 8
    assert spec["l1_swa"].token_rows(5) == 5
    caches = kvpool.zero_kv_caches(model, 3, 128, slack=31)
    assert caches["l0_attn"]["k_cache"].shape == (3, 128 + 31, 32)
    assert caches["l1_swa"]["k_cache"].shape == (3, 8, 32)   # no slack
    # 2 full layers cost 2 x 32 float32 values a token; 3 rings 8 rows each
    assert kvpool.kv_bytes_per_token(model) == 2 * 2 * 32 * 4
    assert kvpool.ring_bytes_per_slot(model, 128) == 3 * 2 * 32 * 4 * 8
    assert kvpool.state_bytes_per_slot(model) == 0
    from flexflow_tpu.search.machine_model import ChipSpec, SimpleMachineModel

    big = SimpleMachineModel(1, ChipSpec())
    free = kvpool.derive_num_slots(model, 128, machine=big, max_slots=10**9)
    per_slot = (kvpool.kv_bytes_per_token(model) * 128
                + kvpool.ring_bytes_per_slot(model, 128))
    from flexflow_tpu.analysis import plan_memory_bytes

    used, _, _ = plan_memory_bytes(model.graph, big, model.config,
                                   optimizer_state_factor=1.0)
    assert free == (big.memory_budget_bytes() - used) // per_slot
    from flexflow_tpu.obs.registry import MetricsRegistry

    reg = MetricsRegistry()
    cb = ContinuousBatcher(model, max_len=128, num_slots=2, page_size=8,
                           registry=reg)
    assert cb.pool.prefix is None           # the default leaves it off
    text = reg.render()
    assert (f'ff_kvpool_ring_bytes_per_slot{{pool="{cb.pool.label}"}}'
            f" {3 * 2 * 32 * 4 * 8}") in text
    assert (f'ff_kvpool_ring_rows{{pool="{cb.pool.label}",op="l1_swa"}} 8'
            in text)


def test_what_a_ring_cannot_do_is_refused_typed(served):
    cfg, builder, model = served
    kw = dict(max_len=128, num_slots=2, page_size=8)
    with pytest.raises(kvpool.RingCacheUnsupported, match="prefix cache"):
        ContinuousBatcher(model, prefix_cache_pages=8, **kw)
    with pytest.raises(kvpool.RingCacheUnsupported, match="specul"):
        ContinuousBatcher(model, draft_model=model, **kw)
    for role in ("prefill", "decode"):
        with pytest.raises(kvpool.RingCacheUnsupported, match="export"):
            ContinuousBatcher(model, role=role, **kw)
    cb = ContinuousBatcher(model, **kw)
    with pytest.raises(kvpool.RingCacheUnsupported, match="resize") as e:
        cb.request_resize(4)
    assert e.value.op_name == "l1_swa"
    with pytest.raises(kvpool.RingCacheUnsupported, match="export"):
        cb.request_export(None)
    with pytest.raises(kvpool.RingCacheUnsupported, match="import"):
        cb.request_import({}, {}, np.zeros(3, np.int32), 0, 4)
    assert issubclass(kvpool.RingCacheUnsupported, ValueError)
    # several queries a slot on a ring (speculative verify) never traces
    m, op = _op("sliding_attention", 2, batch=2)
    with pytest.raises(NotImplementedError, match="ring"):
        _lower(m, op, jnp.zeros((2, 2, E)), _weights(), _garbage_ring(2),
               pos=jnp.zeros((2,), jnp.int32))


def test_no_window_leaves_the_programs_as_they_were():
    """`window`, `head_gate`, `partial_rotary_factor` and `scoring` at
    their defaults add no parameter to the op (the keys of cost caches and
    stored strategies stay) and no weight."""
    config = ff.FFConfig()
    m = ff.FFModel(config)
    x = m.create_tensor([1, 16, E])
    m.multihead_attention(x, x, x, E, HEADS, causal=True, name="a")
    op = m.ops[-1]
    assert "window" not in op.params and "head_gate" not in op.params
    assert [w_.name for w_ in op.weight_specs()][:4] == ["wq", "wk", "wv",
                                                         "wo"]
    assert "wg" not in [w_.name for w_ in op.weight_specs()]
    assert op.kv_ring_rows() is None
    m.moe_router(x, 8, 2, name="r")
    assert "scoring" not in m.ops[-1].params
    assert attn_mod._ring_positions(jnp.int32(-1), 4).max() < 0
