"""Latent attention (ops/latent_attention.py, ops/rope.py), the dropless
gated experts told which experts they hold (ops/moe.py) and the latent
serving cache (serving/sched/kvpool.py), against the plain reference the
benchmark keeps (benchmark/reference/mla_moe_lm.py) at a small size:
hidden 64, 4 heads, q 32 / kv 16 / nope 8 / rope 8 / v 16, 8 experts top-2
plus a shared expert, 2 layers, vocabulary 128."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flexflow_tpu as ff
from benchmark import harness
from benchmark.configs import mla_moe_lm as builder
from benchmark.metrics import mla_moe_shapes as shapes
from benchmark.reference import mla_moe_lm as ref
from flexflow_tpu.core.op import LoweringContext
from flexflow_tpu.ffconst import CompMode
from flexflow_tpu.kernels.pallas import latent_decode
from flexflow_tpu.kernels.registry import KERNELS
from flexflow_tpu.ops import moe, rope
from flexflow_tpu.ops.latent_attention import (LatentAttentionOp, _wide_add,
                                               wide_count)
from flexflow_tpu.ops.moe import GatedExpertsOp, gated_experts_oracle
from flexflow_tpu.serving.sched import kvpool
from flexflow_tpu.serving.sched.continuous import ContinuousBatcher
from tests.conftest import module_xla_cache

_xla_cache = pytest.fixture(scope="module", autouse=True)(module_xla_cache)

SEED = 2**31 + 77
REAL = harness.load_config("mistral_small4_ep4")
ROPE = REAL["rope_parameters"]


def tiny_cfg(**over):
    cfg = dict(REAL, num_hidden_layers=2, hidden_size=64,
               num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
               qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16,
               moe_intermediate_size=32, router_width=8, n_routed_experts=8,
               first_local_expert=0, num_experts_per_tok=2, vocab_size=128,
               tensor_dtype="float32")
    cfg["deployment"] = dict(REAL["deployment"], declared_batch=1, window=32,
                             num_slots=3, max_len=64, page_size=8,
                             prefill_chunk_tokens=16, max_queue=64)
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def lm():
    cfg = tiny_cfg()
    return cfg, builder.build_model(cfg, SEED)


def ref_logits(cfg, tokens):
    """(T, V) float32 logits of the reference's one causal pass, padded to
    its query block."""
    n = len(tokens)
    padded = np.zeros((ref.pad_length(n, n),), np.int32)
    padded[:n] = tokens
    logits, _ = ref.forward(lambda g: builder.make_group(cfg, SEED, g), cfg,
                            [padded], ["float32"])
    return np.asarray(logits["float32"][0])[:n]


def test_forward_logits_match_the_reference(lm):
    cfg, model = lm
    toks = np.random.default_rng(0).integers(0, 128, 32, dtype=np.int32)
    values, _, _ = model.executor.forward_values(
        model.params, model.state, {model.input_ops[0].name: toks[None]},
        None, CompMode.COMP_MODE_INFERENCE)
    probs = np.asarray(values[model.final_tensor.guid])[0]
    want = jax.nn.softmax(ref_logits(cfg, toks), axis=-1)
    np.testing.assert_allclose(probs, want, rtol=2e-4, atol=1e-7)


def test_batcher_prefill_and_decode_match_one_causal_pass(lm):
    """Chunked prefill (prompts of one, two and three chunks, so chunk
    offsets > 0), decode through the latent cache, slots that start and end
    at different times: every served token is the reference's best at its
    position, to a rounding error."""
    cfg, model = lm
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 128, n, dtype=np.int32)
               for n in (5, 20, 37, 9, 16, 33)]
    cb = builder.build_batcher(model, cfg)
    with cb:
        reqs = [cb.submit(p, n) for p, n in zip(prompts, (9, 6, 12, 3, 7, 5))]
        outs = [r.result(timeout=300) for r in reqs]
        counts = cb.publish_op_counters()
    for p, out in zip(prompts, outs):
        z = ref_logits(cfg, np.concatenate([p, out]))
        rows = z[len(p) - 1:len(p) - 1 + len(out)]
        gap = rows.max(-1) - rows[np.arange(len(out)), out]
        assert gap.max() < 1e-4, gap
    # the layers counted their decode iterations, and dropped nothing
    assert all(c["dropped"] == 0.0 and c["steps"] > 0
               and c["assignments"] == 2 * 3 * c["steps"]
               for c in counts.values()), counts
    # every decode iteration of 3 slots took the few-rows form, and the
    # registry carries that count
    assert all(c["few_rows_steps"] == c["steps"] for c in counts.values())
    text = cb.registry.render()
    for name, c in counts.items():
        assert (f'ff_moe_few_rows_steps_total{{op="{name}"}} '
                f'{int(c["steps"])}\n') in text, text


def _mla_op(cfg, batch=2, length=6):
    config = ff.FFConfig()
    config.allow_mixed_precision = False
    m = ff.FFModel(config)
    x = m.create_tensor([batch, length, cfg["hidden_size"]])
    m.latent_attention(x, cfg["num_attention_heads"], cfg["q_lora_rank"],
                       cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                       cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                       rope_parameters=ROPE, name="attn")
    return m, m.ops[-1]


def _weights_of(op, seed=0, scale=0.3):
    key = jax.random.PRNGKey(seed)
    return {w._weight_spec.name: scale * jax.random.normal(
        jax.random.fold_in(key, i), w.dims, jnp.float32)
        for i, w in enumerate(op.weights)}


@pytest.mark.parametrize("start", [0, 8190])
def test_absorbed_decode_equals_expanded(start):
    """Token by token through the latent cache (absorbed, a vector of
    positions) = one expanded causal pass; from position 8,190 on the
    queries cross 8,192, where a(t) leaves 1."""
    cfg = tiny_cfg()
    b, n = 2, 6
    m, op = _mla_op(cfg, b, n)
    w = _weights_of(op)
    x = jax.random.normal(jax.random.PRNGKey(3), (b, n, 64), jnp.float32)
    rows = start + n

    def lower(x_in, cache, pos):
        ctx = LoweringContext(m.config, CompMode.COMP_MODE_INFERENCE)
        ctx.state[("attn", "c_kv")], ctx.state[("attn", "k_rope")] = cache
        ctx.decode_pos = pos
        out = op.lower(ctx, [x_in], w)[0]
        return out, (ctx.state_updates[("attn", "c_kv")],
                     ctx.state_updates[("attn", "k_rope")])

    # expanded: the chunk entry at offset `start` of an empty batch-1 cache
    # per row (earlier rows are zeros and masked by nothing, so feed them)
    zeros = lambda n: (jnp.zeros((n, rows, cfg["kv_lora_rank"])),
                       jnp.zeros((n, rows, 128)))
    want = []
    for i in range(b):
        out, _ = lower(x[i:i + 1], zeros(1), jnp.int32(start))
        want.append(out[0])
    # absorbed: one token a step, per-slot positions
    cache = zeros(b)
    got = []
    for j in range(n):
        out, cache = lower(x[:, j:j + 1], cache,
                           jnp.full((b,), start + j, jnp.int32))
        got.append(out[:, 0])
    if start:   # rows < start hold zeros on both sides: same softmax mass
        assert float(rope.position_scale(jnp.array([start + n - 1]), ROPE)[0]
                     ) == pytest.approx(1 + 0.1 * math.log(2))
    np.testing.assert_allclose(np.stack(got, 1), np.stack(want), rtol=2e-5,
                               atol=2e-6)


def _chunk_lower(m, op, w):
    """Jitted: C queries at the scalar offset `pos` of a batch-1 holder ->
    (output, the holder as the chunk leaves it)."""
    def lower(x_in, cache, pos):
        ctx = LoweringContext(m.config, CompMode.COMP_MODE_INFERENCE)
        ctx.state[("attn", "c_kv")], ctx.state[("attn", "k_rope")] = cache
        ctx.decode_pos = pos
        out = op.lower(ctx, [x_in], w)[0]
        return out, (ctx.state_updates[("attn", "c_kv")],
                     ctx.state_updates[("attn", "k_rope")])
    return jax.jit(lower)


# (holder rows, chunk C, the offsets of one stream of chunks): with the
# ladder's floor at 1 a holder of 37 rows is attended in [C, 2C, ...] and
# the whole. `rows` is the prefix each chunk must take.
_PREFIX_CASES = {
    # C = 4 over [4, 8, 16, 32, 37]: pos + C on the edge 8, one row under
    # it, one past it; the whole holder's last row
    "on_edge": (37, 4, [4], [8]),
    "one_under_edge": (37, 4, [3], [8]),
    "one_past_edge": (37, 4, [5], [16]),
    "holders_last_row": (37, 4, [33], [37]),
    # a prompt of 1, 2 and 4 chunks of 8 over [8, 16, 32, 37]
    "prompt_of_1_chunk": (37, 8, [0], [8]),
    "prompt_of_2_chunks": (37, 8, [0, 8], [8, 16]),
    "prompt_of_4_chunks": (37, 8, [0, 8, 16, 24], [8, 16, 32, 32]),
    # a holder of at most two bases is one branch, the whole
    "one_branch_holder": (15, 8, [0, 7], [15, 15]),
}


@pytest.mark.parametrize("case", sorted(_PREFIX_CASES))
def test_a_chunk_attends_the_shortest_prefix_of_the_holder(case,
                                                           monkeypatch):
    """A stream of chunks through the scalar-offset entry, each over the
    shortest prefix of the holder that holds its last query, equals the
    same stream over the whole holder: the outputs and the holder the
    chunks leave (written whole, every row past them as the previous
    tenant left it) to float32 rounding; the host's `prefill_prefix_rows`
    names the prefix each takes."""
    from flexflow_tpu.ops import latent_attention as mla

    rows, c, offsets, prefixes = _PREFIX_CASES[case]
    cfg = tiny_cfg()
    m, op = _mla_op(cfg, 1, c)
    w = _weights_of(op)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, rows + c, 64),
                          jnp.float32)
    # a previous tenant's rows: finite, so a row read by mistake shows
    start = (jnp.full((1, rows, cfg["kv_lora_rank"]), 3.0),
             jnp.full((1, rows, 128), 3.0))
    got = {}
    for floor in (1, 10**6):
        monkeypatch.setattr(mla, "PREFILL_PREFIX_FLOOR", floor)
        step, cache, outs = _chunk_lower(m, op, w), start, []
        for off in offsets:
            out, cache = step(x[:, off:off + c], cache, jnp.int32(off))
            outs.append(out)
        got[floor] = (outs, cache)
        if floor == 1:
            assert [op.prefill_prefix_rows(rows, off, c)
                    for off in offsets] == prefixes
        else:
            assert {op.prefill_prefix_rows(rows, off, c)
                    for off in offsets} == {rows}
    (bounded, held), (whole, held_whole) = got[1], got[10**6]
    for a, b in zip(bounded, whole):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)
    for a, b in zip(held, held_whole):
        np.testing.assert_array_equal(a, b)
    end = offsets[-1] + c
    np.testing.assert_array_equal(held[0][0, end:], 3.0)


def test_prefill_ladder_from_shapes_alone():
    """The ladder of `mistral_small4_ep4`'s batch-1 holder (4,096 rows and
    511 of slack) for its chunks of 512; a step of one query (the lockstep
    session) starts at the floor, not at 1; the dense op's ladder keeps
    its own base."""
    from flexflow_tpu.ops import attention as attn_mod
    from flexflow_tpu.ops.latent_attention import prefill_ladder

    assert prefill_ladder(4607, 512) == [512, 1024, 2048, 4096, 4607]
    assert prefill_ladder(4607, 1) == [512, 1024, 2048, 4096, 4607]
    assert prefill_ladder(4607, 1024) == [1024, 2048, 4096, 4607]
    assert prefill_ladder(1024, 1) == [1024] and prefill_ladder(319, 64) == [
        319]
    assert attn_mod._prefixes(4607) == [2048, 4096, 4607]
    _, op = _mla_op(tiny_cfg(), 1, 512)
    # decode_sat_4k's prompt quantiles: 15 chunks, none over the whole
    chunks = [op.prefill_prefix_rows(4607, off, 512)
              for n in (152, 215, 304, 431, 609, 861, 1218, 1722)
              for off in range(0, n, 512)]
    assert {r: chunks.count(r) for r in set(chunks)} == {
        512: 8, 1024: 4, 2048: 3}


def test_lockstep_decode_attends_a_prefix_of_the_cache(lm, monkeypatch):
    """`GenerativeSession`'s step (one query at a scalar offset) over a
    ladder of 4, 8, 16, 32 and 64 rows decodes the tokens it decodes over
    the whole cache, each the reference's best to a rounding error."""
    from flexflow_tpu.ops import latent_attention as mla
    from flexflow_tpu.serving.generate import GenerativeSession

    cfg, model = lm
    prompt = np.random.default_rng(3).integers(0, 128, (1, 11),
                                               dtype=np.int32)
    tokens = {}
    for floor in (4, 10**6):
        monkeypatch.setattr(mla, "PREFILL_PREFIX_FLOOR", floor)
        tokens[floor] = GenerativeSession(model, 64).generate(prompt, 40)[0]
    np.testing.assert_array_equal(tokens[4], tokens[10**6])
    z = ref_logits(cfg, np.concatenate([prompt[0], tokens[4]]))
    rows = z[10:10 + 40]
    gap = rows.max(-1) - rows[np.arange(40), tokens[4]]
    assert gap.max() < 1e-4, gap


def test_batcher_counts_prefill_chunks_by_the_prefix_they_attend(
        lm, monkeypatch):
    """Chunks of 16 on batch-1 holders of 64 + 15 rows, the ladder's floor
    at 16 ([16, 32, 64, 79]): a known prompt mix lands in
    `ff_mla_prefill_chunks_total` by the prefix each chunk is attended
    over, for each latent op, and the served tokens are the reference's
    best."""
    from flexflow_tpu.ops import latent_attention as mla

    monkeypatch.setattr(mla, "PREFILL_PREFIX_FLOOR", 16)
    cfg, model = lm
    rng = np.random.default_rng(9)
    # chunks at offsets 0 | 0, 16 | 0, 16, 32 | 0, 16, 32, 48
    prompts = [rng.integers(0, 128, n, dtype=np.int32)
               for n in (5, 20, 37, 56)]
    want = {"16": 4, "32": 3, "64": 3}
    with builder.build_batcher(model, cfg) as cb:
        outs = [cb.submit(p, 6).result(timeout=300) for p in prompts]
        text = cb.registry.render()
    for name in ("l0_attn", "l1_attn"):
        for rows, n in want.items():
            assert (f'ff_mla_prefill_chunks_total{{op="{name}",'
                    f'rows="{rows}"}} {n}\n') in text, text
    assert 'rows="79"' not in text
    for p, out in zip(prompts, outs):
        z = ref_logits(cfg, np.concatenate([p, out]))
        rows = z[len(p) - 1:len(p) - 1 + len(out)]
        gap = rows.max(-1) - rows[np.arange(len(out)), out]
        assert gap.max() < 1e-4, gap


# positions of 8 slots over a cache of 4 blocks of 32 rows: the first row, a
# block's last and the next block's first, the cache's last, an idle slot's
# dummy position (0 again), the middle of a block, and a block boundary
_POS = (0, 31, 32, 127, 0, 77, 63, 64)


@pytest.mark.parametrize("compute,stored,tol", [
    ("float32", "float32", 2e-5), ("bfloat16", "bfloat16", 2e-2),
    ("float32", "bfloat16", 2e-5)])
def test_latent_decode_kernel_equals_the_absorbed_reference(
        compute, stored, tol, monkeypatch):
    """The kernel (interpret mode) against `_absorbed`'s two contractions at
    the published widths of one layer (32 heads, kv 256, rope 64 in 128
    lanes, nope 64, v 128), ragged positions over several blocks. The
    kernel's caches hold NaN in EVERY row past a slot's position: none
    reaches the output, so the blocks past it are not computed and the
    block that holds it is masked in scores and in context."""
    monkeypatch.setattr(latent_decode, "BLOCK_ROWS", 32)
    cfg = dict(REAL, hidden_size=64)          # the core does not see it
    _, op = _mla_op(cfg, len(_POS), 1)
    cdt, sdt = jnp.dtype(compute), jnp.dtype(stored)
    b, m, heads = len(_POS), 128, 32
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    q_nope = jax.random.normal(ks[0], (b, 1, heads, 64), cdt)
    q_rope = jnp.pad(jax.random.normal(ks[1], (b, 1, heads, 64), cdt),
                     ((0, 0),) * 3 + ((0, 64),))
    c_kv = jax.random.normal(ks[2], (b, m, 256), sdt)
    k_rope = jax.random.normal(ks[3], (b, m, 128), sdt)
    w = {"wkv_b": 0.1 * jax.random.normal(ks[4], (256, heads, 192), cdt)}
    pos = jnp.asarray(_POS, jnp.int32)
    qscale = 0.07 * (1.0 + 0.1 * jnp.arange(b, dtype=jnp.float32)
                     )[:, None, None, None]
    want = op._absorbed(q_nope, q_rope, c_kv, k_rope, pos[:, None], qscale,
                        w, cdt, False)
    past = jnp.arange(m)[None, :, None] > pos[:, None, None]
    got = op._absorbed(q_nope, q_rope, jnp.where(past, jnp.nan, c_kv),
                       jnp.where(past, jnp.nan, k_rope), pos[:, None],
                       qscale, w, cdt, True)
    assert got.shape == want.shape == (b, 1, heads, 128)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol,
                               atol=tol)


def test_latent_decode_blocks_divide_the_cache():
    """One block for a short cache, else the largest block of whole 16-row
    tiles that divides it; none, and so the reference, where there is no
    such block."""
    rows = latent_decode.block_rows
    assert [rows(n) for n in (64, 512, 4096, 1536, 4096 + 16)] == [
        64, 512, 512, 512, 16]
    assert rows(1000) is None and rows(520) is None
    pos = jnp.asarray([0, 511, 512, 4095], jnp.int32)
    assert int(latent_decode.rows_read(pos, 4096)) == 512 * (1 + 1 + 2 + 8)


@pytest.mark.parametrize("impl", ["pallas", "reference"])
def test_latent_decode_counts_rows_filled_and_rows_read(impl, monkeypatch):
    """One decode step through `lower()` on either side of the family:
    `rows_filled` is what the sequences hold, `rows_read` what the core
    that ran fetched (whole blocks up to the position; every allocated
    row), and the wide counters carry past 2**20."""
    monkeypatch.setattr(latent_decode, "BLOCK_ROWS", 16)
    cfg = tiny_cfg()
    b, m = 4, 64
    mdl, op = _mla_op(cfg, b, 1)
    w = _weights_of(op)
    x = jax.random.normal(jax.random.PRNGKey(3), (b, 1, 64), jnp.float32)
    pos = jnp.asarray([0, 15, 16, 63], jnp.int32)
    near = jnp.asarray([3, 2**20 - 5], jnp.int32)    # 3 * 2**20 + 2**20 - 5
    ctx = LoweringContext(mdl.config, CompMode.COMP_MODE_INFERENCE)
    ctx.state[("attn", "c_kv")] = jnp.zeros((b, m, 16))
    ctx.state[("attn", "k_rope")] = jnp.zeros((b, m, 128))
    ctx.state[("attn", "attn_steps")] = jnp.int32(7)
    ctx.state[("attn", "rows_filled")] = near
    ctx.state[("attn", "rows_read")] = near
    ctx.decode_pos = pos
    with KERNELS.override("latent_decode", impl):
        op.lower(ctx, [x], w)
    start = wide_count(near)
    assert start == 4 * 2**20 - 5
    assert int(ctx.state_updates[("attn", "attn_steps")]) == 8
    filled = wide_count(ctx.state_updates[("attn", "rows_filled")])
    read = wide_count(ctx.state_updates[("attn", "rows_read")])
    assert filled - start == 1 + 16 + 17 + 64
    assert read - start == (16 * (1 + 1 + 2 + 4) if impl == "pallas"
                            else b * m)
    assert LatentAttentionOp.serving_counters == (
        "attn_steps", "rows_filled", "rows_read")
    big = _wide_add(jnp.asarray([2**30, 0], jnp.int32), 2**30)
    assert wide_count(big) == 2**50 + 2**30     # far past what int32 holds


def test_batcher_decodes_the_same_tokens_on_both_sides_of_latent_decode(
        lm, monkeypatch):
    """`ContinuousBatcher` in float32 with the family forced each way:
    the same greedy tokens, and counters that add up — one request at a
    time, so every decode step has one live slot and two idle ones at
    their dummy position 0."""
    monkeypatch.setattr(latent_decode, "BLOCK_ROWS", 16)
    cfg, model = lm
    rng = np.random.default_rng(5)
    jobs = [(rng.integers(0, 128, 21, dtype=np.int32), 14),
            (rng.integers(0, 128, 9, dtype=np.int32), 30)]
    slots, max_len = 3, cfg["deployment"]["max_len"]
    steps = sum(n - 1 for _, n in jobs)     # the first token is prefill's
    filled = sum(len(p) + k + 1 for p, n in jobs for k in range(n - 1)) \
        + (slots - 1) * steps
    blocks = sum((len(p) + k) // 16 + 1 for p, n in jobs
                 for k in range(n - 1)) + (slots - 1) * steps
    tokens = {}
    for impl in ("pallas", "reference"):
        with KERNELS.override("latent_decode", impl), \
                builder.build_batcher(model, cfg) as cb:
            tokens[impl] = [cb.submit(p, n).result(timeout=300)
                            for p, n in jobs]
            cb.publish_op_counters()
            got = cb.op_counters()
            text = cb.registry.render()
        for name in ("l0_attn", "l1_attn"):
            c = got[name]
            read = (16 * blocks if impl == "pallas"
                    else steps * slots * max_len)
            assert (int(c["attn_steps"]), wide_count(c["rows_filled"]),
                    wide_count(c["rows_read"])) == (steps, filled, read)
            assert f'ff_mla_rows_filled_total{{op="{name}"}} {filled}\n' \
                in text, text
            assert f'ff_mla_rows_read_total{{op="{name}"}} {read}\n' in text
    for a, b in zip(tokens["pallas"], tokens["reference"]):
        assert np.array_equal(a, b)


def test_rope_table_by_hand():
    """YaRN's blended frequencies, the interleaved rotation and the
    position scaling against numbers worked out by hand from the published
    rope_parameters (theta 10,000, dim 64, factor 128, original 8,192,
    beta_fast 32, beta_slow 1)."""
    f = rope.inv_freq(64, ROPE)
    # correction range: dim * ln(ctx / (turns 2 pi)) / (2 ln theta)
    lo = math.floor(64 * math.log(8192 / (32 * 2 * math.pi)) / (2 * math.log(1e4)))
    hi = math.ceil(64 * math.log(8192 / (1 * 2 * math.pi)) / (2 * math.log(1e4)))
    assert (lo, hi) == (12, 25)
    base = lambda j: 1e4 ** (-2 * j / 64)
    assert f[0] == pytest.approx(1.0) and f[12] == pytest.approx(base(12))
    assert f[31] == pytest.approx(base(31) / 128)
    mid = (18 - lo) / (hi - lo)      # pair 18: part extrapolated, part not
    assert f[18] == pytest.approx(base(18) * (1 - mid) + base(18) / 128 * mid)
    assert rope.table_scale(ROPE) == pytest.approx(1.0)
    m = 0.1 * math.log(128) + 1
    assert rope.attention_scale(128, ROPE) == pytest.approx(128 ** -0.5 * m * m)
    # a(t): 1 below 8,192; 1 + 0.1 ln 2 at 8,192..16,383; 1 + 0.1 ln 3 after
    a = rope.position_scale(jnp.array([0, 8191, 8192, 16384]), ROPE)
    np.testing.assert_allclose(a, [1, 1, 1 + 0.1 * math.log(2),
                                   1 + 0.1 * math.log(3)], rtol=1e-6)
    # position 9,000, pair 12 of an interleaved vector (x[24], x[25])
    x = jnp.arange(64, dtype=jnp.float32)
    cos, sin = rope.cos_sin(jnp.array([9000]), 64, ROPE)
    y = np.asarray(rope.rotate_interleaved(x[None], cos, sin))[0]
    ang = 9000 * base(12)
    assert y[24] == pytest.approx(24 * math.cos(ang) - 25 * math.sin(ang), rel=1e-3)
    assert y[25] == pytest.approx(24 * math.sin(ang) + 25 * math.cos(ang), rel=1e-3)
    # and the reference's own table agrees
    np.testing.assert_allclose(ref.inv_freq(64, ROPE), f, rtol=1e-12)


def _experts_op(cfg, local, tokens=24):
    config = ff.FFConfig()
    config.allow_mixed_precision = False
    m = ff.FFModel(config)
    x = m.create_tensor([tokens, cfg["hidden_size"]])
    w, idx = m.moe_router(x, cfg["router_width"], cfg["num_experts_per_tok"],
                          name="router")
    m.gated_experts(x, w, idx, cfg["router_width"],
                    cfg["moe_intermediate_size"], local_experts=local,
                    name="experts")
    return m, m.ops[-2], m.ops[-1]


def _lower(m, op, ins, weights):
    ctx = LoweringContext(m.config, CompMode.COMP_MODE_INFERENCE)
    return op.lower(ctx, ins, weights)


# name -> (token rows, the (ids, weights) the experts are handed; None: the
# router's own[, the geometry: experts in all, a token, (first, count) held]).
# Unless it says otherwise the op holds experts 2..5 of 8, top-2.
_NARROW, _DECODE = (8, 2, (2, 4)), (256, 8, (0, 256))
_SKEWS = {
    # as the router assigns: about half of the assignments are absent
    "router": (24, None),
    # all 24 x 2 assignments of a step on ONE expert (a capacity of
    # ceil(alpha k T / n) would have dropped most): the grouped form has one
    # group of 48 rows, the dense form 3 experts whose weight is 0 everywhere
    "one_expert": (24, lambda t: (np.full((t, 2), 3), np.full((t, 2), 0.5))),
    # expert 4 gets no token: an empty group between two full ones
    "empty_expert": (24, lambda t: (
        np.stack([np.full(t, 3), np.where(np.arange(t) % 2, 5, 2)], 1),
        np.full((t, 2), 0.5))),
    # every assignment on an absent expert: the output is exactly 0
    "all_absent": (24, lambda t: (
        np.stack([np.arange(t) % 2, 6 + np.arange(t) % 2], 1),
        np.full((t, 2), 0.5))),
    "one_token": (1, None),
    "decode_128": (128, None),
    # `lgx_decode_sat`'s decode step at narrow matrices: 40 token rows x 8
    # over 256 HELD experts (all of them), so a step's 320 assignments miss
    # a good part of them (~183 hit)
    "decode_40x8_on_256": (40, None, _DECODE),
    "decode_one_of_256": (40, lambda t: (np.full((t, 8), 3),
                                         np.full((t, 8), 0.125)), _DECODE),
    # every other expert empty: a tile spans groups nobody chose
    "decode_every_other_empty": (40, lambda t: (
        2 * ((np.arange(t)[:, None] * 8 + np.arange(8)) % 128),
        np.full((t, 8), 0.125)), _DECODE),
    # fewer assignments than experts: 32 on 256, and 8
    "decode_4x8_on_256": (4, None, _DECODE),
    "decode_1x8_on_256": (1, None, _DECODE),
}
_COUNTERS = ("assignments", "experts_hit", "steps", "load", "few_rows_steps")


def _experts_case(case, path, monkeypatch):
    """(model, op, inputs [x, w, idx], weights, (first, count) held) of one
    case, the op steered
    down `path` ('few_rows' | 'grouped' | 'tiled') through the predicate's
    threshold; 'tiled' is few rows with the registry's family forced to the
    Pallas grouped matmul (interpreted here)."""
    from flexflow_tpu.kernels.registry import KERNELS

    tokens, forced, (total, k, local) = (*_SKEWS[case], _NARROW)[:3]
    monkeypatch.setattr(moe, "FEW_ROWS_MAX", 0 if path == "grouped" else 10**9)
    if path == "tiled":
        monkeypatch.setitem(KERNELS._overrides, "grouped_experts", "pallas")
    cfg = tiny_cfg(router_width=total, num_experts_per_tok=k)
    m, router, experts = _experts_op(cfg, local, tokens)
    x = jax.random.normal(jax.random.PRNGKey(0), (tokens, 64), jnp.float32)
    if forced is None:
        w, idx = _lower(m, router, [x], _weights_of(router, 1))
        assert np.allclose(np.asarray(w).sum(-1), 1.0, atol=1e-6)
    else:
        idx, w = forced(tokens)
        idx, w = jnp.asarray(idx, jnp.int32), jnp.asarray(w, jnp.float32)
    return m, experts, [x, w, idx], _weights_of(experts, 2), local


@pytest.mark.parametrize("path", ["few_rows", "grouped", "tiled"])
@pytest.mark.parametrize("case", list(_SKEWS))
def test_grouped_experts_equal_the_masked_oracle_and_drop_nothing(
        case, path, monkeypatch):
    """All forms of the routed product, on skews they treat differently —
    among them a decode step's 40 rows x 8 over 256 held experts, which
    miss a good part of them —, equal the masked oracle; the four counters
    read the same on each, and `few_rows_steps` counts the few-rows form
    alone."""
    m, experts, ins, ew, (first, count) = _experts_case(case, path,
                                                        monkeypatch)
    x, w, idx = ins
    ctx = LoweringContext(m.config, CompMode.COMP_MODE_INFERENCE)
    before = {"assignments": 7, "experts_hit": 5, "steps": 3,
              "load": np.zeros(count, np.int32), "few_rows_steps": 2}
    for name, val in before.items():
        ctx.state[("experts", name)] = jnp.asarray(val, jnp.int32)
    got = experts.lower(ctx, ins, ew)[0]
    want = gated_experts_oracle(x, w, idx, ew, first, count)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-5)
    local = np.asarray(idx).reshape(-1) - first
    load = np.bincount(local[(local >= 0) & (local < count)],
                       minlength=count)
    after = {k: np.asarray(ctx.state_updates[("experts", k)])
             for k in _COUNTERS}
    assert after["assignments"] == 7 + load.sum()
    assert after["experts_hit"] == 5 + (load > 0).sum()
    assert after["steps"] == 4
    np.testing.assert_array_equal(after["load"], load)
    assert after["few_rows_steps"] == 2 + (path == "few_rows")
    if case.startswith("decode_") and count == 256:
        assert (load > 0).sum() < count         # a good part is not hit
    if case == "all_absent":
        assert not np.asarray(got).any()
    if case == "one_expert":
        assert float(jnp.abs(got).min(axis=-1).max()) > 0  # no token zeroed


@pytest.mark.parametrize("path", ["few_rows", "grouped"])
@pytest.mark.parametrize("case", ["router", "empty_expert"])
def test_experts_gradients_equal_the_oracles(case, path, monkeypatch):
    """d/d(x, router weights, the three stacks) of a scalar of the output,
    through either form, is the oracle's."""
    m, experts, (x, w, idx), ew, _ = _experts_case(case, path, monkeypatch)
    probe = jax.random.normal(jax.random.PRNGKey(5), x.shape, jnp.float32)
    got = jax.grad(lambda x, w, ew: jnp.sum(
        probe * _lower(m, experts, [x, w, idx], ew)[0]), (0, 1, 2))(x, w, ew)
    want = jax.grad(lambda x, w, ew: jnp.sum(
        probe * gated_experts_oracle(x, w, idx, ew, 2, 4)), (0, 1, 2))(
            x, w, ew)
    for g, o in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, o, rtol=2e-4, atol=2e-5)


def test_few_rows_is_chosen_from_the_static_rows_alone():
    """A decode iteration's slots and a prefill chunk take the few-rows
    form, a long prefill or a training batch the grouped one; the op's
    counter says which ran."""
    assert moe.few_rows(1) and moe.few_rows(128) and moe.few_rows(
        moe.FEW_ROWS_MAX)
    assert not moe.few_rows(moe.FEW_ROWS_MAX + 1) and not moe.few_rows(8192)
    assert GatedExpertsOp.serving_counters == _COUNTERS


# (token rows, experts a token, experts held, experts in all, bytes of one
# matrix) -> the tiled grouped matmul or not: both sides of both crossings
_LGX, _MS4 = 2048 * 512 * 2, 4096 * 2048 * 2
_SMALL_EXPERTS = {
    "lgx-one-row": ((1, 8, 256, 256, _LGX), True),        # 3 % of them hit
    "lgx-8-slots": ((8, 8, 256, 256, _LGX), True),        # 22 %
    "lgx-decode-40": ((40, 8, 256, 256, _LGX), True),     # 71 %: 460 MB not
    "lgx-64-slots": ((64, 8, 256, 256, _LGX), True),      # 87 %: 217 MB
    "lgx-96-slots": ((96, 8, 256, 256, _LGX), False),     # 95 %: the tie
    "lgx-128-slots": ((128, 8, 256, 256, _LGX), False),   # 98 %: all read
    "lgx-at-the-ridge": ((256, 8, 256, 256, _LGX), False),
    "lgx-chunk-512": ((512, 8, 256, 256, _LGX), True),    # past the ridge
    "ms4-decode-128": ((128, 4, 32, 128, _MS4), False),   # 98 %, and 16 MiB
    "ms4-chunk-512": ((512, 4, 32, 128, _MS4), False),    # no one block
    "ms4-8-slots": ((8, 4, 32, 128, _MS4), False),        # 22 %, but 16 MiB
    # few bytes held: nothing worth a sort is left unread, whatever the share
    "four-held-of-256": ((1, 8, 4, 256, _LGX), False),
    "sixteen-all-hit": ((8, 8, 16, 16, _LGX), False),
    "sixteen-one-row": ((1, 8, 16, 16, _LGX), False),     # 60 % of 100 MB
}


@pytest.mark.parametrize("case", sorted(_SMALL_EXPERTS))
def test_small_experts_has_two_crossings(case):
    """`registry.small_experts` from static shapes alone: the tiled grouped
    matmul past the compute ridge, and far under it where the step's
    assignments are expected to leave more of the held experts' bytes
    unchosen than a sort is worth (`laguna_xs2_1chip`'s 40 decode rows, not
    128 slots of it, not `mistral_small4_ep4`'s 4096 x 2048 experts at any
    row count, not a handful of held experts)."""
    from flexflow_tpu.kernels import registry

    shape, tiled = _SMALL_EXPERTS[case]
    assert registry.small_experts(*shape) == tiled


@pytest.mark.parametrize("rows,k,total", [(40, 8, 256), (128, 4, 128),
                                          (128, 8, 256), (8, 8, 256)])
def test_expected_hit_share_is_a_uniform_routers(rows, k, total):
    """The rule's quantity against a count: `rows` tokens that each choose
    `k` distinct experts of `total` uniformly hit, on average, the expected
    share of them to a few percent (0.71 at `lgx_decode_sat`'s decode step,
    0.98 at `ms4_decode_sat`'s)."""
    from flexflow_tpu.kernels import registry

    chosen = np.random.default_rng(0).random((200, rows, total)).argsort(
        -1)[..., :k].reshape(200, -1)        # k distinct of `total` a row
    hit = np.mean([np.unique(step).size for step in chosen]) / total
    assert abs(hit - registry.expected_hit_share(rows * k, total)) < 0.02


@pytest.mark.parametrize("case,mode,backend,rows,tiled", [
    ("cpu-backend", CompMode.COMP_MODE_INFERENCE, "cpu", 512, False),
    ("tpu-chunk", CompMode.COMP_MODE_INFERENCE, "tpu", 512, True),
    ("tpu-decode-40", CompMode.COMP_MODE_INFERENCE, "tpu", 40, True),
    ("cpu-decode-40", CompMode.COMP_MODE_INFERENCE, "cpu", 40, False),
    ("tpu-decode-128", CompMode.COMP_MODE_INFERENCE, "tpu", 128, False),
    ("tpu-training-512", CompMode.COMP_MODE_TRAINING, "tpu", 512, False),
    ("tpu-training-40", CompMode.COMP_MODE_TRAINING, "tpu", 40, False),
    ("tpu-gspmd-40", CompMode.COMP_MODE_INFERENCE, "tpu-gspmd", 40, False),
    ("tpu-gspmd-512", CompMode.COMP_MODE_INFERENCE, "tpu-gspmd", 512, False),
])
def test_tiled_grouped_is_chosen_from_platform_shape_and_mode(
        monkeypatch, case, mode, backend, rows, tiled):
    """The Pallas grouped matmul takes a step of few rows on a TPU alone,
    on either side of `small_experts`' two crossings, and never a training
    step (rows of no group are unwritten: a gradient would read them) nor
    one that GSPMD partitions (it cannot split a Mosaic kernel)."""
    matrix = jax.ShapeDtypeStruct((256, 2048, 512), jnp.bfloat16)
    ctx = LoweringContext(ff.FFConfig(), mode)
    platform, _, gspmd = backend.partition("-")
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    if gspmd:
        monkeypatch.setattr(ctx, "gspmd_partitioned", lambda: True)
    assert GatedExpertsOp._tiled(ctx, rows, 8, 256, matrix,
                                 jnp.bfloat16) == tiled


def test_four_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    """The share test: each of 4 holders computes the routed part its own
    2 of the 8 experts give; those four parts, plus the shared expert
    counted once, are the reference's uncut layer."""
    cfg = tiny_cfg()
    lp = builder.make_group(cfg, SEED, "l0")
    x = jax.random.normal(jax.random.PRNGKey(5), (24, 64), jnp.float32)
    uncut, _ = ref.moe(x, lp, "l0", cfg, "float32")
    m, router, _ = _experts_op(cfg, (0, 8))
    w, idx = _lower(m, router, [x], lp["l0_router"])
    total = ref.gated_mlp(x, lp["l0_shared_gate"]["kernel"],
                          lp["l0_shared_up"]["kernel"],
                          lp["l0_shared_down"]["kernel"], "float32")
    for s in range(4):
        ms, _, experts = _experts_op(cfg, (2 * s, 2))
        mine = {k: v[2 * s:2 * s + 2] for k, v in lp["l0_experts"].items()}
        total = total + _lower(ms, experts, [x, w, idx], mine)[0]
    np.testing.assert_allclose(total, uncut, rtol=2e-5, atol=2e-6)


def _real_width_graph(layers):
    """The configuration at its published widths as a GRAPH (no weights are
    made): what the cache spec, flops() and the memory gate read."""
    from flexflow_tpu.core.graph import Graph

    cfg = dict(REAL, num_hidden_layers=layers)
    config = ff.FFConfig()
    config.batch_size = 1
    config.allow_mixed_precision = False
    config.num_devices = 1
    real_compile = ff.FFModel.compile
    try:        # build_model's calls, stopped before compile()
        ff.FFModel.compile = lambda self, **kw: (_ for _ in ()).throw(
            StopIteration(self))
        builder.build_model(cfg, 0)
    except StopIteration as stop:
        model = stop.args[0]
    finally:
        ff.FFModel.compile = real_compile
    model.graph = Graph(model.ops)
    return cfg, model


def test_real_width_cache_bytes_flops_and_memory_gate():
    from flexflow_tpu.analysis import plan_memory_bytes
    from flexflow_tpu.search.machine_model import make_machine_model

    cfg, model = _real_width_graph(6)
    # a token stores its 256-wide latent and its 64-wide rotary key, the
    # key padded to one 128-lane tile: 768 B a layer in bf16 as stored, of
    # which the algorithm reads 640 (the benchmark's roofline counts those)
    spec = kvpool.kv_cache_spec(model)
    assert [c.per_token for c in spec] == [{"c_kv": 256, "k_rope": 128}] * 6
    assert kvpool.kv_bytes_per_token(model) == 768 * 6
    d = shapes.dims(cfg)
    assert (d["kvr"] + d["rope"]) * d["cache_bytes"] == 640
    # flops() against the benchmark's hand counts (window 512, batch 1)
    ops = {op.name: op for op in model.ops}
    t = 512
    assert shapes.attention_params(cfg) == 28_049_408
    assert shapes.expert_params(cfg) == 25_165_824
    core = 2.0 * 32 * t * t * (64 + 64 + 128)
    assert ops["l0_attn"].flops() == 2.0 * t * shapes.attention_params(cfg) + core
    # uniform router: t x 4 x 32/128 assignments fall here
    assert ops["l0_experts"].flops() == 2.0 * t * shapes.expert_params(cfg)
    assert ops["l0_router"].flops() == 2.0 * t * 4096 * 128
    # the plan gate: 10.85 GB of bf16 weights fit the 16 GB chip at declared
    # batch 1 as an inference deployment; 9 layers (16 GB) do not
    machine = make_machine_model(model.config, 1)
    fits, _, _ = plan_memory_bytes(model.graph, machine, model.config,
                                   optimizer_state_factor=1.0)
    weights = sum(w.num_elements() * 2 for op in model.ops for w in op.weights)
    assert weights == pytest.approx(10.85e9, rel=0.01)
    assert weights <= fits <= weights + 0.3e9
    assert fits < machine.memory_budget_bytes()
    _, big = _real_width_graph(9)
    over, _, _ = plan_memory_bytes(big.graph, machine, big.config,
                                   optimizer_state_factor=1.0)
    assert over > machine.memory_budget_bytes()


def test_prefix_install_and_kv_export_import_round_trip_the_latent_cache(lm):
    """A prompt served again installs its pages from the prefix band (a
    latent array like any other); a parked request's latent rows exported
    from a prefill-role batcher and imported into a decode-role one
    continue token for token."""
    cfg, model = lm
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, 128, 37, dtype=np.int32)
    with builder.build_batcher(model, cfg) as cb:
        first = cb.submit(prompt, 8).result(timeout=300)
        again = cb.submit(prompt, 8)
        assert np.array_equal(again.result(timeout=300), first)
        assert again.prefix_tokens >= 32     # four pages of 8 were installed
    dep = cfg["deployment"]
    mk = lambda role: ContinuousBatcher(
        model, max_len=dep["max_len"], num_slots=2, page_size=dep["page_size"],
        prefill_chunk_tokens=dep["prefill_chunk_tokens"], role=role)
    with mk("prefill") as pre, mk("decode") as dec:
        h = pre.submit(prompt, 8)
        while not pre.parked_requests():
            assert not h.done()
        exp = pre.request_export(h).wait(timeout=60)
        assert set(exp["rows"]) == {f"l{i}_attn/{part}" for i in (0, 1)
                                    for part in ("c_kv", "k_rope")}
        assert exp["rows"]["l0_attn/c_kv"].shape == (37, 16)
        assert exp["rows"]["l0_attn/k_rope"].shape == (37, 128)
        got = dec.request_import(
            exp["desc"], exp["rows"], prompt, exp["last_tok"], 7).wait(60)
        rest = got.result(timeout=300)
        pre.release_parked(h)
    assert np.array_equal(np.concatenate([[exp["last_tok"]], rest]), first)
