"""The Mamba-2 mixer (ops/ssm.py), per-sequence state in the serving pool
(serving/sched/kvpool.py, continuous.py) and the hybrid model the benchmark
builds of them, against the plain reference the benchmark keeps
(benchmark/reference/hybrid_ssm_lm.py: a sequential recurrence over tokens,
no blocks, no cache) at a small size: hidden 64, 4 query heads on 2 KV heads
of 16, a mixer of 4 heads x 16 channels with a 16-wide state in 2 groups,
blocks of 8, 2 layers, vocabulary 128."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flexflow_tpu as ff
from benchmark import harness
from benchmark.configs import hybrid_ssm_lm as builder
from benchmark.reference import hybrid_ssm_lm as ref
from flexflow_tpu.core.op import LoweringContext
from flexflow_tpu.ffconst import CompMode
from flexflow_tpu.ops.latent_attention import wide_count
from flexflow_tpu.serving.generate import GenerativeSession
from flexflow_tpu.serving.sched import kvpool
from flexflow_tpu.serving.sched.continuous import ContinuousBatcher
from tests.conftest import (module_xla_cache,
                            served_both_ways_counts_add_up)

_xla_cache = pytest.fixture(scope="module", autouse=True)(module_xla_cache)

SEED = 2**31 + 131
REAL = harness.load_config("falcon_h1_34b_1chip")


def tiny_cfg(**over):
    cfg = dict(REAL, num_hidden_layers=2, hidden_size=64,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               intermediate_size=128, mamba_d_ssm=64, mamba_n_heads=4,
               mamba_d_head=16, mamba_d_state=16, mamba_n_groups=2,
               mamba_chunk_size=8, vocab_size=128, tensor_dtype="float32",
               ssm_state_dtype="float32")
    cfg["deployment"] = dict(REAL["deployment"], declared_batch=1, window=32,
                             num_slots=3, max_len=64, page_size=8,
                             prefill_chunk_tokens=12, max_queue=64)
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def lm():
    cfg = tiny_cfg()
    return cfg, builder.build_model(cfg, SEED)


def ref_logits(cfg, tokens):
    """(T, V) float32 logits of the reference's one causal pass."""
    n = len(tokens)
    padded = np.zeros((ref.pad_length(n, n),), np.int32)
    padded[:n] = tokens
    group = lambda g: builder.make_group(cfg, SEED, g)
    x = ref.hidden_states(group, cfg, [padded], ["float32"])["float32"][0]
    return np.asarray(ref.head(x, group("head"), cfg, "float32"))[:n]


# -- the op alone --------------------------------------------------------------
def _mixer_op(cfg, batch, length, dtype="float32", state_dtype="float32"):
    config = ff.FFConfig()
    config.allow_mixed_precision = False
    m = ff.FFModel(config)
    x = m.create_tensor([batch, length, cfg["hidden_size"]],
                        ff.DataType(dtype))
    m.ssm_mixer(x, cfg["mamba_d_ssm"], cfg["mamba_n_heads"],
                cfg["mamba_d_state"], n_groups=cfg["mamba_n_groups"],
                d_conv=cfg["mamba_d_conv"], chunk_size=cfg["mamba_chunk_size"],
                slice_multipliers=cfg["ssm_multipliers"],
                eps=cfg["rms_norm_eps"], state_dtype=ff.DataType(state_dtype),
                name="l0_mixer")
    return m, m.ops[-1]


def _mixer_weights(cfg, dtype="float32"):
    w = builder.make_group(dict(cfg, tensor_dtype=dtype), SEED, "l0")
    return w["l0_mixer"]


def _lower(m, op, x, weights, state=None, pos=None, fill=False, valid=None):
    ctx = LoweringContext(m.config, CompMode.COMP_MODE_INFERENCE)
    ctx.decode_pos, ctx.fill_kv_cache, ctx.valid_len = pos, fill, valid
    for k, v in (state or {}).items():
        ctx.state[(op.name, k)] = v
    out = op.lower(ctx, [x], weights)[0]
    return out, {k: ctx.state_updates.get((op.name, k)) for k in (state or {})}


def _zero_state(op, batch, tail_dtype=jnp.float32):
    arrays = op.sequence_state_arrays()
    return {"ssm_state": jnp.zeros((batch,) + arrays["ssm_state"][0],
                                   jnp.float32),
            "conv_tail": jnp.zeros((batch,) + arrays["conv_tail"][0],
                                   tail_dtype)}


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize("dtype,tol", [
    # float32: the blocked form reorders float32 sums, nothing else
    ("float32", 1e-5),
    # bfloat16 operands and outputs (8 bits of mantissa, 2^-9 a rounding)
    # with float32 accumulation and a float32 state, against the float32
    # reference on the same bf16-valued weights: input projection,
    # convolution output, gate norm output and the result are each rounded
    # once, so the whole tensor is off by a few times 2^-9 = 0.002
    ("bfloat16", 1.5e-2)])
def test_whole_sequence_equals_the_sequential_recurrence(dtype, tol):
    cfg = tiny_cfg()
    m, op = _mixer_op(cfg, 2, 29, dtype)   # 29: the last block is ragged
    w = _mixer_weights(cfg, dtype)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 29, 64)).astype(dtype)
    out, new = _lower(m, op, x, w, _zero_state(op, 2, x.dtype), fill=True)
    for b in range(2):
        want, (h_last, tail) = ref.mixer(x[b], w, cfg, "float32")
        assert _rel(out[b], want) < tol
        assert _rel(new["ssm_state"][b], h_last) < tol
        assert _rel(new["conv_tail"][b], tail) < tol
    assert new["ssm_state"].dtype == jnp.float32   # whatever the tensors are


def test_state_is_stored_in_the_type_the_model_states_and_stepped_in_float32():
    """`state_dtype` bfloat16: the pool's array is bf16 (half the bytes a
    decode step reads and writes), every step rounds it once, and 24
    one-token steps stay within a few bf16 roundings of the float32 state."""
    cfg = tiny_cfg()
    w = _mixer_weights(cfg)
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 24, 64))
    states = {}
    for sd in ("float32", "bfloat16"):
        m, op = _mixer_op(cfg, 2, 1, state_dtype=sd)
        assert op.sequence_state_arrays()["ssm_state"][1] == ff.DataType(sd)
        state = _zero_state(op, 2)
        state["ssm_state"] = state["ssm_state"].astype(sd)
        for t in range(24):
            _, state = _lower(m, op, x[:, t:t + 1], w, state,
                              pos=jnp.full((2,), t, jnp.int32))
        assert state["ssm_state"].dtype == jnp.dtype(sd)
        states[sd] = state["ssm_state"]
    assert 0 < _rel(states["bfloat16"], states["float32"]) < 1e-2


@pytest.mark.parametrize("chunk", [4, 5, 8, 12, 16])
def test_chunked_prefill_carries_the_state_and_equals_one_shot(chunk):
    """Chunks that do (4, 8, 16) and do not (5, 12) divide the block of 8,
    the last one padded to full width as the batcher pads it, then one-token
    steps: outputs and outgoing state are the one-shot pass's."""
    cfg = tiny_cfg()
    total, prefill = 31, 27
    m1, op1 = _mixer_op(cfg, 1, total)
    w = _mixer_weights(cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, total, 64))
    want, want_state = _lower(m1, op1, x, w, _zero_state(op1, 1), fill=True)
    mc, opc = _mixer_op(cfg, 1, chunk)
    ms, ops_ = _mixer_op(cfg, 1, 1)
    state, outs = _zero_state(opc, 1), []
    for off in range(0, prefill, chunk):
        n = min(chunk, prefill - off)
        piece = jnp.zeros((1, chunk, 64)).at[:, :n].set(x[:, off:off + n])
        out, state = _lower(mc, opc, piece, w, state, pos=jnp.int32(off),
                            valid=jnp.int32(n))
        outs.append(out[:, :n])
    for t in range(prefill, total):
        out, state = _lower(ms, ops_, x[:, t:t + 1], w, state,
                            pos=jnp.full((1,), t, jnp.int32))
        outs.append(out)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), want, rtol=1e-4,
                               atol=1e-4)
    for k in want_state:
        np.testing.assert_allclose(state[k], want_state[k], rtol=1e-4,
                                   atol=1e-4)


def test_verify_shaped_dispatch_is_refused_at_trace():
    cfg = tiny_cfg()
    m, op = _mixer_op(cfg, 2, 3)
    with pytest.raises(NotImplementedError, match="rolled back"):
        _lower(m, op, jnp.zeros((2, 3, 64)), _mixer_weights(cfg),
               _zero_state(op, 2), pos=jnp.zeros((2,), jnp.int32))


# -- through the serving stack ---------------------------------------------------
def test_forward_logits_match_the_reference(lm):
    cfg, model = lm
    toks = np.random.default_rng(0).integers(0, 128, 32, dtype=np.int32)
    values, _, _ = model.executor.forward_values(
        model.params, model.state, {model.input_ops[0].name: toks[None]},
        None, CompMode.COMP_MODE_INFERENCE)
    probs = np.asarray(values[model.final_tensor.guid])[0]
    want = jax.nn.softmax(ref_logits(cfg, toks), axis=-1)
    # float32 on both sides: the blocked scan's and XLA's summation orders
    np.testing.assert_allclose(probs, want, rtol=5e-4, atol=1e-7)


def _gaps(cfg, prompt, out):
    z = ref_logits(cfg, np.concatenate([prompt, out]))
    rows = z[len(prompt) - 1:len(prompt) - 1 + len(out)]
    return rows.max(-1) - rows[np.arange(len(out)), out]


def test_batcher_prefill_and_decode_match_one_causal_pass(lm):
    """Chunked prefill (chunk 12 against a block of 8: prompts of one to
    three chunks, ragged last chunks), decode through the pool, a long and
    a short request side by side, 7 requests through 3 slots (every slot
    reused mid-run, and the last ones decode beside idle slots): every
    served token is the reference's best at its position by its float32
    LOGITS, to a rounding error; and the counters add up."""
    cfg, model = lm
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 128, n, dtype=np.int32)
               for n in (5, 30, 13, 7, 25, 12, 36)]
    news = (9, 20, 12, 3, 7, 5, 11)
    cb = builder.build_batcher(model, cfg)
    with cb:
        reqs = [cb.submit(p, n) for p, n in zip(prompts, news)]
        outs = [r.result(timeout=300) for r in reqs]
        counts = cb.op_counters()
        cb.publish_op_counters()
    for p, out in zip(prompts, outs):
        gap = _gaps(cfg, p, out)
        assert gap.max() < 1e-4, gap
    assert set(counts) == {"l0_mixer", "l1_mixer"}
    for c in counts.values():
        # every decode iteration stepped every row of the pool, idle or not
        assert int(c["ssm_steps"]) > 0
        assert wide_count(c["state_rows_stepped"]) == 3 * int(c["ssm_steps"])
        assert c["state_resets"] == len(prompts)
    text = cb.registry.render()
    steps = int(counts["l0_mixer"]["ssm_steps"])
    assert (f'ff_ssm_state_rows_stepped_total{{op="l0_mixer"}} {3 * steps}\n'
            in text), text
    assert f'ff_ssm_state_resets_total{{op="l1_mixer"}} {len(prompts)}\n' \
        in text
    # 2 layers x (4 x 16 x 16 float32 + 3 x 128 float32 tail rows)
    per_slot = 2 * (4 * 16 * 16 * 4 + 3 * 128 * 4)
    assert kvpool.state_bytes_per_slot(model) == per_slot
    assert f"ff_kvpool_state_bytes_per_slot{{pool=\"{cb.pool.label}\"}}" \
        f" {per_slot}\n" in text


def test_a_reused_slot_answers_as_a_fresh_one(lm):
    """One slot, so the second request takes over the first one's row with
    its state still in it (and stepped on by idle decode iterations in
    between): admission's reset makes it answer exactly as on a fresh pool,
    under chunked and under one-shot prefill."""
    cfg, model = lm
    rng = np.random.default_rng(2)
    first = rng.integers(0, 128, 29, dtype=np.int32)
    second = rng.integers(0, 128, 17, dtype=np.int32)
    for chunk in (12, 0):
        def serve(prompts):
            cb = ContinuousBatcher(model, max_len=64, num_slots=1,
                                   page_size=8, prefill_chunk_tokens=chunk,
                                   prefix_cache_pages=0)
            with cb:
                return [cb.submit(p, 10).result(timeout=300)
                        for p in prompts]
        fresh = serve([second])[0]
        reused = serve([first, second])[1]
        np.testing.assert_array_equal(reused, fresh)
        assert _gaps(cfg, second, reused).max() < 1e-4


def test_attention_decode_kernel_serves_the_hybrids_tokens(monkeypatch):
    """The hybrid at 4 query heads on 2 KV heads of 128 (the width the
    registry admits the kernel for), float32: with `attention_decode`
    forced each way the batcher serves the same tokens, the reference's own
    best, and the attentions' row counters add up beside the mixers'."""
    from flexflow_tpu.kernels.pallas import latent_decode

    monkeypatch.setattr(latent_decode, "BLOCK_ROWS", 16)
    cfg = tiny_cfg(head_dim=128)
    model = builder.build_model(cfg, SEED)
    rng = np.random.default_rng(6)
    jobs = [(rng.integers(0, 128, 21, dtype=np.int32), 14),
            (rng.integers(0, 128, 9, dtype=np.int32), 30)]
    dep = cfg["deployment"]
    counts, tokens = served_both_ways_counts_add_up(
        lambda: builder.build_batcher(model, cfg), jobs,
        ("l0_attn", "l1_attn"), dep["num_slots"], dep["max_len"], 16)
    assert set(counts) == {"l0_attn", "l1_attn", "l0_mixer", "l1_mixer"}
    assert int(counts["l0_attn"]["attn_steps"]) == int(
        counts["l0_mixer"]["ssm_steps"])
    for (p, _), out in zip(jobs, tokens):
        gap = _gaps(cfg, p, out)
        assert gap.max() < 1e-4, gap


def test_lockstep_session_equals_the_batcher(lm):
    """GenerativeSession pads the prompt to the window: `valid_len` keeps
    the padding out of the state."""
    cfg, model = lm
    prompt = np.random.default_rng(4).integers(0, 128, 11, dtype=np.int32)
    got = GenerativeSession(model, 64).generate(prompt[None], 8)[0]
    assert _gaps(cfg, prompt, got).max() < 1e-4


def test_what_needs_state_at_a_position_is_refused_typed(lm):
    cfg, model = lm
    kw = dict(max_len=64, num_slots=2, page_size=8, prefill_chunk_tokens=12)
    with pytest.raises(kvpool.SequenceStateUnsupported,
                       match="prefix cache.*l0_mixer") as e:
        ContinuousBatcher(model, prefix_cache_pages=4, **kw)
    assert e.value.op_name == "l0_mixer"
    with pytest.raises(kvpool.SequenceStateUnsupported, match="specul"):
        ContinuousBatcher(model, draft_model=model, spec_tokens=2, **kw)
    for role in ("prefill", "decode"):
        with pytest.raises(kvpool.SequenceStateUnsupported, match="export"):
            ContinuousBatcher(model, role=role, **kw)
    # with no option given the prefix cache is simply off
    cb = ContinuousBatcher(model, **kw)
    assert cb.pool.prefix is None and cb._band is None
    with cb:
        with pytest.raises(kvpool.SequenceStateUnsupported, match="resize"):
            cb.request_resize(num_slots=3)
        with pytest.raises(kvpool.SequenceStateUnsupported, match="export"):
            cb.request_export(None)
        with pytest.raises(kvpool.SequenceStateUnsupported, match="import"):
            cb.request_import({}, {}, [1], 1, 1)
    assert issubclass(kvpool.SequenceStateUnsupported, ValueError)


def test_pool_geometry_holds_both_kinds_and_sizes_slots_by_both(lm):
    from flexflow_tpu.search.machine_model import make_machine_model

    cfg, model = lm
    spec = kvpool.kv_cache_spec(model)
    mixers = [c for c in spec if c.per_sequence]
    assert [c.op for c in mixers] == ["l0_mixer", "l1_mixer"]
    assert mixers[0].per_token == {} and mixers[0].per_sequence == {
        "ssm_state": ((4, 16, 16), jnp.float32),
        "conv_tail": ((3, 128), jnp.float32)}
    attn = [c for c in spec if c.per_token]
    # 2 KV heads of 16: the cache row is kv_heads x head_dim wide
    assert [c.per_token for c in attn] == [{"k_cache": 32, "v_cache": 32}] * 2
    assert kvpool.kv_bytes_per_token(model) == 2 * 2 * 32 * 4
    caches = kvpool.zero_kv_caches(model, 3, 64)
    assert caches["l0_mixer"]["ssm_state"].shape == (3, 4, 16, 16)
    assert caches["l0_mixer"]["conv_tail"].shape == (3, 3, 128)
    assert caches["l1_attn"]["k_cache"].shape == (3, 64, 32)
    # a slot costs its rows AND its fixed state
    big = make_machine_model(model.config, 1)
    free = kvpool.derive_num_slots(model, 64, machine=big, max_slots=10**9)
    per_slot = (kvpool.kv_bytes_per_token(model) * 64
                + kvpool.state_bytes_per_slot(model))
    from flexflow_tpu.analysis import plan_memory_bytes

    model_bytes, _, _ = plan_memory_bytes(model.graph, big, model.config,
                                          optimizer_state_factor=1.0)
    assert free == int((big.memory_budget_bytes() - model_bytes) // per_slot)
    assert per_slot > kvpool.kv_bytes_per_token(model) * 64


def test_a_model_whose_only_cache_is_per_sequence_state_serves():
    """No attention anywhere: `kv_cache_spec` finds the mixers by their own
    capability, and the batcher serves the model."""
    config = ff.FFConfig()
    config.batch_size = 1
    config.allow_mixed_precision = False
    config.num_devices = 1
    m = ff.FFModel(config)
    tok = m.create_tensor([1, 16], ff.DataType.DT_INT32)
    t = m.embedding(tok, 50, 32, ff.AggrMode.AGGR_MODE_NONE, name="emb")
    t = m.add(t, m.ssm_mixer(m.rms_norm(t, [-1], name="ln"), 32, 2, 8,
                             chunk_size=4, name="mixer"))
    m.softmax(m.dense(t, 50, use_bias=False, name="head"))
    m.compile(optimizer=ff.SGDOptimizer(m, lr=0.0),
              loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    assert [c.op for c in kvpool.kv_cache_spec(m)] == ["mixer"]
    assert kvpool.kv_bytes_per_token(m) == 0
    prompt = np.arange(9, dtype=np.int32)
    with ContinuousBatcher(m, max_len=32, num_slots=2, page_size=8,
                           prefill_chunk_tokens=6) as cb:
        served = cb.submit(prompt, 6).result(timeout=300)
    lockstep = GenerativeSession(m, 32).generate(prompt[None], 6)[0]
    np.testing.assert_array_equal(served, lockstep)


def test_training_step_differentiates_through_the_scan():
    """jax.grad through the blocked scan: finite, non-zero gradients for
    every mixer weight (the backward pass is XLA's transpose of the scan; a
    hand-written one is ROADMAP's)."""
    cfg = tiny_cfg()
    m, op = _mixer_op(cfg, 2, 19)
    w = _mixer_weights(cfg)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 19, 64))

    def loss(w):
        ctx = LoweringContext(m.config, CompMode.COMP_MODE_TRAINING)
        ctx.decode_pos, ctx.fill_kv_cache, ctx.valid_len = None, False, None
        return jnp.sum(jnp.square(op.lower(ctx, [x], w)[0]))

    grads = jax.grad(loss)(w)
    for name, g in grads.items():
        assert np.isfinite(np.asarray(g)).all() and float(
            jnp.max(jnp.abs(g))) > 0, name
