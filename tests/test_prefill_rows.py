"""A prefill dispatch computes only the row that is sampled
(`Executor.forward_values(final_row=...)`, the continuous batcher's three
prefill programs): past the last op that keeps a serving cache the graph
runs for one token position, or not at all. Held here on the three served
families at a small size — the dense LM of test_continuous_batching.py, the
latent + routed model of test_latent_moe.py, the hybrid of test_ssm.py —
against the lockstep GenerativeSession and against the same dispatch with
every row computed."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.core.op import LoweringContext
from flexflow_tpu.ffconst import CompMode, OpType
from flexflow_tpu.ops.common import emit_dtype
from flexflow_tpu.runtime.executor import NO_ROW, RowCutError
from flexflow_tpu.serving.generate import GenerativeSession
from flexflow_tpu.serving.sched.continuous import ContinuousBatcher
from flexflow_tpu.serving.sched.kvpool import op_states
from tests.conftest import module_xla_cache
from tests.test_generate import _build_lm
from tests.test_latent_moe import SEED as LATENT_SEED
from tests.test_latent_moe import builder as latent_builder
from tests.test_latent_moe import tiny_cfg as latent_cfg
from tests.test_ssm import SEED as HYBRID_SEED
from tests.test_ssm import builder as hybrid_builder
from tests.test_ssm import tiny_cfg as hybrid_cfg

_xla_cache = pytest.fixture(scope="module", autouse=True)(module_xla_cache)

FAMILIES = ("dense", "latent_moe", "hybrid_ssm")


@pytest.fixture(scope="module")
def families():
    """family -> (model, vocabulary, window, max_len, page, chunk)"""
    return {
        "dense": (_build_lm(2, 12), 50, 12, 16, 4, 4),
        "latent_moe": (latent_builder.build_model(latent_cfg(), LATENT_SEED),
                       128, 32, 64, 8, 8),
        "hybrid_ssm": (hybrid_builder.build_model(hybrid_cfg(), HYBRID_SEED),
                       128, 32, 64, 8, 8),
    }


def _lengths(chunk):
    """chunk - 1, chunk, chunk + 1, 2 chunks, and two chunks and a last
    one of a single token."""
    return (chunk - 1, chunk, chunk + 1, 2 * chunk, 2 * chunk + 1)


def _prompt(vocab, n, seed):
    return np.random.default_rng(seed).integers(
        1, vocab, n, dtype=np.int32)


@pytest.mark.parametrize("one_shot", (False, True),
                         ids=("chunked", "one_shot"))
@pytest.mark.parametrize("which", range(5), ids=(
    "chunk-1", "chunk", "chunk+1", "2chunk", "2chunk+1"))
@pytest.mark.parametrize("family", FAMILIES)
def test_served_tokens_equal_the_lockstep_sessions(families, family, which,
                                                   one_shot):
    """The first token (the prefill's pick) and every one after it."""
    model, vocab, _window, max_len, page, chunk = families[family]
    n = _lengths(chunk)[which]
    prompt = _prompt(vocab, n, 100 + which)
    want = np.asarray(
        GenerativeSession(model, max_len).generate(prompt[None], 4)[0])
    kw = dict(prefix_cache_pages=0) if family == "hybrid_ssm" else {}
    with ContinuousBatcher(model, max_len=max_len, num_slots=2,
                           page_size=page,
                           prefill_chunk_tokens=0 if one_shot else chunk,
                           **kw) as cb:
        got = cb.submit(prompt, 4).result(timeout=300)
    np.testing.assert_array_equal(got, want)


def _forward(model, cb, small, tokens, **kw):
    """One batch-1 dispatch as the batcher's prefill programs make it:
    (final value or None, the caching ops' new arrays)."""
    values, new_state, _ = model.executor.forward_values(
        model.params, {**model.state, **small},
        {model.input_ops[0].name: tokens}, None,
        CompMode.COMP_MODE_INFERENCE, **kw)
    names = [op.name for op in cb.attn_ops]
    return values.get(model.final_tensor.guid), op_states(new_state, names)


@pytest.mark.parametrize("which", range(5), ids=(
    "chunk-1", "chunk", "chunk+1", "2chunk", "2chunk+1"))
@pytest.mark.parametrize("family", FAMILIES)
def test_the_picked_distribution_is_the_full_forwards_row(families, family,
                                                          which):
    """Chunk by chunk (no row until the last chunk, then its one), and in
    one shot: the (V,) distribution the first token is picked from is row
    plen - 1 of the same dispatch with every row computed, and the caches
    it leaves are the same arrays."""
    model, vocab, window, max_len, page, chunk = families[family]
    n = _lengths(chunk)[which]
    prompt = _prompt(vocab, n, 200 + which)
    kw = dict(prefix_cache_pages=0) if family == "hybrid_ssm" else {}
    cb = ContinuousBatcher(model, max_len=max_len, num_slots=2,
                           page_size=page, prefill_chunk_tokens=chunk, **kw)
    small = cb._zero_small()
    for off in range(0, n, chunk):
        real = min(chunk, n - off)
        tokens = np.zeros((1, chunk), np.int32)
        tokens[0, :real] = prompt[off:off + real]
        last = off + real >= n
        step = dict(decode_pos=np.int32(off),
                    valid_len=np.int32(real) if last else None)
        full, want_small = _forward(model, cb, small, tokens, **step)
        assert full.shape == (1, chunk, vocab)
        row, small = _forward(
            model, cb, small, tokens, **step,
            final_row=np.int32(real - 1) if last else NO_ROW)
        assert (row is None) == (not last)
        jax.tree_util.tree_map(np.testing.assert_array_equal, small,
                               want_small)
    assert row.shape == (1, 1, vocab)
    np.testing.assert_allclose(row[0, 0], full[0, real - 1], rtol=1e-5,
                               atol=1e-7)
    # one shot: the whole window with fresh caches, the prompt in front
    tokens = np.zeros((1, window), np.int32)
    tokens[0, :n] = prompt
    shot = dict(fill_kv_cache=True, valid_len=np.int32(n))
    zero = jax.tree_util.tree_map(
        lambda a: jnp.zeros((1,) + a.shape[1:], a.dtype), cb._caches)
    full, _ = _forward(model, cb, zero, tokens, **shot)
    one, _ = _forward(model, cb, zero, tokens, **shot,
                      final_row=np.int32(n - 1))
    np.testing.assert_allclose(one[0, 0], full[0, n - 1], rtol=1e-5,
                               atol=1e-7)
    # and the chunked pick is the one causal pass's row, to a rounding
    # error of the chunked attention
    np.testing.assert_allclose(row[0, 0], full[0, n - 1], rtol=2e-4,
                               atol=1e-6)


def _walk(model, params, state, feed, mode, decode_pos):
    """The forward as `Executor.forward_values` walked it before it knew
    of rows: every op of the graph in order, on every position."""
    ctx = LoweringContext(model.config, mode, model.executor.mesh,
                          jax.random.PRNGKey(0))
    ctx.decode_pos, ctx.fill_kv_cache, ctx.valid_len = decode_pos, False, None
    ctx.state = {(op, var): val for op, vars_ in state.items()
                 for var, val in vars_.items()}
    for op in model.executor.topo:
        if op.op_type == OpType.INPUT:
            ctx.values[op.outputs[0].guid] = ctx.constrain(feed,
                                                           op.outputs[0])
            continue
        weights = {w._weight_spec.name: ctx.constrain(
            params[op.name][w._weight_spec.name], w) for w in op.weights}
        with jax.named_scope(f"{op.op_type.value}:{op.name}"):
            outs = op.lower(ctx, [ctx.values[t.guid] for t in op.inputs],
                            weights)
        for t, v in zip(op.outputs, outs):
            ctx.values[t.guid] = ctx.constrain(
                v.astype(emit_dtype(model.config, t.dtype)), t)
    return (ctx.values[model.final_tensor.guid],
            {op: {var: ctx.state_updates.get((op, var), val)
                  for var, val in vars_.items()}
             for op, vars_ in state.items()})


@pytest.mark.parametrize("call", ("training", "decode"))
def test_with_no_row_named_the_forward_is_what_it_was(families, call):
    """`final_row` left out (training, `decode_all`, speculation's verify,
    GenerativeSession): the program is the plain walk's, equation for
    equation, and so are the values."""
    model = families["dense"][0]
    name = model.input_ops[0].name
    tokens = _prompt(50, 24, 7).reshape(2, 12)
    if call == "training":
        mode, pos, state, feed = (CompMode.COMP_MODE_TRAINING, None,
                                  model.state, tokens)
    else:
        cb = ContinuousBatcher(model, max_len=16, num_slots=2, page_size=4)
        mode, pos = (CompMode.COMP_MODE_INFERENCE,
                     np.asarray([3, 5], np.int32))
        state, feed = {**model.state, **cb._caches}, tokens[:, :1]

    def fwd(params, state, x):
        values, new_state, _ = model.executor.forward_values(
            params, state, {name: x}, jax.random.PRNGKey(0), mode,
            decode_pos=pos)
        return values[model.final_tensor.guid], new_state

    def walk(params, state, x):
        return _walk(model, params, state, x, mode, pos)

    args = (model.params, state, feed)
    assert str(jax.make_jaxpr(fwd)(*args)) == str(jax.make_jaxpr(walk)(*args))
    got, want = jax.jit(fwd)(*args), jax.jit(walk)(*args)
    jax.tree_util.tree_map(np.testing.assert_array_equal, got, want)
    assert got[0].shape == feed.shape + (50,)


def _lm_with_tail(tail):
    """One cached attention layer, then `tail(model, t)` before the head."""
    config = ff.FFConfig()
    config.batch_size = 1
    config.allow_mixed_precision = False
    model = ff.FFModel(config)
    tokens = model.create_tensor([1, 8], ff.DataType.DT_INT32)
    t = model.embedding(tokens, 20, 16, ff.AggrMode.AGGR_MODE_NONE,
                        name="emb")
    t = model.add(t, model.multihead_attention(t, t, t, 16, 2, causal=True,
                                               name="attn"))
    model.softmax(model.dense(tail(model, t), 20, name="lm_head"))
    model.compile(optimizer=ff.SGDOptimizer(model, lr=0.0),
                  loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    return model


def test_a_tail_that_reads_across_positions_is_refused_by_name():
    """After the last caching op: a softmax over the token axis, a reverse
    of it (an op that says nothing of positions). Refused typed, naming the
    op, when the batcher is built and when a row is asked of the executor;
    with no row named the graph runs as ever."""
    over_tokens = _lm_with_tail(
        lambda m, t: m.multiply(t, m.softmax(t, axis=1, name="mix")))
    with pytest.raises(RowCutError, match="mix.*attn"):
        ContinuousBatcher(over_tokens, max_len=8, num_slots=1, page_size=4)
    flipped = _lm_with_tail(lambda m, t: m.reverse(t, 1, name="flip"))
    with pytest.raises(RowCutError, match="flip"):
        flipped.executor.forward_values(
            flipped.params, flipped.state,
            {flipped.input_ops[0].name: np.zeros((1, 8), np.int32)}, None,
            CompMode.COMP_MODE_INFERENCE, final_row=np.int32(2))
    values, _, _ = flipped.executor.forward_values(
        flipped.params, flipped.state,
        {flipped.input_ops[0].name: np.zeros((1, 8), np.int32)}, None,
        CompMode.COMP_MODE_INFERENCE)
    assert values[flipped.final_tensor.guid].shape == (1, 8, 20)
    # a softmax over the features, after the cut, is every served model's
    assert _lm_with_tail(lambda m, t: m.softmax(t)).executor.row_cut()[0] > 0
    # and a graph that keeps no cache has no cut to name a row after
    plain = ff.FFModel(ff.FFConfig())
    plain.softmax(plain.dense(plain.create_tensor([2, 8]), 4))
    plain.compile(optimizer=ff.SGDOptimizer(plain, lr=0.0),
                  loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    with pytest.raises(RowCutError, match="keeps a serving cache"):
        plain.executor.row_cut()
