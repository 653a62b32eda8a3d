"""KV-cache autoregressive generation (serving/generate.py): incremental
decoding must reproduce the naive recompute-everything loop."""
import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.ffconst import CompMode
from flexflow_tpu.serving.generate import GenerativeSession
from tests.conftest import module_xla_cache

# module-scoped XLA compilation cache — see conftest.module_xla_cache
_xla_cache = pytest.fixture(scope="module", autouse=True)(module_xla_cache)


def _build_lm(batch, window, vocab=50, hidden=32, heads=4, layers=2,
              use_flash=None, parallel_axes=None):
    config = ff.FFConfig()
    config.batch_size = batch
    config.allow_mixed_precision = False
    model = ff.FFModel(config)
    tokens = model.create_tensor([batch, window], ff.DataType.DT_INT32)
    t = model.embedding(tokens, vocab, hidden, ff.AggrMode.AGGR_MODE_NONE,
                        name="emb")
    for i in range(layers):
        attn = model.multihead_attention(t, t, t, hidden, heads, causal=True,
                                         use_flash=use_flash,
                                         name=f"l{i}_attn")
        t = model.layer_norm(model.add(t, attn), [-1], name=f"l{i}_ln1")
        h = model.dense(t, hidden * 2, ff.ActiMode.AC_MODE_GELU,
                        name=f"l{i}_ff1")
        h = model.dense(h, hidden, name=f"l{i}_ff2")
        t = model.layer_norm(model.add(t, h), [-1], name=f"l{i}_ln2")
    model.softmax(model.dense(t, vocab, name="lm_head"))
    model.compile(optimizer=ff.SGDOptimizer(model, lr=0.0),
                  loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                  parallel_axes=parallel_axes)
    return model


def _naive_generate(model, prompt, n_new, window):
    """Recompute the full (causal) forward per step; greedy argmax."""
    b, plen = prompt.shape
    feeds_name = model.input_ops[0].name
    seq = list(prompt.T)  # list of (b,) columns
    out = []
    for _ in range(n_new):
        cur = len(seq)
        padded = np.zeros((b, window), np.int32)
        padded[:, :cur] = np.stack(seq, axis=1)
        values, _, _ = model.executor.forward_values(
            model.params, model.state, {feeds_name: padded}, None,
            CompMode.COMP_MODE_INFERENCE)
        probs = np.asarray(values[model.final_tensor.guid])
        tok = probs[:, cur - 1, :].argmax(-1).astype(np.int32)
        out.append(tok)
        seq.append(tok)
    return np.stack(out, axis=1)


def test_kv_cache_generate_matches_naive_loop():
    b, window, n_new = 2, 12, 5
    model = _build_lm(b, window)
    prompt = np.random.RandomState(0).randint(1, 50, size=(b, 4)).astype(np.int32)

    ref = _naive_generate(model, prompt, n_new, window)
    session = GenerativeSession(model, max_len=window)
    got = session.generate(prompt, n_new)
    np.testing.assert_array_equal(got, ref)


def test_chunked_decode_matches_per_step_loop():
    """tokens_per_dispatch > 1 (K decode steps per jitted scan dispatch)
    is token-identical to the per-step loop, including a ragged final
    chunk."""
    b, window, n_new = 2, 12, 5
    model = _build_lm(b, window)
    prompt = np.random.RandomState(2).randint(1, 50, size=(b, 4)).astype(np.int32)

    ref = GenerativeSession(model, max_len=window).generate(prompt, n_new)
    got = GenerativeSession(model, max_len=window).generate(
        prompt, n_new, tokens_per_dispatch=3)  # chunks of 3, 1 ragged
    np.testing.assert_array_equal(got, ref)


def test_chunked_decode_eos_stops_same_step():
    """With an eos_id, the chunked path stops emitting on the same step as
    the per-step loop (speculative in-flight compute is discarded).
    batch=1 so finished.all() genuinely fires, mid-chunk for K=4."""
    b, window, n_new = 1, 12, 8
    model = _build_lm(b, window)
    prompt = np.random.RandomState(3).randint(1, 50, size=(b, 4)).astype(np.int32)

    ref = GenerativeSession(model, max_len=window).generate(prompt, n_new)
    # synthetic EOS: the token the unchunked run emits at step 1, so the
    # stop lands mid-chunk for tokens_per_dispatch=4
    eos = int(ref[0, 1])
    ref_eos = GenerativeSession(model, max_len=window).generate(
        prompt, n_new, eos_id=eos)
    assert ref_eos.shape[1] < n_new, ref_eos  # the stop actually fired
    got_eos = GenerativeSession(model, max_len=window).generate(
        prompt, n_new, eos_id=eos, tokens_per_dispatch=4)
    np.testing.assert_array_equal(got_eos, ref_eos)


def test_sampled_decode_chunk_invariant():
    """temperature>0 sampling draws per-POSITION rng keys, so the same
    seed yields identical tokens at any tokens_per_dispatch — and
    different seeds yield different sequences."""
    b, window, n_new = 2, 12, 6
    model = _build_lm(b, window)
    prompt = np.random.RandomState(6).randint(1, 50, size=(b, 4)).astype(np.int32)

    kw = dict(temperature=1.0, top_k=10, seed=42)
    ref = GenerativeSession(model, max_len=window).generate(
        prompt, n_new, **kw)
    got = GenerativeSession(model, max_len=window).generate(
        prompt, n_new, tokens_per_dispatch=4, **kw)
    np.testing.assert_array_equal(got, ref)
    other = GenerativeSession(model, max_len=window).generate(
        prompt, n_new, temperature=1.0, top_k=10, seed=43)
    assert not np.array_equal(other, ref)
    # temperature=0 stays exactly the greedy path
    greedy = GenerativeSession(model, max_len=window).generate(prompt, n_new)
    greedy0 = GenerativeSession(model, max_len=window).generate(
        prompt, n_new, temperature=0.0, seed=7)
    np.testing.assert_array_equal(greedy0, greedy)


def test_partial_batch_prompts_pad_and_slice():
    """Fewer prompts than the compiled batch: the session pads by tiling
    (rows decode independently) and returns only the real rows — exact
    match with the corresponding rows of a full-batch run. Oversize and
    malformed prompts raise ValueError."""
    b, window, n_new = 2, 12, 5
    model = _build_lm(b, window)
    prompt = np.random.RandomState(8).randint(1, 50, size=(b, 4)).astype(np.int32)

    full = GenerativeSession(model, max_len=window).generate(prompt, n_new)
    one = GenerativeSession(model, max_len=window).generate(
        prompt[:1], n_new, tokens_per_dispatch=3)
    assert one.shape == (1, n_new)
    np.testing.assert_array_equal(one, full[:1])

    s = GenerativeSession(model, max_len=window)
    import pytest

    with pytest.raises(ValueError, match="exceed the session batch"):
        s.generate(np.zeros((3, 4), np.int32), n_new)
    with pytest.raises(ValueError, match="non-empty"):
        s.generate(np.zeros((4,), np.int32), n_new)
    with pytest.raises(ValueError, match="prefill window"):
        s.generate(np.zeros((2, window + 1), np.int32), 1)


def test_generate_zero_tokens_returns_empty():
    """max_new_tokens=0: both paths return an empty (b, 0) array."""
    b, window = 2, 12
    model = _build_lm(b, window)
    prompt = np.random.RandomState(5).randint(1, 50, size=(b, 4)).astype(np.int32)
    for k in (1, 4):
        got = GenerativeSession(model, max_len=window).generate(
            prompt, 0, tokens_per_dispatch=k)
        assert got.shape == (b, 0), got.shape


def test_kv_cache_generate_flash_prefill_matches_naive_loop():
    """use_flash=True prefill: the packed kernel fills the KV cache (which
    stores exactly the packed (b, l, h*d) projections) and decode steps
    attend against it — same tokens as the naive full-recompute loop."""
    b, window, n_new = 2, 12, 5
    model = _build_lm(b, window, use_flash=True)
    prompt = np.random.RandomState(4).randint(1, 50, size=(b, 4)).astype(np.int32)

    ref = _naive_generate(model, prompt, n_new, window)
    session = GenerativeSession(model, max_len=window)
    # the lockstep session holds its caches as the pool stores them:
    # (batch, max_len, heads*head_dim), hidden 32 = 4 heads of 8
    assert {c.shape for pair in session._caches.values()
            for c in pair.values()} == {(b, window, 32)}
    got = session.generate(prompt, n_new)
    np.testing.assert_array_equal(got, ref)


def test_kv_cache_generate_with_tensor_parallel_heads_matches_one_device():
    """Heads sharded over a 'model' axis: the packed cache's last dimension
    is then sharded by head, and the decode step contracts head by head
    instead of over the whole packed row — same tokens as one device."""
    import jax

    b, window, n_new = 2, 12, 5
    ref = _build_lm(b, window)
    tp = _build_lm(b, window, parallel_axes={"model": 2})
    tp.params = jax.tree.map(
        lambda a, like: jax.device_put(np.asarray(a), like.sharding),
        ref.params, tp.params)
    attn = next(op for op in tp.graph.ops.values() if op.name == "l0_attn")
    assert attn.weights[0].parallel_shape.partition_spec()[1] == "model"
    prompt = np.random.RandomState(5).randint(1, 50, size=(b, 4)).astype(np.int32)
    np.testing.assert_array_equal(
        GenerativeSession(tp, max_len=window).generate(prompt, n_new),
        GenerativeSession(ref, max_len=window).generate(prompt, n_new))


def test_generate_eos_early_stop():
    b, window = 1, 12  # single row: eos must genuinely stop the loop
    model = _build_lm(b, window)
    prompt = np.random.RandomState(1).randint(1, 50, size=(b, 3)).astype(np.int32)
    session = GenerativeSession(model, max_len=window)
    first = session.generate(prompt, 6)
    eos = int(first[0, 1])  # force an early stop at the 2nd generated token
    got = session.generate(prompt, 6, eos_id=eos)
    # the stop lands AT the first occurrence of the eos token — computed,
    # not assumed at index 1, because the greedy sequence may repeat a
    # token (first[0, 0] == first[0, 1] on some backends/versions)
    want = int(np.argmax(first[0] == eos)) + 1
    assert got.shape[1] == want, got
    np.testing.assert_array_equal(got[0], first[0, :want])
