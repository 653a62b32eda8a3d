"""Continuous-batching serving (ISSUE 5): the paged KV pool, the
iteration-level scheduler, admission control, streaming, and the three
batcher/generate satellite fixes.

The decisive property throughout: continuous decode is TOKEN-IDENTICAL to
the lockstep GenerativeSession path for the same prompt — per-row
attention over the slot-dense cache is independent of what else shares
the iteration."""
import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from flexflow_tpu.serving import (BatcherStopped, DynamicBatcher,
                                  InferenceServer)
from flexflow_tpu.serving.generate import GenerativeSession
from flexflow_tpu.serving.sched import (AdmissionController,
                                        ContinuousBatcher, PagedKVPool,
                                        PoolExhausted, PoolSaturated,
                                        QueueFull, RequestState,
                                        RequestTooLarge, derive_num_slots,
                                        kv_bytes_per_token)
from tests.conftest import (module_xla_cache,
                            served_both_ways_counts_add_up)
from tests.test_generate import _build_lm

# module-scoped XLA compilation cache — see conftest.module_xla_cache
_xla_cache = pytest.fixture(scope="module", autouse=True)(module_xla_cache)


@pytest.fixture(scope="module")
def lm():
    """One compiled LM shared by the module (b=2, window=12)."""
    return _build_lm(2, 12)


def _prompts(lens, seed=0, vocab=50):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab, size=(n,)).astype(np.int32) for n in lens]


# ---------------------------------------------------------------------
# PagedKVPool
# ---------------------------------------------------------------------
def test_pool_alloc_extend_free_accounting():
    pool = PagedKVPool(num_slots=2, max_len=16, page_size=4)
    assert pool.pages_per_slot == 4 and pool.total_pages == 8
    s0 = pool.alloc("a", 5)  # 5 tokens -> 2 pages
    assert s0 in (0, 1)
    assert pool.pages_used() == 2 and pool.live_sequences() == 1
    # growing within the page: no new page; crossing: one more
    pool.extend("a", 3)  # 8 tokens -> still 2 pages
    assert pool.pages_used() == 2
    pool.extend("a", 1)  # 9 tokens -> 3 pages
    assert pool.pages_used() == 3
    assert pool.pages_of("a") == [s0 * 4, s0 * 4 + 1, s0 * 4 + 2]
    s1 = pool.alloc("b", 1)
    assert s1 != s0
    assert pool.free_slot_count() == 0
    pool.free("a")
    assert pool.pages_used() == 1 and pool.free_slot_count() == 1
    pool.free("a")  # idempotent
    pool.free("b")
    assert pool.pages_used() == 0 and pool.utilization() == 0.0


def test_pool_exhaustion_and_limits():
    pool = PagedKVPool(num_slots=1, max_len=8, page_size=4)
    pool.alloc("a", 4)
    with pytest.raises(PoolExhausted, match="slots in use"):
        pool.alloc("b", 1)
    with pytest.raises(ValueError, match="already allocated"):
        pool.alloc("a", 1)
    with pytest.raises(PoolExhausted, match="per-slot capacity"):
        pool.extend("a", 5)  # 4 + 5 > max_len=8
    with pytest.raises(KeyError):
        pool.extend("zzz", 1)
    pool.free("a")
    with pytest.raises(PoolExhausted, match="per-slot capacity"):
        pool.alloc("c", 9)


def test_pool_gauges_track_usage_per_pool():
    """Gauge series are labeled per pool, so two pools in one process (a
    multi-model server) never clobber each other's values."""
    from flexflow_tpu.obs import REGISTRY

    pool = PagedKVPool(num_slots=2, max_len=8, page_size=4)
    other = PagedKVPool(num_slots=1, max_len=8, page_size=4)
    used = REGISTRY.gauge("ff_kvpool_pages_used", labels=("pool",))
    total = REGISTRY.gauge("ff_kvpool_pages_total", labels=("pool",))
    assert total.value(pool=pool.label) == 4
    assert total.value(pool=other.label) == 2
    pool.alloc("a", 8)
    other.alloc("x", 1)
    assert used.value(pool=pool.label) == 2
    assert used.value(pool=other.label) == 1
    pool.free("a")
    assert used.value(pool=pool.label) == 0
    assert used.value(pool=other.label) == 1


def test_derive_num_slots_from_machine_spec(lm):
    from flexflow_tpu.search.machine_model import ChipSpec, SimpleMachineModel

    # v5e-class HBM vs a toy model: the ceiling clamps
    big = SimpleMachineModel(1, ChipSpec())
    assert derive_num_slots(lm, 64, machine=big, max_slots=16) == 16
    # a chip whose HBM the model itself exhausts: the floor keeps serving
    tiny = SimpleMachineModel(1, ChipSpec(hbm_gb=1e-9))
    assert derive_num_slots(lm, 64, machine=tiny) == 1
    # in between: capacity scales with (HBM - model) / (kv/token * max_len)
    per_tok = kv_bytes_per_token(lm)
    from flexflow_tpu.analysis import plan_memory_bytes

    model_bytes, _, _ = plan_memory_bytes(
        lm.graph, big, lm.config, optimizer_state_factor=1.0)
    want = int((big.memory_budget_bytes() - model_bytes) // (per_tok * 64))
    got = derive_num_slots(lm, 64, machine=big, max_slots=10**9)
    assert got == want and got > 16


# ---------------------------------------------------------------------
# AdmissionController
# ---------------------------------------------------------------------
def test_admission_static_rejections():
    pool = PagedKVPool(num_slots=2, max_len=16, page_size=4)
    adm = AdmissionController(pool, window=8, max_queue=4)
    with pytest.raises(RequestTooLarge, match="empty"):
        adm.admit("r0", 0, 4)
    with pytest.raises(RequestTooLarge, match="prefill window"):
        adm.admit("r1", 9, 4)
    with pytest.raises(RequestTooLarge, match="cache capacity"):
        adm.admit("r2", 8, 9)  # 17 > max_len 16
    assert adm.queue_depth() == 0  # nothing was reserved


def test_admission_queue_and_page_backpressure():
    pool = PagedKVPool(num_slots=1, max_len=16, page_size=4)  # 4 pages
    adm = AdmissionController(pool, window=8, max_queue=2,
                              queue_pages_budget=6)
    adm.admit("a", 8, 8)  # 4 pages of backlog
    with pytest.raises(PoolSaturated):
        adm.admit("b", 8, 8)  # 4 more > budget 6
    adm.admit("c", 4, 2)  # 2 pages -> exactly at budget
    with pytest.raises(QueueFull):
        adm.admit("d", 1, 1)  # depth bound (2) hit first
    # scheduling moves pages out of the backlog and frees the queue
    wait = adm.on_scheduled("a")
    assert wait >= 0.0
    adm.admit("d", 1, 1)
    assert adm.queue_depth() == 2 and adm.backlog_pages() == 3
    adm.release("c")
    adm.release("d")
    assert adm.queue_depth() == 0 and adm.backlog_pages() == 0


# ---------------------------------------------------------------------
# ContinuousBatcher: parity, state machine, slot reuse, streaming
# ---------------------------------------------------------------------
def test_continuous_token_parity_with_lockstep(lm):
    """Mixed prompt lengths through 2 slots (3 requests, so one reuses a
    freed slot): every request's greedy tokens are IDENTICAL to a lockstep
    GenerativeSession run of that prompt alone."""
    prompts = _prompts([4, 7, 3], seed=0)
    session = GenerativeSession(lm, max_len=12)
    refs = [session.generate(p[None, :], 5)[0] for p in prompts]
    with ContinuousBatcher(lm, max_len=12, num_slots=2, page_size=4,
                           max_queue=8) as cb:
        reqs = [cb.submit(p, 5) for p in prompts]
        outs = [r.result(timeout=300) for r in reqs]
    for out, ref, req in zip(outs, refs, reqs):
        np.testing.assert_array_equal(out, np.asarray(ref))
        assert req.state is RequestState.FINISHED
        assert req.t_first_token is not None and req.t_done is not None
        assert req.ttft_s >= 0 and req.queue_wait_s >= 0
    st = cb.stats()
    assert st["completed"] == 3 and st["failed"] == 0
    assert st["pool"]["pages_used"] == 0 and st["slots_active"] == 0


def test_continuous_slot_reuse_mid_decode(lm):
    """num_slots=1 forces full serialization through ONE slot: each next
    request prefills into the slot the previous one released, and the
    cache rows left behind never leak into the next request's tokens."""
    prompts = _prompts([5, 5, 5], seed=3)
    session = GenerativeSession(lm, max_len=12)
    refs = [session.generate(p[None, :], 6)[0] for p in prompts]
    with ContinuousBatcher(lm, max_len=12, num_slots=1, page_size=4,
                           max_queue=8, queue_pages_budget=64) as cb:
        reqs = [cb.submit(p, 6) for p in prompts]
        for req, ref in zip(reqs, refs):
            np.testing.assert_array_equal(req.result(timeout=300),
                                          np.asarray(ref))
    assert cb.pool.free_slot_count() == 1


def test_grouped_kv_decode_kernel_token_parity_and_row_counters(monkeypatch):
    """A causal LM of 4 query heads on 2 KV heads of 128 — the width the
    registry admits the dense decode kernel for — through the batcher with
    `attention_decode` forced each way: the same greedy tokens as the
    lockstep session, and the attentions' row counters add up
    (`rows_read >= rows_filled`, `attn_steps` = decode iterations a
    counting op, `ff_attn_rows_*` mirror them)."""
    import flexflow_tpu as ff
    from flexflow_tpu.kernels.pallas import latent_decode

    monkeypatch.setattr(latent_decode, "BLOCK_ROWS", 16)
    config = ff.FFConfig()
    config.batch_size = 1
    config.allow_mixed_precision = False
    config.num_devices = 1      # a step jitted over a mesh keeps the chain
    model = ff.FFModel(config)
    tokens = model.create_tensor([1, 24], ff.DataType.DT_INT32)
    t = model.embedding(tokens, 50, 32, ff.AggrMode.AGGR_MODE_NONE,
                        name="emb")
    for i in range(2):
        attn = model.multihead_attention(
            t, t, t, 32, 4, kdim=128, vdim=128, causal=True, kv_heads=2,
            name=f"l{i}_attn")
        t = model.layer_norm(model.add(t, attn), [-1], name=f"l{i}_ln")
    model.softmax(model.dense(t, 50, name="lm_head"))
    model.compile(optimizer=ff.SGDOptimizer(model, lr=0.0),
                  loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    rng = np.random.RandomState(21)
    jobs = [(rng.randint(1, 50, size=(n,)).astype(np.int32), new)
            for n, new in ((21, 14), (9, 30))]
    slots, max_len = 3, 64
    session = GenerativeSession(model, max_len=max_len)
    refs = [session.generate(p[None, :], n)[0] for p, n in jobs]
    counts, outs = served_both_ways_counts_add_up(
        lambda: ContinuousBatcher(model, max_len=max_len, num_slots=slots,
                                  page_size=8, max_queue=4),
        jobs, ("l0_attn", "l1_attn"), slots, max_len, 16)
    assert set(counts) == {"l0_attn", "l1_attn"}
    for out, ref in zip(outs, refs):
        np.testing.assert_array_equal(out, np.asarray(ref))


def test_continuous_eos_frees_slot_early(lm):
    """A request that hits EOS stops emitting THAT iteration and returns
    fewer tokens; its pages are released immediately."""
    [p] = _prompts([4], seed=1)
    ref = GenerativeSession(lm, max_len=12).generate(p[None, :], 6)[0]
    eos = int(ref[2])
    with ContinuousBatcher(lm, max_len=12, num_slots=2,
                           page_size=4) as cb:
        out = cb.submit(p, 6, eos_id=eos).result(timeout=300)
    np.testing.assert_array_equal(out, ref[:3])  # stops AT the eos token


def test_continuous_streaming_order_and_result_agree(lm):
    [p] = _prompts([4], seed=2)
    with ContinuousBatcher(lm, max_len=12, num_slots=2,
                           page_size=4) as cb:
        req = cb.submit(p, 5)
        streamed = list(req.stream(timeout=300))
        np.testing.assert_array_equal(req.result(timeout=10), streamed)
    assert len(streamed) == 5


def test_continuous_sampling_deterministic_and_traffic_independent(lm):
    """temperature>0: a request's tokens are a function of its own
    (seed, prompt) — the same request alone or sharing iterations with
    other traffic samples the SAME sequence; a different seed differs."""
    prompts = _prompts([4, 6, 5], seed=4)
    kw = dict(max_len=12, num_slots=2, page_size=4, temperature=1.0,
              top_k=10)
    with ContinuousBatcher(lm, **kw) as cb:
        alone = cb.submit(prompts[0], 5, seed=42).result(timeout=300)
    with ContinuousBatcher(lm, **kw) as cb:
        reqs = [cb.submit(prompts[0], 5, seed=42),
                cb.submit(prompts[1], 5, seed=7),
                cb.submit(prompts[2], 5, seed=9)]
        crowded = reqs[0].result(timeout=300)
        other = cb.submit(prompts[0], 5, seed=43).result(timeout=300)
    np.testing.assert_array_equal(alone, crowded)
    assert not np.array_equal(alone, other)


def test_scheduler_iteration_is_covered_by_spans(lm):
    """Every pass of the loop is one `serve.iter` span whose children name
    each phase: with a decode slowed to the scale of a real step (50 ms)
    they cover >= 95 % of the time of the passes that decode, and the
    passes' counters add up to exactly what was served. The share is of
    the decoding passes' summed duration, and of the median pass, not of
    each pass (PERF.md section 6: checks on the loop hold on means): one
    preemption between two spans, under six busy test workers, is 5 % of
    a 60 ms pass but not of the run's."""
    import time

    from flexflow_tpu import obs

    prompts = _prompts([4, 7, 3, 6, 5], seed=2)
    tr = obs.enable_tracing()
    with ContinuousBatcher(lm, max_len=12, num_slots=2, page_size=4,
                           max_queue=8, queue_pages_budget=64) as cb:
        decode = cb._decode_fn

        def slow_decode(*args):
            time.sleep(0.05)
            return decode(*args)

        cb._decode_fn = slow_decode
        outs = [r.result(timeout=300)
                for r in [cb.submit(p, 5) for p in prompts]]
    evs = [e for e in tr.events() if e["ph"] == "X"
           and e["name"].startswith("serve.")]
    iters = [e for e in evs if e["name"] == "serve.iter"]
    assert [it["args"]["iter"] for it in iters] == \
        list(range(1, len(iters) + 1))

    def inside(e, it):
        return (e is not it and e["tid"] == it["tid"] and it["ts"] <= e["ts"]
                and e["ts"] + e["dur"] <= it["ts"] + it["dur"] + 1e-3)

    seen = set()
    uncovered = decoding = 0.0
    shares = []
    for it in iters:
        kids = sorted((e["ts"], e["ts"] + e["dur"], e["name"])
                      for e in evs if inside(e, it))
        seen.update(k[2] for k in kids)
        covered, end = 0.0, it["ts"]
        for lo, hi, _name in kids:       # nested children count once
            covered += max(0.0, hi - max(lo, end))
            end = max(end, hi)
        if it["args"]["decode_slots"]:
            uncovered += it["dur"] - covered
            decoding += it["dur"]
            shares.append(covered / it["dur"])
    assert decoding > 0 and uncovered <= 0.05 * decoding, (
        uncovered, decoding)
    # the first pass compiles and is ten passes long: the median keeps a
    # phase that lost its span in EVERY pass from hiding behind it
    assert np.median(shares) >= 0.95, shares
    assert {"serve.schedule", "serve.prefill", "serve.decode_stage",
            "serve.decode", "serve.decode_dispatch", "serve.decode_fetch",
            "serve.emit"} <= seen
    total = {k: sum(it["args"][k] for it in iters)
             for k in ("admitted", "emitted", "retired", "prefill_tokens")}
    assert total == {"admitted": 5, "retired": 5,
                     "emitted": sum(len(o) for o in outs),
                     "prefill_tokens": sum(len(p) for p in prompts)}
    # one position a request went through the vocabulary head, however
    # many chunks its prompt took (chunk 4: three prompts took two)
    fills = tr.events("serve.prefill")
    assert len(fills) == 8 and sum(
        e["args"]["tokens"] for e in fills) == total["prefill_tokens"]
    assert sum(e["args"]["head_rows"] for e in fills) == len(prompts)
    assert cb.registry.counter(
        "ff_serving_prefill_head_rows_total", labels=("pool",)).value(
            pool=cb.pool.label) == len(prompts)
    # the decode span kept its meaning: dispatch + fetch, nothing else
    for d in tr.events("serve.decode"):
        assert d["dur"] >= 50e3 and "requests" in d["args"]


def test_serving_steps_name_what_runs_outside_the_graph(lm):
    """Sampling and the cache-span scatter carry device names in the
    lowered decode and fused-finish programs."""
    import jax.numpy as jnp

    cb = ContinuousBatcher(lm, max_len=12, num_slots=2, page_size=4)
    s = cb.num_slots
    text = cb._decode_fn.lower(
        lm.params, lm.state, cb._caches, jnp.zeros(s, jnp.int32),
        jnp.zeros(s, jnp.int32), jnp.zeros((s, 2), jnp.uint32)
    ).as_text(debug_info=True)
    assert "sample:pick" in text and "multihead_attention:" in text
    chunk = cb.prefill_chunk_tokens
    text = cb._last_chunk_fn.lower(
        lm.params, lm.state, cb._caches, cb._zero_small(),
        jnp.zeros((1, chunk), jnp.int32), jnp.asarray(0, jnp.int32), 0,
        jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32),
        jnp.zeros(2, jnp.uint32)).as_text(debug_info=True)
    assert "sample:pick" in text and "kv:scatter_span" in text


def test_continuous_admission_rejections(lm):
    with ContinuousBatcher(lm, max_len=12, num_slots=1, page_size=4,
                           max_queue=2) as cb:
        # chunked prefill (the default) removed the prompt <= window cap:
        # a 13-token prompt against the 12-token window is only rejected
        # because prompt + max_new exceeds the per-slot cache span
        with pytest.raises(RequestTooLarge, match="cache capacity"):
            cb.submit(np.ones(13, np.int32), 2)
        with pytest.raises(RequestTooLarge, match="cache capacity"):
            cb.submit(np.ones(8, np.int32), 8)
        with pytest.raises(ValueError, match="max_new_tokens"):
            cb.submit(np.ones(4, np.int32), 0)
        with pytest.raises(ValueError, match="ONE prompt"):
            cb.submit(np.ones((2, 4), np.int32), 2)
        from flexflow_tpu.obs import REGISTRY

        rej = REGISTRY.counter("ff_serving_rejections_total",
                               labels=("reason",))
        assert rej.value(reason="too_large") == 2
    # the one-shot path keeps the window cap (it pads the prompt to the
    # model's declared input length)
    with ContinuousBatcher(lm, max_len=16, num_slots=1, page_size=4,
                           max_queue=2, prefill_chunk_tokens=0) as cb:
        with pytest.raises(RequestTooLarge, match="prefill window"):
            cb.submit(np.ones(13, np.int32), 2)


def test_continuous_stop_fails_queued_typed(lm):
    """stop(): active requests finish; requests still queued fail with
    BatcherStopped; submits after stop are rejected."""
    cb = ContinuousBatcher(lm, max_len=12, num_slots=1, page_size=4,
                           max_queue=8, queue_pages_budget=64)
    cb._running = True  # accept submits; the scheduler loop never runs
    reqs = [cb.submit(p, 4) for p in _prompts([4, 4], seed=5)]
    cb.stop()
    for r in reqs:
        with pytest.raises(BatcherStopped):
            r.result(timeout=10)
        assert r.state is RequestState.FAILED
    with pytest.raises(BatcherStopped):
        cb.submit(_prompts([4])[0], 2)


def test_continuous_cancel_queued_request(lm):
    """cancel() removes a still-queued request (reservation released,
    typed RequestCancelled), and refuses once it reached a slot."""
    from flexflow_tpu.serving.sched import RequestCancelled

    cb = ContinuousBatcher(lm, max_len=12, num_slots=1, page_size=4,
                           max_queue=8, queue_pages_budget=64)
    cb._running = True  # accept submits; scheduler loop never runs
    a = cb.submit(_prompts([4], seed=8)[0], 4)
    assert cb.cancel(a) is True
    with pytest.raises(RequestCancelled):
        a.result(timeout=5)
    assert cb.admission.queue_depth() == 0
    cb._running = False
    # a FINISHED/scheduled request cannot be cancelled
    with ContinuousBatcher(lm, max_len=12, num_slots=1,
                           page_size=4) as cb2:
        b = cb2.submit(_prompts([4], seed=9)[0], 3)
        b.result(timeout=300)
        assert cb2.cancel(b) is False


def test_batcher_submit_after_stop_fails_fast():
    """submit() on a stopped batcher must fail the future with
    BatcherStopped, not enqueue into a dead queue and hang the waiter."""
    fake = _FakeModel()
    b = DynamicBatcher(fake, max_batch_size=4)
    b.start()
    b.stop()
    with pytest.raises(BatcherStopped):
        b.submit({"x": np.zeros((1, 3), np.float32)}).result(timeout=5)


# ---------------------------------------------------------------------
# server wiring: /generate, streaming, 429 backpressure
# ---------------------------------------------------------------------
def test_server_continuous_generate_and_stream(lm):
    prompts = _prompts([4, 6], seed=6)
    session = GenerativeSession(lm, max_len=12)
    refs = [session.generate(p[None, :], 5)[0] for p in prompts]
    server = InferenceServer()
    server.register_continuous(
        "clm", ContinuousBatcher(lm, max_len=12, num_slots=2, page_size=4))
    httpd = server.serve_http(port=0)
    try:
        port = httpd.server_address[1]

        def post(payload, path="/v2/models/clm/generate"):
            return urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{port}{path}",
                data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"}))

        # ragged multi-prompt: each row matches its lockstep reference
        with post({"prompt": [p.tolist() for p in prompts],
                   "max_new_tokens": 5}) as r:
            toks = json.load(r)["tokens"]
        for row, ref in zip(toks, refs):
            np.testing.assert_array_equal(row, np.asarray(ref))
        # streaming: one NDJSON line per token, then the done trailer
        with post({"prompt": prompts[0].tolist(), "max_new_tokens": 5,
                   "stream": True}) as r:
            assert r.headers["Content-Type"] == "application/x-ndjson"
            lines = [json.loads(ln) for ln in r.read().splitlines()]
        assert [ln["token"] for ln in lines[:-1]] == list(refs[0])
        assert lines[-1]["done"] is True
        assert lines[-1]["tokens"] == list(refs[0])
        # health inventory + stats carry the scheduler state
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz") as r:
            assert json.load(r)["continuous"] == ["clm"]
        assert server.stats()["_continuous"]["clm"]["completed"] >= 3
        # request that can never fit -> 400 with the typed reason
        with pytest.raises(urllib.error.HTTPError) as e400:
            post({"prompt": list(range(1, 14)), "max_new_tokens": 2})
        assert e400.value.code == 400
        assert json.load(e400.value)["reason"] == "too_large"
        # /metrics carries the serving families and stays exposition-valid
        from flexflow_tpu.obs import validate_exposition

        text = server.prometheus_text()
        validate_exposition(text)
        for fam in ("ff_kvpool_pages_used", "ff_serving_slots_active",
                    "ff_serving_ttft_ms", "ff_serving_queue_depth"):
            assert fam in text, fam
    finally:
        httpd.shutdown()
        server.shutdown()


def test_server_continuous_backpressure_429(lm):
    """Typed saturation surfaces as HTTP 429: a batcher whose queue budget
    is exhausted by a held (unscheduled) request rejects the next one."""
    server = InferenceServer()
    cb = ContinuousBatcher(lm, max_len=12, num_slots=1, page_size=4,
                           max_queue=1)
    server.register_continuous("clm", cb, start=False)
    cb._running = True  # accept submits without running the scheduler
    blocker = cb.submit(_prompts([4], seed=7)[0], 4)  # fills max_queue=1
    httpd = server.serve_http(port=0)
    try:
        port = httpd.server_address[1]
        with pytest.raises(urllib.error.HTTPError) as e429:
            urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{port}/v2/models/clm/generate",
                data=json.dumps({"prompt": [1, 2, 3],
                                 "max_new_tokens": 2}).encode()))
        assert e429.value.code == 429
        assert json.load(e429.value)["reason"] == "queue_full"
    finally:
        httpd.shutdown()
        cb._running = False
        server.shutdown()
        with pytest.raises(BatcherStopped):
            blocker.result(timeout=10)


def test_register_continuous_mode_exclusive(lm):
    server = InferenceServer()
    try:
        server.register_generative("lm", GenerativeSession(lm, max_len=12))
        with pytest.raises(ValueError, match="one serving mode"):
            server.register_continuous(
                "lm", ContinuousBatcher(lm, max_len=12, num_slots=1),
                start=False)
    finally:
        server.shutdown()


# ---------------------------------------------------------------------
# satellite regressions: DynamicBatcher + GenerativeSession padding
# ---------------------------------------------------------------------
class _FakeModel:
    """Recording stand-in for InferenceModel: no jax, just shapes."""

    def __init__(self, dim=3):
        self.input_names = ["x"]
        self.input_specs = {"x": (dim,)}
        self.batches = []

    def predict(self, inputs):
        x = inputs["x"]
        self.batches.append(x.shape[0])
        return x * 2.0


def test_batcher_caps_coalescing_at_max_batch_size():
    """The merged batch NEVER exceeds max_batch_size: the overflow request
    leads the next batch instead (pre-fix, 3x2 rows coalesced into one
    6-row batch against max_batch_size=4)."""
    fake = _FakeModel()
    reqs = [np.full((2, 3), i, np.float32) for i in range(3)]
    with DynamicBatcher(fake, max_batch_size=4, max_delay_ms=200.0) as b:
        futs = [b.submit({"x": r}) for r in reqs]
        outs = [f.result(timeout=30) for f in futs]
    for r, o in zip(reqs, outs):
        np.testing.assert_array_equal(o, r * 2.0)
    assert fake.batches and max(fake.batches) <= 4, fake.batches
    assert sum(fake.batches) == 6


def test_batcher_validates_at_submit_failing_only_offender():
    """One malformed request must not poison the batch it would have
    joined: bad names/shapes fail at submit(), good requests still run."""
    fake = _FakeModel()
    with DynamicBatcher(fake, max_batch_size=8, max_delay_ms=50.0) as b:
        bad_name = b.submit({"y": np.zeros((1, 3), np.float32)})
        bad_shape = b.submit({"x": np.zeros((1, 4), np.float32)})
        bad_empty = b.submit({"x": np.zeros((0, 3), np.float32)})
        good = b.submit({"x": np.ones((1, 3), np.float32)})
        np.testing.assert_array_equal(good.result(timeout=30),
                                      np.full((1, 3), 2.0))
        with pytest.raises(KeyError):
            bad_name.result(timeout=5)
        with pytest.raises(ValueError, match="trailing shape"):
            bad_shape.result(timeout=5)
        with pytest.raises(ValueError, match="leading batch dim"):
            bad_empty.result(timeout=5)


def test_batcher_stop_drains_pending_with_typed_error():
    """stop() fails still-queued futures with BatcherStopped instead of
    leaving their waiters hanging."""
    fake = _FakeModel()
    b = DynamicBatcher(fake, max_batch_size=4)
    futs = [b.submit({"x": np.zeros((1, 3), np.float32)})
            for _ in range(3)]  # never started: everything stays queued
    b.stop()
    for f in futs:
        with pytest.raises(BatcherStopped):
            f.result(timeout=5)


def test_generate_padded_rows_never_delay_eos(lm):
    """Partial-batch padding rows are finished from step 0: under sampling
    the tiled pad row draws its own tokens, and pre-fix its (non-)eos kept
    the whole batch decoding past the real row's stop (width 6, not 2)."""
    p = np.random.RandomState(11).randint(1, 50, size=(1, 4)).astype(np.int32)
    kw = dict(temperature=1.0, top_k=10, seed=5)
    free = GenerativeSession(lm, max_len=12).generate(p, 6, **kw)
    eos = int(free[0, 1])
    got = GenerativeSession(lm, max_len=12).generate(p, 6, eos_id=eos, **kw)
    assert got.shape == (1, 2), got
    np.testing.assert_array_equal(got[0], free[0, :2])
    # the chunked path honors the same early stop
    chunked = GenerativeSession(lm, max_len=12).generate(
        p, 6, eos_id=eos, tokens_per_dispatch=3, **kw)
    np.testing.assert_array_equal(chunked, got)


# ---------------------------------------------------------------------
# expert-affine admission (ISSUE 16)
# ---------------------------------------------------------------------
class _FakeReq:
    def __init__(self, sig, skips=0):
        self.expert_sig = frozenset(sig)
        self.affinity_skips = skips


def test_pick_affine_prefers_overlap_within_window():
    from flexflow_tpu.serving.sched.affinity import (overlap_fraction,
                                                     pick_affine)

    active = [frozenset({0, 1})]
    queue = [_FakeReq({2, 3}), _FakeReq({0, 1}), _FakeReq({1, 4}),
             _FakeReq({0, 1})]
    idx, outcome, frac = pick_affine(queue, active, window=4)
    assert (idx, outcome, frac) == (1, "affine", 1.0)  # ties -> lowest idx
    # outside the window the perfect match is invisible
    idx, outcome, _ = pick_affine(queue[:1] + queue[2:], active, window=1)
    assert (idx, outcome) == (0, "fifo")
    assert overlap_fraction(frozenset(), active) == 0.0


def test_pick_affine_forces_starved_head():
    from flexflow_tpu.serving.sched.affinity import pick_affine

    queue = [_FakeReq({2, 3}, skips=4), _FakeReq({0, 1})]
    idx, outcome, _ = pick_affine(queue, [frozenset({0, 1})], window=4)
    assert (idx, outcome) == (0, "forced")  # no starvation past `window`


def test_expert_affinity_batcher_parity_and_stats():
    """Affinity ON re-orders admissions only: every request's tokens
    match the lockstep GenerativeSession reference, and the scheduler
    reports its pick outcomes + overlap EWMA."""
    from flexflow_tpu.serving.sched.affinity import ExpertAffinityProbe
    from flexflow_tpu.serving.sched.bench import build_tiny_moe_lm

    lm = build_tiny_moe_lm(2, 16, vocab=32, hidden=16, heads=2, layers=1,
                           experts=4, moe_top_k=2)
    probe = ExpertAffinityProbe(lm)
    assert probe.num_experts == 4 and probe.top_k == 2
    prompts = _prompts([4, 6, 5, 3, 7, 4], seed=9, vocab=32)
    sigs = [probe.signature(p) for p in prompts]
    assert all(len(s) == 2 for s in sigs)
    assert sigs[0] == probe.signature(prompts[0])  # deterministic

    session = GenerativeSession(lm, max_len=16)
    refs = [session.generate(p[None, :], 4)[0] for p in prompts]
    with ContinuousBatcher(lm, max_len=16, num_slots=2, page_size=4,
                           expert_affinity=True,
                           affinity_window=3) as cb:
        reqs = [cb.submit(p, 4) for p in prompts]
        outs = [r.result(timeout=300) for r in reqs]
        stats = cb.stats()
    for got, ref in zip(outs, refs):
        np.testing.assert_array_equal(got, ref)
    aff = stats["affinity"]
    assert aff["window"] == 3
    assert sum(aff["picks"].values()) > 0
    if aff["overlap_ewma"] is not None:
        assert 0.0 <= aff["overlap_ewma"] <= 1.0


def test_expert_affinity_rejects_dense_models(lm):
    """expert_affinity=True on a model with no EXPERTS op fails fast at
    construction, not mid-serve."""
    with pytest.raises(ValueError, match="EXPERTS"):
        ContinuousBatcher(lm, max_len=12, num_slots=2, page_size=4,
                          expert_affinity=True)
