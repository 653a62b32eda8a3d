"""Runner of a serving cell: `ContinuousBatcher.submit()` ->
`GenRequest` token streams, under a traffic file's loop:

  closed_backlog  the first wave fills the slots in small groups (mid-life:
                  each first request's output is cut to a seeded fraction, so
                  the slots are out of step from the start), then the whole
                  backlog is queued BEFORE the window opens: no generator
                  thread runs inside the window. The rate counts tokens as
                  they are emitted between two decode-iteration boundaries.
  open_poisson    arrivals at the file's fixed rate from `loadgen.OpenLoop`,
                  a ramp before the window opens, latencies from each
                  request's DUE time.

Everything is read after the window from what the program's public handles
recorded on the host clock (`GenRequest.token_times`, `t_first_token`,
`queue_wait_s`); the main thread sleeps through the window.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List

import numpy as np

from .. import check, harness, loadgen, traffic as traffic_mod, weights
from ..reducers import percentile

ITER_GAP_S = 0.004     # token stamps closer than this are one iteration


def warm_up(batcher, vocab: int, chunk: int, seed: int) -> None:
    """Every program the traffic will drive, once: a prompt of two chunks
    (the chunk step, the fused last chunk with its scatter and first token,
    the prefix-cache insert), then decode iterations."""
    rng = np.random.default_rng([int(seed), 0x3A93])
    reqs = [batcher.submit(rng.integers(0, vocab, size=n, dtype=np.int32), 4)
            for n in (chunk + chunk // 2, max(2, chunk // 4))]
    for r in reqs:
        r.result(timeout=1100.0)


def decode_boundaries(reqs) -> np.ndarray:
    """End times of the decode iterations, from the stamps of every
    non-first token (a first token comes from a prefill, between
    iterations)."""
    ts = np.sort(np.concatenate(
        [np.asarray(r.token_times[1:], np.float64) for r in reqs]
        + [np.zeros(0)]))
    if ts.size == 0:
        return ts
    last = np.append(np.diff(ts) > ITER_GAP_S, True)
    return ts[last]


def all_token_times(reqs) -> np.ndarray:
    return np.sort(np.concatenate(
        [np.asarray(r.token_times, np.float64) for r in reqs] + [np.zeros(0)]))


def token_gaps_ms(reqs, lo: float, hi: float) -> List[float]:
    """Gaps between consecutive tokens of one request whose later token fell
    in [lo, hi], all requests together."""
    out: List[float] = []
    for r in reqs:
        t = np.asarray(r.token_times, np.float64)
        if t.size < 2:
            continue
        d = np.diff(t)
        keep = (t[1:] >= lo) & (t[1:] <= hi)
        out.extend((d[keep] * 1e3).tolist())
    return out


def window_work(reqs, lo: float, hi: float) -> Dict[str, float]:
    """Tokens the host-clock interval [lo, hi] processed, for the shape
    functions: decoded tokens with the rows each attended (its sequence's
    filled length), and the prompts whose prefill ended there."""
    dec_tokens = dec_rows = prompt_tokens = prefill_rows = 0
    for r in reqs:
        t = np.asarray(r.token_times, np.float64)
        if t.size == 0:
            continue
        plen = int(len(r.prompt))
        if lo <= t[0] <= hi:
            prompt_tokens += plen
            prefill_rows += plen * (plen + 1) // 2
        j = np.nonzero((t[1:] >= lo) & (t[1:] <= hi))[0] + 1
        dec_tokens += int(j.size)
        dec_rows += int((plen + j).sum())
    return {"decode_tokens": float(dec_tokens),
            "decode_attended_rows": float(dec_rows),
            "prompt_tokens": float(prompt_tokens),
            "prefill_attended_rows": float(prefill_rows)}


def fill_backlog(submit, count: int, first_wave: int, group: int) -> List:
    """The closed loop's fill: the first wave in groups of `group` (each
    prefilling slot holds a batch-1 cache of its own, 0.25 GB at the cell's
    size, so the slots are not all filled at once), every group waited for
    until it decodes; then the whole backlog into the queue."""
    handles = []
    for g0 in range(0, first_wave, group):
        hs = [submit(i) for i in range(g0, min(g0 + group, first_wave))]
        handles.extend(hs)
        while not all(h.t_first_token is not None or h.done() for h in hs):
            time.sleep(0.02)
    handles.extend(submit(i) for i in range(first_wave, count))
    return handles


def _finished(r) -> bool:
    return r.done() and r.error is None


def _stop(batcher) -> None:
    """End the scheduler now: what is still decoding is cut off (only
    finished requests are compared), and the thread is waited for."""
    batcher.abort(RuntimeError("benchmark window closed"))
    for _ in range(600):
        if not batcher.scheduler_alive():
            return
        time.sleep(0.1)
    raise RuntimeError("the scheduler thread did not stop")


def _sample_for_check(done: List, n: int, seed: int) -> List:
    """`n` finished requests drawn from the seed, the longest among them."""
    if not done:
        return []
    rng = np.random.default_rng([int(seed), 0xC4EC])
    longest = max(range(len(done)),
                  key=lambda i: len(done[i].prompt) + len(done[i].tokens))
    rest = [i for i in range(len(done)) if i != longest]
    pick = [longest] + list(rng.permutation(rest)[:max(0, n - 1)])
    return [done[i] for i in pick]


def reference_gaps(cfg: Dict, builder, seed: int, sample, control: str = ""):
    ref = harness.module_of("reference", cfg["reference"])
    params = weights.make_weights(builder.param_spec(cfg), seed, "float32")
    return ref.served_gaps(
        params, cfg, [np.asarray(r.prompt) for r in sample],
        [np.asarray(r.tokens, np.int32) for r in sample],
        pad_to=int(cfg["deployment"]["max_len"]), control_prec=control)


def run(ctx: harness.RunContext) -> harness.Record:
    cfg, tr = ctx.config, ctx.traffic
    dep = {**cfg["deployment"], **ctx.sizes}
    cfg = {**cfg, "deployment": dep}
    tr = {**tr, **ctx.sizes.get("traffic", {})}
    builder = harness.module_of("configs", cfg["builder"])
    vocab = int(cfg["vocab_size"])
    slots = int(dep["num_slots"])
    open_loop = tr["loop"] == "open_poisson"

    model, batcher = builder.build_program(cfg, tr, ctx.chips, ctx.seed)
    ctx.setup.lap("build_compile_init")
    if open_loop:
        span = float(tr["ramp_s"]) + ctx.seconds + float(tr["tail_s"])
        count = int(np.ceil(span * float(tr["rate_per_s"]))) + 8
        first_wave = 0
    else:
        count = int(tr["backlog_requests"])
        first_wave = slots
    reqs_in = traffic_mod.make_requests(tr, ctx.seed, vocab, count, first_wave)
    harness.log("traffic", **traffic_mod.describe(reqs_in, tr))
    ctx.setup.lap("data")

    if ctx.trace:
        from flexflow_tpu.obs.tracing import enable_tracing

        tracer = enable_tracing()
    batcher.start()
    warm_up(batcher, vocab, int(dep["prefill_chunk_tokens"]), ctx.seed)
    ctx.setup.lap("warmup")
    c1 = ctx.compiles.snapshot()

    submit = lambda i: batcher.submit(reqs_in[i].prompt,
                                      reqs_in[i].max_new_tokens)
    cap = None
    if open_loop:
        gen = loadgen.OpenLoop([r.due_s for r in reqs_in], submit)
        t_start = gen.start()
        t_open = t_start + float(tr["ramp_s"])
        time.sleep(max(0.0, t_open - time.monotonic()))
        handles = gen.handles
    else:
        handles = fill_backlog(submit, count, first_wave,
                               int(tr["first_wave"]["group"]))
        time.sleep(float(tr["settle_s"]))
        t_open = time.monotonic()
    ctx.setup.lap("fill")
    setup_s = ctx.setup.window_opens()
    harness.log("setup", setup_s=setup_s, phases=ctx.setup.phases,
                compile_s=c1["seconds"], compiles=c1["count"],
                cache_hits=c1["hits"], cache_misses=c1["misses"])

    if ctx.trace:
        tracer.clear()          # spans from here on are the window's
    if ctx.trace and not open_loop:
        cap = harness.TraceCapture()
        cap.__enter__()
        time.sleep(min(ctx.trace_seconds, ctx.seconds))
        cap.__exit__(None, None, None)
    t_close = t_open + ctx.seconds
    time.sleep(max(0.0, t_close - time.monotonic()))

    if open_loop and ctx.trace:
        # the open loop is traced AFTER the window, under the same arrivals,
        # and the arrivals are stopped before the profiler is: stopping it
        # stalls the scheduler for seconds, the requests that arrive
        # meanwhile are then admitted all at once, each with a batch-1 cache
        # of its own, and sixteen of those are more than the chip holds
        # (seen on the chip, PERF.md section 6)
        cap = harness.TraceCapture()
        cap.__enter__()
        time.sleep(min(ctx.trace_seconds, float(tr["tail_s"]) / 2))
    if open_loop:
        # every request due in the window is waited for (a late answer is
        # late, not wrong); arrivals go on meanwhile, so the system stays
        # under the load the last requests were offered into
        deadline = t_close + min(60.0, float(tr["tail_s"]))
        while time.monotonic() < deadline:
            due_in = [h for i, h in enumerate(list(handles))
                      if t_open <= gen.due_at(i) < t_close]
            if all(isinstance(h, Exception) or h.t_first_token is not None
                   or h.done() for h in due_in) and len(gen.sent_at) > 0 \
                    and gen.due_at(len(gen.sent_at) - 1) >= t_close:
                break
            time.sleep(0.05)
        gen.stop()
        if cap is not None:
            cap.__exit__(None, None, None)
    else:
        # the window runs from the first decode-iteration boundary at or
        # after its opening to the first one `--seconds` later
        while True:
            time.sleep(0.25)
            b = decode_boundaries([h for h in handles if h.token_times])
            b = b[b >= t_open]
            if b.size and b[-1] >= b[0] + ctx.seconds:
                break
            if not batcher.scheduler_alive():
                raise RuntimeError("the scheduler died inside the window")
            if all(h.done() for h in handles):
                raise RuntimeError(
                    "the backlog emptied before the window closed: the"
                    " traffic file's backlog_requests is too small for"
                    " --seconds")
    c2 = ctx.compiles.snapshot()
    _stop(batcher)
    peak = harness.memory_peak_bytes(ctx.devices[:ctx.chips])

    ok = [h for h in handles if not isinstance(h, Exception)]
    refused = len(handles) - len(ok)
    series: Dict[str, List[float]] = {}
    e2e: Dict[str, float] = {"setup_s": setup_s}
    if open_loop:
        idx_in = [i for i in range(len(handles))
                  if t_open <= gen.due_at(i) < t_close]
        ttft, waits = [], []
        unanswered = 0
        for i in idx_in:
            h = handles[i]
            if isinstance(h, Exception) or h.t_first_token is None:
                unanswered += 1
                continue
            ttft.append((h.t_first_token - gen.due_at(i)) * 1e3)
            if h.queue_wait_s is not None:
                waits.append((h.t_submit - gen.due_at(i)
                              + h.queue_wait_s) * 1e3)
        worst = max(ttft + [(time.monotonic() - t_open) * 1e3])
        ttft_all = ttft + [worst] * unanswered
        gaps = token_gaps_ms(ok, t_open, t_close)
        series["ttft_ms"] = ttft_all
        e2e["itl_p95_ms"] = percentile(gaps, 95) if gaps else float("nan")
        e2e["ttft_p90_ms"] = percentile(ttft_all, 90)
        series["queue_wait_ms"] = waits
        in_window = set(idx_in)
        series["loadgen_late_ms"] = [
            l * 1e3 for i, l in enumerate(gen.late_s) if i in in_window]
        series["itl_ms"] = gaps
        attempted, failed = len(idx_in), unanswered
        done_in = [handles[i] for i in idx_in
                   if not isinstance(handles[i], Exception)
                   and _finished(handles[i])]
        harness.log("window", seconds=ctx.seconds, due=len(idx_in),
                    unanswered=unanswered, refused=refused,
                    finished=len(done_in), ttft_p50_ms=percentile(ttft_all, 50),
                    ttft_p90_ms=e2e["ttft_p90_ms"],
                    itl_p95_ms=e2e["itl_p95_ms"], token_gaps=len(gaps),
                    queue_at_close=batcher.stats()["queue_depth"],
                    memory_peak_bytes=peak)
    else:
        live = [h for h in ok if h.token_times]
        b = decode_boundaries(live)
        t0 = float(b[b >= t_open][0])
        t1 = float(b[b >= t0 + ctx.seconds][0])
        stamps = all_token_times(live)
        emitted = int(((stamps > t0) & (stamps <= t1)).sum())
        iters = int(((b > t0) & (b <= t1)).sum())
        e2e["serve_out_tokens_per_s"] = emitted / (t1 - t0)
        series["itl_ms"] = token_gaps_ms(live, t0, t1)
        unanswered = sum(1 for h in ok if h.done() and h.error is not None
                         and h.t_done is not None and h.t_done <= t1)
        done_in = [h for h in ok if _finished(h)]
        attempted = sum(1 for h in ok if h.token_times
                        and h.token_times[0] <= t1)
        failed = unanswered + refused
        t_open, t_close = t0, t1
        harness.log("window", seconds=t1 - t0, tokens=emitted,
                    decode_iterations=iters,
                    tokens_per_iteration=emitted / max(1, iters),
                    finished=len(done_in), refused=refused,
                    queue_at_close=len([h for h in ok if not h.token_times]),
                    memory_peak_bytes=peak)
    counters = {"setup_compile_s": c1["seconds"],
                "window_compiles": c2["count"] - c1["count"]}

    work: Dict[str, float] = {}
    if cap is not None:
        work = window_work(ok, cap.t0, cap.t1)
        for name in ("serve.decode", "serve.prefill"):
            evs = tracer.events(name)
            series[f"span:{name}"] = [e["dur"] / 1e3 for e in evs
                                      if e.get("ph") == "X"]
        from flexflow_tpu.obs.tracing import disable_tracing

        disable_tracing()

    sample = _sample_for_check(done_in, int(tr["check_requests"]), ctx.seed)
    # free the program's state before the reference touches the chip
    model.params = model.state = None
    del batcher, model, submit
    if open_loop:
        del gen
    gc.collect()
    summary = cap.summary() if cap is not None else None
    t_ref = time.perf_counter()
    gaps_ref = reference_gaps(cfg, builder, ctx.seed, sample) if sample else []
    checks, notes = check.serve_checks(gaps_ref, cfg["checks"], failed)
    harness.log("reference", seconds=time.perf_counter() - t_ref,
                requests_compared=len(sample),
                longest=max((len(r.prompt) + len(r.tokens) for r in sample),
                            default=0), **notes)
    return harness.Record(
        end_to_end=e2e, attempted=attempted, failed=failed, checks=checks,
        memory_peak_bytes=peak, series=series, counters=counters, work=work,
        trace=summary, notes=notes)
