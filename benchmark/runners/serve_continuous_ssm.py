"""Runner of a closed-loop serving cell whose model keeps per-sequence
state beside K/V (a state-space mixer in every block) and is too large for
the reference to hold whole in float32: `serve_continuous`'s closed loop,
with the reference handed its weights one group at a time and the mixers'
own counters read for the shape functions. No router, so no fragile rule:
every served position is compared.

The timed half follows `serve_continuous.run` step for step (same set-up
phases, same window between two decode-iteration boundaries, same
`serve_out_tokens_per_s`), as `serve_continuous_bf16` does; neither file
can be edited by the PR that added this one, and a `benchmark` PR should
fold the three (PERF.md section 7).
"""
from __future__ import annotations

import gc
import resource
import time
from typing import Dict, List, Sequence

import numpy as np

from .. import harness, traffic as traffic_mod
from .serve_continuous import (_finished, _sample_for_check, _stop,
                               all_token_times, decode_boundaries,
                               fill_backlog, token_gaps_ms, warm_up,
                               window_work)
from .serve_continuous_bf16 import _delta, serve_checks


def ssm_counts(batcher) -> Dict[str, float]:
    """The mixers' counters, summed over the layers (decode iterations
    thread them; the batcher counts the resets): one-token steps, slot rows
    whose state a step read and wrote, admissions."""
    from flexflow_tpu.ops.latent_attention import wide_count

    got = [v for v in batcher.op_counters().values()
           if "state_rows_stepped" in v]
    return {"ssm_layer_steps": float(sum(int(v["ssm_steps"]) for v in got)),
            "ssm_state_rows_stepped": float(sum(
                wide_count(v["state_rows_stepped"]) for v in got)),
            "ssm_state_resets": float(max(
                [int(v["state_resets"]) for v in got], default=0))}


def reference_gaps(cfg: Dict, builder, seed: int, sample,
                   controls: Sequence[str] = ()) -> Dict:
    """The reference's gaps for a sample of finished requests (and for each
    control or fault put in the program's place), the weights made one
    group at a time and freed after use."""
    ref = harness.module_of("reference", cfg["reference"])
    longest = max(len(r.prompt) + len(r.tokens) for r in sample)
    return ref.served_gaps(
        lambda name: builder.make_group(cfg, seed, name), cfg,
        [np.asarray(r.prompt) for r in sample],
        [np.asarray(r.tokens, np.int32) for r in sample],
        pad_to=ref.pad_length(longest, int(cfg["deployment"]["max_len"])),
        controls=list(controls))


def gap_numbers(gaps: List[np.ndarray], unanswered: int = 0):
    """Every number the comparison may name, from per-position gaps."""
    g = np.concatenate([np.asarray(x, np.float64) for x in gaps]) \
        if len(gaps) else np.array([np.inf])
    return {"served_logit_gap_max": float(g.max()),
            "served_logit_gap_mean": float(g.mean()),
            "served_off_best_share": float((g > 0).mean()),
            "unanswered": float(unanswered),
            "served_logit_gap_p99": float(np.percentile(g, 99)),
            "tokens_compared": float(g.size)}


def run(ctx: harness.RunContext) -> harness.Record:
    cfg, tr = ctx.config, ctx.traffic
    dep = {**cfg["deployment"], **ctx.sizes}
    cfg = {**cfg, "deployment": dep}
    tr = {**tr, **ctx.sizes.get("traffic", {})}
    if tr["loop"] != "closed_backlog":
        raise ValueError("serve_continuous_ssm runs closed-loop cells")
    builder = harness.module_of("configs", cfg["builder"])
    vocab = int(cfg["vocab_size"])
    slots = int(dep["num_slots"])

    model, batcher = builder.build_program(cfg, tr, ctx.chips, ctx.seed)
    ctx.setup.lap("build_compile_init")
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
    handles = fill_backlog(submit, count, first_wave,
                           int(tr["first_wave"]["group"]))
    time.sleep(float(tr["settle_s"]))
    t_open = time.monotonic()
    ctx.setup.lap("fill")
    setup_s = ctx.setup.window_opens()
    harness.log("setup", setup_s=setup_s, phases=ctx.setup.phases,
                compile_s=c1["seconds"], compiles=c1["count"],
                cache_hits=c1["hits"], cache_misses=c1["misses"])

    ssm_open = ssm_counts(batcher)
    work: Dict[str, float] = {}
    if ctx.trace:
        tracer.clear()          # spans from here on are the window's
        cap = harness.TraceCapture()
        cap.__enter__()
        time.sleep(min(ctx.trace_seconds, ctx.seconds))
        cap.__exit__(None, None, None)
    t_close = t_open + ctx.seconds
    time.sleep(max(0.0, t_close - time.monotonic()))

    # the window runs from the first decode-iteration boundary at or after
    # its opening to the first one `--seconds` later
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
                "the backlog emptied before the window closed: the traffic"
                " file's backlog_requests is too small for --seconds")
    ssm_window = _delta(ssm_counts(batcher), ssm_open)
    batcher.publish_op_counters()
    c2 = ctx.compiles.snapshot()
    _stop(batcher)
    peak = harness.memory_peak_bytes(ctx.devices[:ctx.chips])

    ok = [h for h in handles if not isinstance(h, Exception)]
    refused = len(handles) - len(ok)
    series: Dict[str, List[float]] = {}
    e2e: Dict[str, float] = {"setup_s": setup_s}
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
    harness.log("window", seconds=t1 - t0, tokens=emitted,
                decode_iterations=iters,
                tokens_per_iteration=emitted / max(1, iters),
                finished=len(done_in), refused=refused,
                queue_at_close=len([h for h in ok if not h.token_times]),
                memory_peak_bytes=peak, **ssm_window)
    counters = {"setup_compile_s": c1["seconds"],
                "window_compiles": c2["count"] - c1["count"], **ssm_window}

    if cap is not None:
        work.update(window_work(ok, cap.t0, cap.t1))
        # rows a step reads and writes per mixer: every row of the pool
        if ssm_window["ssm_layer_steps"] > 0:
            work["state_rows_per_layer_step"] = (
                ssm_window["ssm_state_rows_stepped"]
                / ssm_window["ssm_layer_steps"])
        work["prefill_chunk_tokens"] = float(dep["prefill_chunk_tokens"])
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
    gc.collect()
    summary = cap.summary() if cap is not None else None
    stats = ctx.devices[0].memory_stats() or {}
    t_ref = time.perf_counter()
    got = reference_gaps(cfg, builder, ctx.seed, sample) if sample \
        else {"program": []}
    checks, notes = serve_checks(gap_numbers(got["program"], failed),
                                 cfg["checks"])
    harness.log("reference", seconds=time.perf_counter() - t_ref,
                memory_held_before_bytes=int(stats.get("bytes_in_use", 0)),
                memory_limit_bytes=int(stats.get("bytes_limit", 0)),
                memory_peak_after_bytes=harness.memory_peak_bytes(
                    ctx.devices[:ctx.chips]),
                host_rss_peak_bytes=resource.getrusage(
                    resource.RUSAGE_SELF).ru_maxrss * 1024,
                requests_compared=len(sample),
                longest=max((len(r.prompt) + len(r.tokens) for r in sample),
                            default=0), **notes)
    return harness.Record(
        end_to_end=e2e, attempted=attempted, failed=failed, checks=checks,
        memory_peak_bytes=peak, series=series, counters=counters, work=work,
        trace=summary, notes=notes)
