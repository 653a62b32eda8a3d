"""Runner of a training cell: `FFModel.compile()` -> `fit(steps_per_execution=K)`.

Set-up builds ONE compiled model, drives it from the seed through its first
dispatch (K optimizer steps on rows that all differ) through `fit()` itself,
reads what the comparison needs as per-leaf norms on the device, and hands
the same object to the window. The window is whole `fit()` calls until
`--seconds` have passed; each call ends on fetched metrics, so the rate is
all tokens of all finished steps over all the time.
"""
from __future__ import annotations

import gc
import time
from typing import Dict

import numpy as np

from .. import check, harness, traffic as traffic_mod, weights


def program_readings(model, builder, cfg: Dict, seed: int) -> Dict:
    """What the first dispatch left: the mean loss of its K steps as `fit`
    reports it; per leaf the norm of Adam's first moment and of the
    parameters' change from the seeded weights; and that moment itself,
    copied to the host (0.37 GB in bf16) so that it can be held against the
    reference's once the window has closed and the program is gone."""
    recs = model.step_stats.records()
    p0 = weights.make_weights(builder.param_spec(cfg), seed, "float32",
                              weights.replicated(model.mesh))
    import jax

    out = {"loss": float(recs[0]["loss"]),
           "moment_host": jax.device_get(model.opt_state["m"]),
           "moment_norms": check.leaf_norms(model.opt_state["m"]),
           "delta_norms": check.delta_norms(model.params, p0)}
    del p0
    return out


def reference_readings(cfg: Dict, builder, seed: int, x, y, steps: int,
                       prec: str = "float32", keep_rows=None) -> Dict:
    ref = harness.module_of("reference", cfg["reference"])
    p0 = weights.make_weights(builder.param_spec(cfg), seed, "float32")
    out = ref.train_steps(p0, x, y, cfg, cfg["optimizer"], steps, prec=prec,
                          block_rows=int(cfg.get("reference_block_rows", 4)),
                          keep_rows=keep_rows)
    out["loss"] = float(np.mean(out["losses"]))
    return out


def run(ctx: harness.RunContext) -> harness.Record:
    import jax

    cfg, tr = ctx.config, ctx.traffic
    dep = {**cfg["deployment"], **ctx.sizes}
    cfg = {**cfg, "deployment": dep}
    builder = harness.module_of("configs", cfg["builder"])
    chips = ctx.chips
    K = int(dep["steps_per_execution"])
    bs = int(dep["per_chip_batch"]) * chips
    C = int(dep["dispatches_per_fit"])
    seq = int(cfg["sequence_length"])

    model = builder.build_program(cfg, tr, chips, ctx.seed)
    ctx.setup.lap("build_compile_init")
    rows = C * K * bs
    x, y = traffic_mod.make_train_rows(tr, ctx.seed, int(cfg["vocab_size"]),
                                       rows, seq, int(cfg["num_labels"]))
    harness.log("traffic", rows=rows, sequence_length=seq, global_batch=bs,
                steps_per_execution=K, dispatches_per_fit=C,
                tokens_per_fit=rows * seq, parallel_axes=model.parallel_axes)
    ctx.setup.lap("data")

    # the first dispatch: the window's own call and feed, K steps
    first = K * bs
    c0 = ctx.compiles.snapshot()
    model.fit([x[:first]], y[:first], batch_size=bs, epochs=1,
              steps_per_execution=K)
    prog = program_readings(model, builder, cfg, ctx.seed)
    ctx.setup.lap("first_dispatch")
    c1 = ctx.compiles.snapshot()

    setup_s = ctx.setup.window_opens()
    harness.log("setup", setup_s=setup_s, phases=ctx.setup.phases,
                compile_s=c1["seconds"], compiles=c1["count"],
                cache_hits=c1["hits"], cache_misses=c1["misses"])
    step_ms = []
    call_s = []
    calls = 0
    trace = None
    work: Dict[str, float] = {}
    t0 = time.perf_counter()
    while True:
        if ctx.trace and calls == 0:
            with harness.TraceCapture() as cap:
                with jax.profiler.TraceAnnotation("bench.fit"):
                    model.fit([x], y, batch_size=bs, epochs=1,
                              steps_per_execution=K)
            trace = cap
            work = {"steps": float(C * K),
                    "steps_per_dispatch": float(K),
                    "sequences_per_step_per_chip": float(bs // chips),
                    "tokens_per_chip": float(C * K * (bs // chips) * seq)}
        else:
            model.fit([x], y, batch_size=bs, epochs=1, steps_per_execution=K)
        calls += 1
        step_ms.extend(r["step_ms"] for r in model.step_stats.records())
        call_s.append(time.perf_counter() - t0 - sum(call_s))
        elapsed = time.perf_counter() - t0
        if elapsed >= ctx.seconds:
            break
    c2 = ctx.compiles.snapshot()
    tokens = calls * rows * seq
    rate = tokens / elapsed / chips
    peak = harness.memory_peak_bytes(ctx.devices[:chips])
    harness.log("window", seconds=elapsed, fit_calls=calls,
                steps=calls * C * K, tokens=tokens,
                tokens_per_s_per_chip=rate, memory_peak_bytes=peak,
                # where a slow run lost its time: every fit() call's seconds
                # and every dispatch's step time as the program clocked it
                fit_call_s=[round(c, 4) for c in call_s],
                dispatch_step_ms=[round(m, 3) for m in step_ms])

    # free the program's state, then the reference follows the first K steps
    model.params = model.opt_state = model.state = None
    model.executor = None
    del model
    gc.collect()
    summary = trace.summary() if trace is not None else None
    t_ref = time.perf_counter()
    ref = reference_readings(cfg, builder, ctx.seed,
                             x[:first].reshape(K, bs, seq),
                             y[:first, :, 0].reshape(K, bs, seq), K)
    prog["moment_rel_diffs"] = check.rel_diffs(prog.pop("moment_host"),
                                               ref["moments"])
    checks, notes = check.train_checks(prog, ref, cfg["checks"])
    harness.log("reference", seconds=time.perf_counter() - t_ref,
                program_loss=prog["loss"], reference_loss=ref["loss"],
                reference_losses=ref["losses"], **notes)
    return harness.Record(
        end_to_end={"train_tokens_per_s_per_chip": rate, "setup_s": setup_s},
        attempted=calls * C * K, failed=0, checks=checks,
        memory_peak_bytes=peak,
        series={"step_ms": step_ms},
        counters={"setup_compile_s": c1["seconds"],
                  "window_compiles": c2["count"] - c1["count"],
                  "setup_compiles": c1["count"] - c0["count"]},
        work=work, trace=summary, notes=notes)
