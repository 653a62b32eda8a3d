"""Builder's tool (run on the chip by hand, never by a benchmark run): ONE
set-up of a cell with the profiler open from before `build_program` to the
moment the window would open, to say what the host was doing where the
program's own record (`metrics/startup.py`) has no name.

    python3 benchmark/tests/readings_setup.py --workload lm_decode_sat --seed 3500000001

The laps are the runners' own (`build_compile_init`, `warmup` or
`first_dispatch`; the traffic's `data` and `fill` are left out), each under
an annotation `bench.lap.<name>`, so the trace holds them beside the
program's phases and `first_dispatch` spans (every `Tracer.span()` is a
profiler annotation) on one clock. Reported, to
chiprun_out/readings_setup_<workload>.json and as one JSON line:

  laps              per lap its seconds, the top-level program spans inside
                    it, and the host events with the most SELF time on the
                    threads that ran program spans, outside those spans:
                    what the lap's unnamed part is made of
  first_dispatch    per program its span's seconds and the host events with
                    the most self time inside it (on its own thread: the
                    compiler's pool threads are not counted)
  registry          the eight `setup_*_s` metrics as `metrics/startup.py`
                    reads them from this process
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_T0 = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

TOP_LEVEL = ("compile", "serve.build", "startup.platform", "first_dispatch")
EXECUTOR = ("executor.train_step", "executor.multi_step",
            "executor.eval_step", "executor.forward")
NEW = ("setup_import_s", "setup_platform_init_s", "setup_model_compile_s",
       "setup_init_params_s", "setup_serve_build_s", "setup_first_dispatch_s",
       "setup_trace_lower_s", "setup_unnamed_s")


def self_times(events, lo_ps, hi_ps, skip=()):
    """{name: seconds} of self time (an event's duration less the events
    nested in it) of one thread's events clipped to [lo, hi); events named
    in `skip`, and everything under them, count nothing."""
    out, stack = {}, []      # stack: [name, end, self_ps, skipped]
    evs = sorted((e for e in events if e.end_ps > lo_ps
                  and e.start_ps < hi_ps),
                 key=lambda e: (e.start_ps, -e.dur_ps))

    def close(until):
        while stack and stack[-1][1] <= until:
            name, _end, own, skipped = stack.pop()
            if not skipped:
                out[name] = out.get(name, 0.0) + own / 1e12

    for e in evs:
        close(e.start_ps)
        lo, hi = max(e.start_ps, lo_ps), min(e.end_ps, hi_ps)
        if stack:
            stack[-1][2] -= hi - lo
        stack.append([e.name, e.end_ps, hi - lo,
                      e.name in skip or (bool(stack) and stack[-1][3])])
    close(float("inf"))
    return out


def top(d, k=14):
    return [[n, round(s, 4)] for n, s in
            sorted(d.items(), key=lambda kv: -kv[1])[:k] if s >= 0.01]


def readings(cfg, tr, chips: int, seed: int, setup) -> dict:
    """The traced set-up of one configuration (its `deployment` as it is to
    be run); `setup`: the run's `SetupClock`, lapped here."""
    import jax

    from benchmark import harness, traffic as traffic_mod
    from benchmark.metrics import startup

    builder = harness.module_of("configs", cfg["builder"])
    dep = cfg["deployment"]

    def lap(name):
        return jax.profiler.TraceAnnotation(f"bench.lap.{name}")

    cap = harness.TraceCapture()
    cap.__enter__()
    if cfg["runner"] == "train_fit":
        with lap("build_compile_init"):
            model = builder.build_program(cfg, tr, chips, seed)
        setup.lap("build_compile_init")
        K = int(dep["steps_per_execution"])
        bs = int(dep["per_chip_batch"]) * chips
        x, y = traffic_mod.make_train_rows(
            tr, seed, int(cfg["vocab_size"]), K * bs,
            int(cfg["sequence_length"]), int(cfg["num_labels"]))
        setup.lap("data")
        with lap("first_dispatch"):
            model.fit([x], y, batch_size=bs, epochs=1, steps_per_execution=K)
        setup.lap("first_dispatch")
    else:
        from benchmark.runners import serve_continuous as sc

        with lap("build_compile_init"):
            model, batcher = builder.build_program(cfg, tr, chips, seed)
        setup.lap("build_compile_init")
        with lap("warmup"):
            batcher.start()
            sc.warm_up(batcher, int(cfg["vocab_size"]),
                       int(dep["prefill_chunk_tokens"]), seed)
        setup.lap("warmup")
        sc._stop(batcher)
    cap.__exit__(None, None, None)
    trace = cap.summary()

    class Ctx:
        pass

    ctx = Ctx()
    ctx.setup = setup
    registry = {}
    for name in NEW:
        spec = harness.load_metric(name)
        got = startup.read(spec, ctx, None)
        if got is not None:
            registry[name] = {"value": got[0], **got[1]}

    threads = [(n, evs) for n, evs in trace.host
               if any(e.name.startswith(("bench.lap.", "serve.", "fit.",
                                         "first_dispatch", "compile"))
                      for e in evs)]
    laps, first = {}, {}
    for _thread, evs in threads:
        for e in evs:
            if e.name.startswith("bench.lap."):
                laps[e.name[len("bench.lap."):]] = e
    out_laps = {}
    for name, l in laps.items():
        spans, rest = {}, {}
        for _thread, evs in threads:
            for e in evs:
                if (e.name in TOP_LEVEL or e.name.startswith("executor.")) \
                        and l.start_ps <= e.start_ps < l.end_ps:
                    # an executor's program has its first call under its own
                    # dispatch span (`traced_dispatch`)
                    key = e.name if e.name != "first_dispatch" else \
                        f"first_dispatch:{e.stats.get('program', '?')}"
                    spans[key] = spans.get(key, 0.0) + e.dur_ps / 1e12
            for k, v in self_times(evs, l.start_ps, l.end_ps,
                                   skip=TOP_LEVEL + EXECUTOR).items():
                rest[k] = rest.get(k, 0.0) + v
        out_laps[name] = {"seconds": l.dur_ps / 1e12, "program_spans": spans,
                          "outside_program_spans_top": top(rest)}
    for _thread, evs in threads:
        for e in evs:
            if e.name == "first_dispatch" or (e.name in EXECUTOR
                                              and e.name not in first):
                inside = self_times(evs, e.start_ps, e.end_ps)
                first[str(e.stats.get("program", e.name))] = {
                    "seconds": e.dur_ps / 1e12, "top": top(inside, 10)}
    return {"setup_laps": setup.phases, "laps": out_laps,
            "first_dispatch": first, "registry": registry,
            "threads": [n for n, _ in threads]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=3_500_000_001)
    args = ap.parse_args(argv)

    from benchmark import harness, traffic as traffic_mod
    from flexflow_tpu.runtime.platform import require_tpu

    cell = harness.find_cell(harness.load_manifest(), args.workload)
    cfg = harness.load_config(cell["config"])
    tr = traffic_mod.load_traffic(cell["traffic"])
    require_tpu("readings_setup", int(cell["chips"]))
    harness.apply_matmul_precision(cfg)
    harness.open_compile_cache()
    line = {"workload": args.workload, "seed": args.seed,
            **readings(cfg, tr, int(cell["chips"]), args.seed,
                       harness.SetupClock(_T0))}
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/readings_setup_{args.workload}.json", "w") as f:
        json.dump(line, f, indent=1)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
