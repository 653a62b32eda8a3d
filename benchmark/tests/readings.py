"""Builder's tool (run on the chip by hand, never by a benchmark run): the
readings a limit is set from, for one cell, many seeds, ONE process so the
set-up is paid once.

    python3 benchmark/tests/readings.py --workload bert_train_1chip --seeds 12

For every seed it prints one JSON line with
  program   the timed path's numbers against the float32 reference (lower)
  control   the reference in the configuration's `control_precision`, put in
            the program's place (upper)
  faults    training: the reference with half (or, on four chips, a quarter:
            the exchange between chips left out) of the batch used
each with `correct`: what `Record.correct` says of those numbers at the
cell's own limits (the configuration's `checks`) — true for the program,
false for a control or a fault, or the limits do not hold. All lines go to
chiprun_out/readings_<workload>.jsonl. `--more-controls a,b` reads further
precisions of the reference; `--skip-program 1` (training) reads controls
and faults alone, without building the program.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

_T0 = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np  # noqa: E402


def _train(ctx, seeds, out):
    from benchmark import check, harness, traffic as traffic_mod
    from benchmark.runners import train_fit

    cfg, tr = ctx.config, ctx.traffic
    dep = cfg["deployment"]
    builder = harness.module_of("configs", cfg["builder"])
    chips = ctx.chips
    K, bs = int(dep["steps_per_execution"]), int(dep["per_chip_batch"]) * chips
    seq = int(cfg["sequence_length"])
    skip_program = bool(ctx.sizes.get("skip_program"))
    model = None if skip_program else builder.build_program(
        cfg, tr, chips, seeds[0])
    for n, seed in enumerate(seeds):
        t = time.perf_counter()
        x, y = traffic_mod.make_train_rows(
            tr, seed, int(cfg["vocab_size"]), K * bs, seq,
            int(cfg["num_labels"]))
        xs, ys = x.reshape(K, bs, seq), y[:, :, 0].reshape(K, bs, seq)
        ref = train_fit.reference_readings(cfg, builder, seed, xs, ys, K)
        line = {"seed": seed, "reference_loss": ref["loss"],
                "reference_losses": ref["losses"]}

        def against_ref(other):
            other["moment_rel_diffs"] = check.rel_diffs(
                other.pop("moment_host", None) or other["moments"],
                ref["moments"])
            other.pop("moments", None)
            c, notes = check.train_checks(other, ref, cfg["checks"])
            return {"correct": harness.Record({}, 1, 0, c, 0).correct,
                    **{k: v["value"] for k, v in c.items()}, **notes}

        if not skip_program:
            if n:
                builder.install_weights(model, cfg, seed)
            model.fit([x], y, batch_size=bs, epochs=1, steps_per_execution=K)
            prog = train_fit.program_readings(model, builder, cfg, seed)
            line["program_loss"] = prog["loss"]
            line["program"] = against_ref(prog)
        if n < ctx.sizes.get("control_seeds", 3):
            for name in [cfg["control_precision"]] + ctx.sizes.get(
                    "more_controls", []):
                ctl = train_fit.reference_readings(
                    cfg, builder, seed, xs, ys, K, prec=name)
                key = "control" if name == cfg["control_precision"] \
                    else f"control_{name}"
                line[key] = against_ref(ctl)
            part = bs // 2 if chips == 1 else bs // chips
            flt = train_fit.reference_readings(
                cfg, builder, seed, xs, ys, K, keep_rows=np.arange(part))
            line["fault_part_of_batch"] = {"rows_used": part,
                                           **against_ref(flt)}
        line["seconds"] = time.perf_counter() - t
        out(line)
        gc.collect()


def _serve(ctx, seeds, out):
    from benchmark import check, harness, traffic as traffic_mod
    from benchmark.runners import serve_continuous as sc

    cfg, tr = ctx.config, ctx.traffic
    dep = cfg["deployment"]
    builder = harness.module_of("configs", cfg["builder"])
    vocab, slots = int(cfg["vocab_size"]), int(dep["num_slots"])
    open_loop = tr["loop"] == "open_poisson"
    model, batcher = builder.build_program(cfg, tr, 1, seeds[0])

    def read(gaps):
        c, notes = check.serve_checks(gaps, cfg["checks"])
        return {"correct": harness.Record({}, 1, 0, c, 0).correct,
                **{k: v["value"] for k, v in c.items()}, **notes}

    for n, seed in enumerate(seeds):
        t = time.perf_counter()
        if n:
            builder.install_weights(model, cfg, seed)
        batcher.start()
        if n == 0:
            sc.warm_up(batcher, vocab, int(dep["prefill_chunk_tokens"]), seed)
        secs = ctx.seconds
        if open_loop:
            count = int(np.ceil((secs + 2.0) * float(tr["rate_per_s"]))) + 4
            reqs = traffic_mod.make_requests(tr, seed, vocab, count, 0)
            from benchmark import loadgen

            gen = loadgen.OpenLoop(
                [r.due_s for r in reqs],
                lambda i: batcher.submit(reqs[i].prompt,
                                         reqs[i].max_new_tokens))
            gen.start()
            time.sleep(secs)
            gen.stop()
            handles = [h for h in gen.handles if not isinstance(h, Exception)]
            # a late answer is late, not wrong: wait for what was sent
            t_end = time.monotonic() + 60.0
            while time.monotonic() < t_end and not all(
                    h.done() for h in handles):
                time.sleep(0.1)
        else:
            reqs = traffic_mod.make_requests(
                tr, seed, vocab, int(tr["backlog_requests"]), slots)
            handles = sc.fill_backlog(
                lambda i: batcher.submit(reqs[i].prompt,
                                         reqs[i].max_new_tokens),
                len(reqs), slots, int(tr["first_wave"]["group"]))
            time.sleep(secs)
        sc._stop(batcher)
        done = [h for h in handles if sc._finished(h)]
        sample = sc._sample_for_check(done, int(tr["check_requests"]), seed)
        line = {"seed": seed, "finished": len(done),
                "program": read(sc.reference_gaps(cfg, builder, seed, sample)),
                "control": read(sc.reference_gaps(
                    cfg, builder, seed, sample,
                    control=cfg["control_precision"]))}
        for name in ctx.sizes.get("more_controls", []):
            line[f"control_{name}"] = read(sc.reference_gaps(
                cfg, builder, seed, sample, control=name))
        line["seconds"] = time.perf_counter() - t
        out(line)
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2_200_000_001)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--more-controls", default="")
    ap.add_argument("--skip-program", type=int, default=0)
    args = ap.parse_args(argv)

    from benchmark import harness, traffic
    from flexflow_tpu.runtime.platform import require_tpu

    manifest = harness.load_manifest()
    cell = harness.find_cell(manifest, args.workload)
    config = harness.load_config(cell["config"])
    devices = require_tpu("readings", int(cell["chips"]))
    harness.apply_matmul_precision(config)
    harness.open_compile_cache()
    ctx = harness.RunContext(
        manifest=manifest, cell=cell, config=config,
        traffic=traffic.load_traffic(cell["traffic"]), seed=args.first_seed,
        seconds=args.seconds, trace=False, devices=list(devices),
        setup=harness.SetupClock(_T0), compiles=harness.CompileClock(),
        sizes={"control_seeds": args.control_seeds,
               "skip_program": args.skip_program,
               "more_controls": [c for c in args.more_controls.split(",") if c]})
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    os.makedirs("chiprun_out", exist_ok=True)
    path = f"chiprun_out/readings_{args.workload}.jsonl"
    with open(path, "a") as f:
        def out(line):
            print(json.dumps(line), flush=True)
            f.write(json.dumps(line) + "\n")
            f.flush()

        fn = _train if config["runner"] == "train_fit" else _serve
        fn(ctx, seeds, out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
