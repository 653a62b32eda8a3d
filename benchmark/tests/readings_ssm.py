"""Builder's tool (run on the chip by hand, never by a benchmark run): the
readings the limits of a `serve_continuous_ssm` cell are set from, many
seeds in ONE process — `readings_bf16.py`'s twin (that one reads router
margins this model does not have).

    python3 benchmark/tests/readings_ssm.py --workload fh1_decode_sat --seeds 4

First the program serves every seed's traffic for `--seconds` (one build,
the weights installed anew per seed) and the sampled requests are kept on
the host; then the program is freed and, per seed, the reference reads the
sample once in float32 (the program's tokens in place) and once per control
(`control_precision`, `more_controls`; bfloat16 and `also_read` for
information) and fault (`faults`) put in the program's place. One JSON line per seed and reading
with every number of `gap_numbers` and `correct` at the configuration's own
limits, to chiprun_out/readings_<workload>.jsonl.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np  # noqa: E402


class _Text:
    def __init__(self, prompt, tokens):
        self.prompt, self.tokens = np.asarray(prompt), list(tokens)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=4)
    ap.add_argument("--first-seed", type=int, default=2_300_000_001)
    ap.add_argument("--seconds", type=float, default=25.0)
    args = ap.parse_args(argv)

    from benchmark import harness, traffic as traffic_mod
    from benchmark.runners import serve_continuous as sc
    from benchmark.runners import serve_continuous_ssm as runner
    from flexflow_tpu.runtime.platform import require_tpu

    manifest = harness.load_manifest()
    cell = harness.find_cell(manifest, args.workload)
    cfg = harness.load_config(cell["config"])
    tr = traffic_mod.load_traffic(cell["traffic"])
    require_tpu("readings_ssm", int(cell["chips"]))
    harness.open_compile_cache()
    builder = harness.module_of("configs", cfg["builder"])
    dep = cfg["deployment"]
    vocab, slots = int(cfg["vocab_size"]), int(dep["num_slots"])
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    os.makedirs("chiprun_out", exist_ok=True)
    path = f"chiprun_out/readings_{args.workload}.jsonl"

    samples = {}
    model, batcher = builder.build_program(cfg, tr, 1, seeds[0])
    for n, seed in enumerate(seeds):
        t = time.perf_counter()
        if n:
            builder.install_weights(model, cfg, seed)
        batcher.start()
        if n == 0:
            sc.warm_up(batcher, vocab, int(dep["prefill_chunk_tokens"]), seed)
        reqs = traffic_mod.make_requests(
            tr, seed, vocab, int(tr["backlog_requests"]), slots)
        handles = sc.fill_backlog(
            lambda i: batcher.submit(reqs[i].prompt, reqs[i].max_new_tokens),
            len(reqs), slots, int(tr["first_wave"]["group"]))
        time.sleep(args.seconds)
        sc._stop(batcher)
        done = [h for h in handles if sc._finished(h)]
        samples[seed] = [_Text(h.prompt, h.tokens) for h in
                         sc._sample_for_check(done, int(tr["check_requests"]),
                                              seed)]
        print(json.dumps({"seed": seed, "finished": len(done),
                          "served_s": time.perf_counter() - t}), flush=True)
    model.params = model.state = None
    del model, batcher, handles, done
    gc.collect()

    controls = [cfg["control_precision"], *cfg.get("more_controls", []),
                "bfloat16", *cfg.get("also_read", []),
                *cfg.get("faults", [])]
    with open(path, "a") as f:
        for seed in seeds:
            t = time.perf_counter()
            got = runner.reference_gaps(cfg, builder, seed, samples[seed],
                                        controls=controls)
            for name in ["program", *controls]:
                nums = runner.gap_numbers(got[name])
                checks, _ = runner.serve_checks(nums, cfg["checks"])
                line = {"seed": seed, "reading": name,
                        "correct": harness.Record({}, 1, 0, checks,
                                                  0).correct, **nums}
                print(json.dumps(line), flush=True)
                f.write(json.dumps(line) + "\n")
            f.write(json.dumps({"seed": seed, "reference_s":
                                time.perf_counter() - t}) + "\n")
            f.flush()
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
