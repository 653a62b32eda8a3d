"""Builder's tool (on the chip, by hand, once when an open-loop cell is
defined): find the knee — the highest arrival rate the deployment sustains:
completed >= 98 % of offered over the window and the queue no longer at its
end than at its start — by one sweep in ONE process. The rate at 0.8 of it is
then written into the traffic file as a number.

    python3 benchmark/tests/sweep_open.py --workload <an open-loop cell> --rates 2,3,4,5,6,8 --seconds 25
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

import numpy as np  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seed", type=int, default=2_300_000_011)
    args = ap.parse_args(argv)

    from benchmark import harness, loadgen, traffic
    from benchmark.reducers import percentile
    from benchmark.runners import serve_continuous as sc
    from flexflow_tpu.runtime.platform import require_tpu

    manifest = harness.load_manifest()
    cell = harness.find_cell(manifest, args.workload)
    cfg = harness.load_config(cell["config"])
    tr = traffic.load_traffic(cell["traffic"])
    devices = require_tpu("sweep_open", 1)
    harness.apply_matmul_precision(cfg)
    harness.open_compile_cache()
    builder = harness.module_of("configs", cfg["builder"])
    vocab = int(cfg["vocab_size"])
    model, batcher = builder.build_program(cfg, tr, 1, args.seed)
    batcher.start()
    sc.warm_up(batcher, vocab, int(cfg["deployment"]["prefill_chunk_tokens"]),
               args.seed)
    os.makedirs("chiprun_out", exist_ok=True)
    ramp = float(tr["ramp_s"])
    with open(f"chiprun_out/sweep_{args.workload}.jsonl", "a") as f:
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            spec = dict(tr, rate_per_s=rate)
            n = int(np.ceil((ramp + args.seconds + 2.0) * rate)) + 4
            reqs = traffic.make_requests(spec, args.seed + k, vocab, n, 0)
            gen = loadgen.OpenLoop(
                [r.due_s for r in reqs],
                lambda i: batcher.submit(reqs[i].prompt,
                                         reqs[i].max_new_tokens))
            t_start = gen.start()
            t_open, t_close = t_start + ramp, t_start + ramp + args.seconds
            time.sleep(max(0.0, t_open - time.monotonic()))
            q0 = batcher.stats()
            time.sleep(max(0.0, t_close - time.monotonic()))
            q1 = batcher.stats()
            gen.stop()
            handles = list(gen.handles)
            t_end = time.monotonic() + 45.0
            while time.monotonic() < t_end and not all(
                    isinstance(h, Exception) or h.done() for h in handles):
                time.sleep(0.1)
            drained = all(isinstance(h, Exception) or h.done()
                          for h in handles)
            idx = [i for i in range(len(handles))
                   if t_open <= gen.due_at(i) < t_close]
            ok = [handles[i] for i in idx
                  if not isinstance(handles[i], Exception)]
            ttft = [(handles[i].t_first_token - gen.due_at(i)) * 1e3
                    for i in idx if not isinstance(handles[i], Exception)
                    and handles[i].t_first_token is not None]
            done_in = sum(1 for h in handles if not isinstance(h, Exception)
                          and h.t_done is not None and h.error is None
                          and t_open <= h.t_done <= t_close)
            gaps = sc.token_gaps_ms(ok, t_open, t_close)
            line = {"rate_per_s": rate, "due_in_window": len(idx),
                    "refused": len(idx) - len(ok),
                    "completed_over_offered": done_in / max(1, len(idx)),
                    "queue_open": q0["queue_depth"],
                    "queue_close": q1["queue_depth"],
                    "slots_open": q0["slots_active"],
                    "slots_close": q1["slots_active"],
                    "ttft_p50_ms": percentile(ttft, 50) if ttft else None,
                    "ttft_p90_ms": percentile(ttft, 90) if ttft else None,
                    "itl_p50_ms": percentile(gaps, 50) if gaps else None,
                    "itl_p95_ms": percentile(gaps, 95) if gaps else None,
                    "late_p99_ms": percentile(
                        [l * 1e3 for l in gen.late_s], 99),
                    "drained_after": drained,
                    "memory_peak_bytes": harness.memory_peak_bytes(
                        devices[:1])}
            print(json.dumps(line), flush=True)
            f.write(json.dumps(line) + "\n")
            f.flush()
            if not drained:
                sc._stop(batcher)
                batcher.start()
    sc._stop(batcher)
    return 0


if __name__ == "__main__":
    sys.exit(main())
