"""One run of one cell:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Needs a TPU with at least the chips the cell asks for, and exits non-zero
without printing a result otherwise (there is no CPU switch; the tests call
the runners directly). Earlier lines of standard output are JSON notes
(traffic generated, set-up split, window, reference); the LAST line is the
result, to the contract in BENCHMARK.json's instructions.
"""
from __future__ import annotations

import time

_T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark import harness, peaks, traffic

    manifest = harness.load_manifest()
    cell = harness.find_cell(manifest, args.workload)
    config = harness.load_config(cell["config"])
    traffic_spec = traffic.load_traffic(cell["traffic"])

    # the program's own rule for the device: a TPU or nothing
    from flexflow_tpu.runtime.platform import require_tpu

    devices = require_tpu("benchmark/run.py", int(cell["chips"]))
    peaks.peaks_for(devices[0].device_kind)   # an unknown kind stops here
    harness.apply_matmul_precision(config)
    cache_dir = harness.open_compile_cache()
    setup = harness.SetupClock(_T_PROCESS_START)
    ctx = harness.RunContext(
        manifest=manifest, cell=cell, config=config, traffic=traffic_spec,
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        devices=list(devices), setup=setup, compiles=harness.CompileClock())
    harness.log("start", workload=cell["name"], config=cell["config"],
                traffic=cell["traffic"], chips=cell["chips"], seed=args.seed,
                seconds=args.seconds, trace=args.trace,
                device_kind=devices[0].device_kind, devices=len(devices),
                compile_cache=cache_dir)
    runner = harness.module_of("runners", config["runner"])
    record = runner.run(ctx)
    return harness.emit(ctx, record)


if __name__ == "__main__":
    sys.exit(main())
