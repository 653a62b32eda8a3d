"""`python -m flexflow_tpu collective-bench`: measure the explicit
collective lowering on the current mesh.

Sweeps {reduction strategy} x {bytes} over all visible devices (one
'data' mesh axis, exactly the surface runtime/collectives.py lowers the
grad sync onto) and, on a hierarchical machine spec, each tier's ring
phase in isolation. Every timing lands as an obs.calibrate row
(`CollectiveCalibration`: op, strategy, tier, bytes, measured_us next to
the machine model's prediction) in
``<out>/collective_calibration.json`` — the data source
`refit.fit_collective_coefficients` fits the per-tier link constants
from, closing the loop between the tier pricing the Unity search ranks
plans with and collectives that actually ran (docs/observability.md).

``--fit-profile`` runs that fit and persists the resulting
FittedProfile as ``<out>/fitted_profile.json`` (loadable into any later
search via ``--fitted-profile``). A ``BENCH {...}`` stdout line reports
the largest-size measurement per strategy; the last stdout line is a
JSON summary and the exit code is nonzero unless every sweep point
measured a positive wall time.

All FFConfig flags pass through — ``--machine-spec`` selects the
hierarchy whose tiers are swept; without one the flat machine yields a
single "mesh" tier. The predicted side states the spec's TPU-class
constants, so on the CPU emulation the ratios are large and only the
RELATIVE per-tier slopes are meaningful — which is exactly what the fit
consumes.
"""
from __future__ import annotations

import functools
import json
import math
import os
import statistics
import sys
import time
from typing import Any, Dict, List, Optional

DEFAULT_SIZES_MB = (0.25, 1.0, 4.0)
DEFAULT_STRATEGIES = ("flat", "rs_ar_ag", "hier_ring")


def _median_wall_us(fn, args, warmup: int, repeats: int) -> float:
    import jax

    for _ in range(max(0, warmup)):
        jax.block_until_ready(fn(*args))
    samples = []
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        samples.append((time.perf_counter() - t0) * 1e6)
    return float(statistics.median(samples))


def sweep_collectives(config, sizes_bytes: List[int],
                      strategies: List[str], warmup: int = 1,
                      repeats: int = 3) -> Dict[str, Any]:
    """Run the sweep on the live devices; returns {"rows": [...],
    "n_devices", "tiers", "machine"} with rows as CollectiveCalibration
    objects."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ..runtime.collectives import lower_allreduce, tier_axis_groups
    from ..search.machine_model import make_machine_model
    from .calibration import CollectiveCalibration

    n = max(1, config.total_devices)
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise SystemExit(
            f"collective-bench: {n} devices requested but only"
            f" {len(devices)} visible")
    mesh = Mesh(np.array(devices), ("data",))
    machine = make_machine_model(config, n)
    tier_path = (machine.tier_path(n)
                 if hasattr(machine, "tier_path") else [])
    if tier_path and math.prod(ni for _, ni in tier_path) != n:
        print(f"collective-bench: machine spec tiers do not factor the"
              f" {n}-device mesh; sweeping flat", file=sys.stderr)
        tier_path = []
    group_sizes = [ni for _, ni in tier_path] or [n]
    tier_names = [t.name for t, _ in tier_path] or ["mesh"]
    groups = tier_axis_groups(n, group_sizes)
    outer_tier = tier_names[-1]
    sm = functools.partial(jax.shard_map, check_vma=False)
    rows: List[CollectiveCalibration] = []

    def timed(body, elems) -> float:
        x = jax.device_put(
            jnp.ones((n, elems), jnp.float32),
            NamedSharding(mesh, P("data")))
        fn = jax.jit(sm(body, mesh=mesh, in_specs=P("data"),
                        out_specs=P("data")))
        return _median_wall_us(fn, (x,), warmup, repeats)

    for strategy in strategies:
        if n <= 1:
            break
        if strategy != "flat" and len(group_sizes) <= 1:
            continue  # nothing to decompose on a flat machine
        for size in sizes_bytes:
            elems = max(1, int(size) // 4)

            def body(x, strategy=strategy):
                return lower_allreduce(x[0], "data", strategy,
                                       group_sizes, groups)[None]

            measured = timed(body, elems)
            bytes_ = elems * 4.0
            if hasattr(machine, "tier_path"):
                predicted = machine.allreduce_time_us(bytes_, n,
                                                      strategy=strategy)
            else:
                predicted = machine.allreduce_time_us(bytes_, n)
            rows.append(CollectiveCalibration(
                op="allreduce", strategy=strategy, tier=outer_tier,
                bytes=bytes_, participants=n, predicted_us=predicted,
                measured_us=measured))
    # each tier's ring phase in isolation: the per-tier fit's evidence
    for level_idx, (tname, nj) in enumerate(
            zip(tier_names, group_sizes)):
        if nj <= 1 or n <= 1:
            continue
        level_groups = groups[level_idx]
        for size in sizes_bytes:
            elems = max(1, int(size) // 4)

            def body(x, level_groups=level_groups):
                import jax.lax as lax

                return lax.psum(x[0], "data",
                                axis_index_groups=level_groups)[None]

            measured = timed(body, elems)
            bytes_ = elems * 4.0
            if tier_path:
                tier = next(t for t, _ in tier_path if t.name == tname)
                predicted = (2.0 * (nj - 1) / nj * bytes_
                             / machine.tier_bw(tier) * 1e6
                             + machine.tier_latency(tier))
            else:
                predicted = machine.allreduce_time_us(bytes_, n)
            rows.append(CollectiveCalibration(
                op="psum", strategy="tier_ring", tier=tname,
                bytes=bytes_, participants=nj, predicted_us=predicted,
                measured_us=measured))
    return {"rows": rows, "n_devices": n, "tiers": tier_names,
            "group_sizes": group_sizes,
            "machine": type(machine).__name__, "chip": machine.chip.name}


def run_collective_bench(argv: Optional[List[str]] = None) -> int:
    from .cli import _take

    argv = list(argv or [])
    out_dir = _take(argv, "--out", "collective_bench_out")
    warmup = _take(argv, "--warmup", 1, cast=int)
    repeats = _take(argv, "--repeats", 3, cast=int)
    sizes_spec = _take(argv, "--sizes-mb",
                       ",".join(str(s) for s in DEFAULT_SIZES_MB))
    strategies_spec = _take(argv, "--strategies",
                            ",".join(DEFAULT_STRATEGIES))
    fit_profile = "--fit-profile" in argv
    if fit_profile:
        argv.remove("--fit-profile")

    from ..runtime.platform import cpu_mesh_from_env

    cpu_mesh_from_env()

    import flexflow_tpu as ff

    config = ff.FFConfig()
    rest = config.parse_args(argv)
    if rest:
        print(f"warning: unrecognized flags {rest}", file=sys.stderr)
    try:
        sizes = [max(4, int(float(s) * 1e6))
                 for s in sizes_spec.split(",") if s.strip()]
    except ValueError:
        raise SystemExit(f"--sizes-mb: cannot parse {sizes_spec!r}") \
            from None
    strategies = [s.strip() for s in strategies_spec.split(",")
                  if s.strip()]
    bad = set(strategies) - set(DEFAULT_STRATEGIES)
    if bad:
        raise SystemExit(f"--strategies: unknown {sorted(bad)}; choices:"
                         f" {DEFAULT_STRATEGIES}")

    os.makedirs(out_dir, exist_ok=True)
    result = sweep_collectives(config, sizes, strategies,
                               warmup=warmup, repeats=repeats)
    rows = result["rows"]
    payload = {k: v for k, v in result.items() if k != "rows"}
    payload["rows"] = [r.to_dict() for r in rows]
    cal_path = os.path.join(out_dir, "collective_calibration.json")
    with open(cal_path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")

    problems: List[str] = []
    if not rows:
        problems.append("no collectives measurable (single device?)")
    for r in rows:
        if not (r.measured_us > 0 and math.isfinite(r.measured_us)):
            problems.append(
                f"{r.op}/{r.strategy}/{r.tier}@{int(r.bytes)}B measured"
                f" {r.measured_us!r}")

    profile_path = None
    if fit_profile and rows:
        import jax

        from ..search.machine_model import make_machine_model
        from .refit import FittedProfile, fit_collective_coefficients

        machine = make_machine_model(config, max(1, config.total_devices))
        coeffs = fit_collective_coefficients(rows, machine)
        profile_path = FittedProfile(
            chip=machine.chip.name, backend=jax.default_backend(),
            coefficients=coeffs, fitted_ops=len(rows),
            num_chips=max(1, config.total_devices),
        ).save(os.path.join(out_dir, "fitted_profile.json"))

    largest: Dict[str, Any] = {}
    for r in rows:
        if r.op != "allreduce":
            continue
        cur = largest.get(r.strategy)
        if cur is None or r.bytes > cur["bytes"]:
            largest[r.strategy] = {"bytes": r.bytes,
                                   "measured_us": r.measured_us,
                                   "predicted_us": r.predicted_us}
    bench = {
        "metric": "collective_allreduce_us",
        "n_devices": result["n_devices"],
        "tiers": result["tiers"],
        "per_strategy": largest,
        "rows": len(rows),
        "calibration": cal_path,
        "fitted_profile": profile_path,
    }
    print("BENCH " + json.dumps(bench))
    summary = {"ok": not problems, "out": out_dir, "rows": len(rows),
               "tiers": result["tiers"], "fitted_profile": profile_path,
               "problems": problems}
    print(json.dumps(summary))
    return 0 if not problems else 1
