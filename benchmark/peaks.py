"""The one table of hardware peaks, keyed by `device_kind` (peaks.json, with
its source). A kind that is not in the table is an error, never a default."""
from __future__ import annotations

import json
import os
from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    kind: str
    bf16_flops_per_s: float
    hbm_bytes: float
    hbm_bytes_per_s: float


def peaks_for(device_kind: str) -> Peaks:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    row = table.get(device_kind)
    if not isinstance(row, dict):
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json;"
            " add its published peaks with their source")
    return Peaks(device_kind, float(row["bf16_flops_per_s"]),
                 float(row["hbm_bytes"]), float(row["hbm_bytes_per_s"]))


def least_time_s(flops: float, hbm_bytes: float, peaks: Peaks):
    """Roofline: the least time the chip could take, and the side that sets
    it (`mxu` or `hbm`)."""
    t_flops = flops / peaks.bf16_flops_per_s
    t_bytes = hbm_bytes / peaks.hbm_bytes_per_s
    return (t_flops, "mxu") if t_flops >= t_bytes else (t_bytes, "hbm")
