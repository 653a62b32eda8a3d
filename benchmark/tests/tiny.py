"""Tiny sizes for the CPU rehearsals: the real runners, configurations and
traffic files with the widths and counts shrunk IN THE TEST (the command line
keeps no such switch). A name that starts with `example` is a cell of
`tests/example/`: what a later PR adds as files and entries alone."""
import json
import os
import time

from benchmark import harness, traffic

EXAMPLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "example")


def grown_manifest():
    """BENCHMARK.json with the entries of tests/example/BENCHMARK.example.json
    added to it, as the PR that brings those files would add them."""
    manifest = harness.load_manifest()
    with open(os.path.join(EXAMPLE, "BENCHMARK.example.json")) as f:
        add = json.load(f)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        manifest[group].extend(add[group])
    for name, cells in add["also_reported_by"].items():
        for m in manifest["end_to_end"]:
            if m["name"] == name:
                m["workloads"].extend(cells)
    return manifest


def _load(loader, name):
    root = harness.HERE
    if name.startswith("example"):
        root = EXAMPLE
    return loader(name, root)


def tiny_context(workload: str, seed: int = 2**31 + 11, seconds: float = 2.0,
                 trace: bool = False):
    import jax

    manifest = grown_manifest() if workload.startswith("example") \
        else harness.load_manifest()
    cell = harness.find_cell(manifest, workload)
    cfg = _load(harness.load_config, cell["config"])
    tiny = dict(cfg, num_hidden_layers=2, hidden_size=64,
                num_attention_heads=4, head_dim=16, intermediate_size=256,
                vocab_size=97)
    tr = _load(traffic.load_traffic, cell["traffic"])
    if cfg["runner"] == "train_fit":
        tiny["sequence_length"] = 32
        sizes = {"per_chip_batch": 4, "steps_per_execution": 2,
                 "dispatches_per_fit": 2}
        tr["sequence_length"] = 32
        # the limits are set from readings at the cell's own size; at this
        # width on the CPU one leaf (layer0_attn/bv) reads 0.05-0.06 where
        # the chip's worst is under 0.01, and the faults read 0.58 and more
        tiny["checks"] = dict(cfg["checks"], moment_rel_diff_worst=0.15)
    else:
        sizes = {"declared_batch": 1, "num_slots": 4, "window": 64,
                 "max_len": 128, "page_size": 8, "prefill_chunk_tokens": 32,
                 "max_queue": 8192}
        tr["prompt"] = dict(tr["prompt"], min=4, max=70, median=30,
                            quantiles=4)
        tr["output"] = dict(tr["output"], min=3, max=20, quantiles=4)
        tr.update(max_total=128, backlog_requests=4096, rate_per_s=6.0,
                  ramp_s=1.0, tail_s=5.0)
        if "first_wave" in tr:
            tr["first_wave"] = dict(tr["first_wave"], group=2)
        tr["settle_s"] = 0.2
    return harness.RunContext(
        manifest=manifest, cell=cell, config=tiny, traffic=tr, seed=seed,
        seconds=seconds, trace=trace, devices=jax.devices(),
        setup=harness.SetupClock(time.perf_counter()),
        compiles=harness.CompileClock(), sizes=sizes, trace_seconds=0.5,
        roots=(harness.HERE, EXAMPLE))
