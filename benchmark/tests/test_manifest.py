"""BENCHMARK.json against the contract's limits, every file found by name,
and an example of each thing a later PR may add as files of its own."""
import os
import re

import pytest

from benchmark import harness, reducers, shapes, traffic
from benchmark.tests.tiny import EXAMPLE, grown_manifest

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest()


def test_keys_names_units(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["benchmark"]
    assert 1 <= manifest["run_seconds"] <= 51
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in manifest[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
    assert len(names) == len(set(names))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for c in manifest["workloads"]:
        assert set(c) == {"name", "config", "traffic", "chips", "why"}
        assert len(c["why"]) <= 200 and c["chips"] in (1, 4)
        assert NAME.match(c["traffic"]) and NAME.match(c["config"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_at_most_one_four_chip_cell_in_four(manifest):
    four = [c for c in manifest["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)


def test_every_file_is_found_by_name(manifest):
    cfgs = {c["name"]: c for c in manifest["configs"]}
    files = set()
    for cell in manifest["workloads"]:
        c = cfgs[cell["config"]]
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        files.add(c["file"])
        cfg = harness.load_config(cell["config"])
        assert cfg["reduced"] == c["reduced"]
        for pkg, key in (("configs", "builder"), ("reference", "reference"),
                         ("runners", "runner")):
            assert os.path.exists(os.path.join(
                ROOT, "benchmark", pkg, cfg[key] + ".py")), (pkg, cfg[key])
        traffic.load_traffic(cell["traffic"])
        assert set(cfg["checks"]), "a cell compares at least one number"
    assert files == {c["file"] for c in manifest["configs"]}, \
        "every configuration is used by some cell"
    for m in manifest["per_layer"]:
        spec = harness.load_metric(m["name"])
        assert callable(harness.reader_of(spec))
        if "shape_fn" in spec:
            assert spec["shape_fn"] in shapes.SHAPE_FNS


def test_every_cell_reports_what_its_metrics_move(manifest):
    e2e = {m["name"] for m in manifest["end_to_end"]}
    assert "setup_s" in e2e
    for cell in manifest["workloads"]:
        mine = {m["name"] for m in harness.cell_metrics(
            manifest, cell["name"], "end_to_end")}
        assert "setup_s" in mine and len(mine) >= 2, cell["name"]
        layer = harness.cell_metrics(manifest, cell["name"], "per_layer")
        assert layer, cell["name"]
        for m in layer:
            assert m["moves"] in mine, (cell["name"], m["name"], m["moves"])
    layers = {}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    for m in manifest["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_a_later_pr_adds_files_only(manifest):
    """A cell, a configuration, a traffic mix and a per-layer metric, each
    as new files and new entries: the example tree under tests/example holds
    them, `BENCHMARK.example.json` the entries, and the harness loads them by
    name with no edit (test_rehearsal runs the open-loop one end to end)."""
    cfg = harness.load_config("example_lm_small", EXAMPLE)
    assert cfg["builder"] == "causal_lm" and cfg["hidden_size"] == 1024
    tr = traffic.load_traffic("example_short_chat", EXAMPLE)
    reqs = traffic.make_requests(tr, 5, cfg["vocab_size"], 40, 0)
    assert len(reqs) == 40 and max(len(r.prompt) for r in reqs) <= 128
    grown = grown_manifest()
    assert len(grown["workloads"]) == len(manifest["workloads"]) + 2
    for cell in grown["workloads"]:
        mine = {m["name"] for m in harness.cell_metrics(
            grown, cell["name"], "end_to_end")}
        assert "setup_s" in mine and len(mine) >= 2, cell["name"]
        for m in harness.cell_metrics(grown, cell["name"], "per_layer"):
            assert m["moves"] in mine, (cell["name"], m["name"])
    mine = [m["name"] for m in harness.cell_metrics(grown, "example_cell",
                                                    "per_layer")]
    assert "example_itl_p99_ms" in mine and "setup_compile_s" in mine
    spec = harness.load_metric("example_itl_p99_ms", EXAMPLE)
    rec = harness.Record({}, 0, 0, {}, 0, series={"itl_ms": [1.0, 2.0, 50.0]})
    assert harness.reader_of(spec)(spec, None, rec) == 50.0


def test_a_reader_of_a_new_kind_is_a_module_of_its_own(monkeypatch):
    class Reader:
        @staticmethod
        def read(spec, ctx, rec):
            return 7.0

    monkeypatch.setattr(harness, "module_of",
                        lambda pkg, name: Reader if (pkg, name) == (
                            "metrics", "my_reader") else None)
    assert harness.reader_of({"reducer": "my_reader"})({}, None, None) == 7.0
    assert harness.reader_of({"reducer": "counter"}) is reducers.counter
