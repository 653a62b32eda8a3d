"""CPU rehearsal of chip_smoke.py and unit tests of the device boundary.

chip_smoke.py itself runs on a TPU or not at all (it has no CPU option); its
phases are importable, so they are rehearsed here at toy width on the test
mesh — wrong paths, arguments and control flow are found without chip time.
The device assertion is stubbed HERE, never by an option of the script.
"""
import contextlib
import json
import os
import subprocess
import sys
import types

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TOY = chip_smoke.Sizes(
    hidden=32, heads=4, layers=1, seq=16, vocab=64, batch=4,
    steps_per_execution=2, train_dispatches=2, kernel_layers=1,
    search_layers=1, search_budget=2, search_devices=2,
    slots=2, window=24, max_len=32, page_size=8, prompts=(5, 20, 9),
    new_tokens=3, latent=(4, 32, 16, 8, 8, 16),
    # heads of 128 as at the real width: narrower ones count no rows
    hybrid=(4, 2, 128, 32, 4, 8, 2), window_pair=(4, 8, 2, 128, 6),
    mesh_batch=4, mesh_layers=1, mesh_steps=3)


@pytest.fixture(scope="module")
def clock():
    return chip_smoke.CompileClock()


@pytest.mark.parametrize("phase", ["train", "kernels", "search", "serve",
                                   "latent", "hybrid", "window", "mesh"])
def test_phase_at_toy_width(phase, clock, capsys):
    """Each phase runs end to end and prints its one JSON line. On this
    backend the kernels run interpreted and the search measures CPU op
    times — what is rehearsed is the control flow and every comparison."""

    from flexflow_tpu.kernels.registry import KERNELS

    # steer the lowerings down the branch the chip takes: there the
    # registry's crossover selects flash at the real widths; here it must
    # be forced (the kernels phase does its own forcing)
    steer = (contextlib.nullcontext() if phase == "kernels"
             else KERNELS.override("attention", "pallas"))
    with steer:
        line = chip_smoke.run_phase(phase, clock,
                                    getattr(chip_smoke, f"phase_{phase}"),
                                    TOY, 0)
    printed = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert printed["phase"] == phase == line["phase"]
    assert {"seconds", "compile_seconds"} <= set(printed)
    if phase == "train":
        assert printed["worst_rel"] <= chip_smoke.LOSS_DISPATCH_RTOL
    elif phase == "kernels":
        assert printed["interpret"] is True
        assert set(printed["families"]) == {"attention"}
    elif phase == "search":
        assert printed["analytic_fallbacks"] == 0 == printed["failures"]
        assert printed["ops_measured"] > 0
    elif phase == "serve":
        assert printed["token_parity"] == {
            "default": "3/3 identical",
            "decode_kernels_forced": "3/3 identical"}
    elif phase == "latent":
        assert printed["token_parity"] == "3/3 identical"
        # a cache of one 32-row block: both sides read every allocated row
        assert (printed["rows_read_over_filled"]["pallas"]
                == printed["rows_read_over_filled"]["reference"] > 1)
    elif phase == "hybrid":
        # 3 prompts through 2 slots: one slot reused, its state reset
        assert printed["token_parity"] == "3/3 identical"
        assert printed["state_resets"] == 3
        # a cache of one 32-row block: both sides read every allocated row
        assert (printed["rows_read_over_filled"]["pallas"]
                == printed["rows_read_over_filled"]["reference"] > 1)
    elif phase == "window":
        # 3 prompts through 2 slots: one slot's ring reused; the prompt of
        # 20 tokens wraps a ring of 6 rows three times
        assert printed["token_parity"] == "3/3 identical"
        assert printed["ring_rows"] == 6
        assert (printed["rows_read_over_filled"]["pallas"]
                == printed["rows_read_over_filled"]["reference"] > 1)
    else:
        assert printed["dp_x_tp"]["mesh_devices"] == 4
        assert printed["dp_x_tp"]["params"]["devices"] == [0, 1, 2, 3]
        assert printed["dp_x_tp"]["params"]["arrays_split"] > 0
        assert "all-reduce" in printed["dp_x_tp"]["collectives"]
        # whatever plan the search picks at this width ran and was compared
        assert len(printed["searched"]["losses"]) == TOY.mesh_steps


@pytest.mark.parametrize("argv,phases", [
    ([], ["train", "kernels", "search", "serve", "latent", "hybrid",
          "window"]),
    (["--chips", "4", "--seed", "3"], ["mesh"]),
])
def test_main_runs_the_right_phases_and_ends_with_the_ok_line(
        argv, phases, monkeypatch, capsys):
    """main() with the device check and the phases stubbed: one chip runs
    the four one-chip phases, --chips 4 ONLY the mesh phase; the last
    line is exactly the contract's object."""
    devices = jax.devices()
    monkeypatch.setattr(chip_smoke, "require_tpu",
                        lambda what, chips: devices)
    for name in ("train", "kernels", "search", "serve", "hybrid", "window",
                 "mesh"):
        monkeypatch.setattr(chip_smoke, f"phase_{name}",
                            lambda *a, _n=name: {"stub": _n})
    assert chip_smoke.main(argv) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert [json.loads(ln)["phase"] for ln in lines[:-1]] == (
        ["device"] + phases + ["cold_start"])
    assert lines[-1] == json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}})


def test_cold_start_line_names_the_phases_and_the_first_calls(clock, capsys):
    """Printed once at the end of a run: what the process recorded of its
    own start-up (obs/startup.py), seconds by phase and by program."""
    chip_smoke.run_phase("train", clock, chip_smoke.phase_train, TOY, 0)
    capsys.readouterr()
    line = chip_smoke.cold_start_line()
    assert json.loads(capsys.readouterr().out) == line
    seconds = line["seconds"]
    assert {"compile", "compile.init_params", "compile.build_steps",
            "first_dispatch:multi_step"} <= set(seconds)
    assert all(v >= 0 for v in seconds.values())
    assert seconds["compile"] >= seconds["compile.init_params"]


def test_script_exits_nonzero_without_a_tpu():
    """The real script, as the driver runs it, on a host with no TPU:
    non-zero exit, no result line, before any work."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=ROOT,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert proc.stdout.strip() == ""


def test_require_tpu_refuses_too_few_chips(monkeypatch):
    tpu = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda: [tpu])
    assert chip_smoke.require_tpu("x", 1) == [tpu]
    with pytest.raises(SystemExit, match="needs 4 TPU chips"):
        chip_smoke.require_tpu("x", 4)


# -- the device boundary (runtime/platform.py, search/machine_model.py) ----
@pytest.mark.parametrize("backend,want", [("tpu", False), ("cpu", True),
                                          ("gpu", RuntimeError)])
def test_pallas_interpret_is_decided_by_the_backend(backend, want,
                                                    monkeypatch):
    """Compiled on a TPU, interpreted on the CPU test backend, and an
    error — never a silent interpreter — anywhere else."""
    from flexflow_tpu.runtime.platform import pallas_interpret

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if want is RuntimeError:
        with pytest.raises(RuntimeError, match="gpu"):
            pallas_interpret()
    else:
        assert pallas_interpret() is want


@pytest.mark.parametrize("platform,kind,want", [
    ("tpu", "TPU v5 lite", "tpu-v5e"), ("tpu", "TPU v4", "tpu-v4"),
    ("cpu", "cpu", "tpu-v5e"), ("tpu", "TPU v9 mega", ValueError),
    ("gpu", "NVIDIA A100", ValueError)])
def test_chip_is_resolved_from_device_kind(platform, kind, want):
    """The machine model prices the attached chip, looked up by
    device_kind; an unknown TPU raises; a CPU run keeps the explicitly
    described chip."""
    from flexflow_tpu.search.machine_model import chip_for_device

    device = types.SimpleNamespace(platform=platform, device_kind=kind)
    if want is ValueError:
        with pytest.raises(ValueError, match="no chip spec"):
            chip_for_device(device)
    else:
        assert chip_for_device(device).name == want


@pytest.mark.parametrize("env_dir", [None, "placed"])
def test_compile_cache_is_placed_from_outside(env_dir, monkeypatch,
                                              tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: that directory, and nothing set in
    code. Unset: the fixed <checkout>/.jax_cache."""
    from flexflow_tpu.runtime import platform

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(ROOT, ".jax_cache")
        assert platform.enable_compile_cache() == want
        assert updates == [("jax_compilation_cache_dir", want)]
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
        assert platform.enable_compile_cache() == want
        assert updates == []


def test_opcost_failure_raises_on_tpu_and_is_counted_on_cpu(monkeypatch):
    """An op-cost measurement that fails is priced analytically only on
    the CPU backend; on a TPU it raises — the step would fail the same
    way."""
    import flexflow_tpu as ff
    from flexflow_tpu.search.simulator import OpCostCache, OpStrategy

    config = ff.FFConfig()
    config.batch_size = 4
    model = ff.FFModel(config)
    t = model.dense(model.create_tensor([4, 8]), 8, name="fc")
    op = t.owner_op
    cache = OpCostCache(config)

    def boom(*a, **k):
        raise RuntimeError("RESOURCE_EXHAUSTED: scoped vmem")

    monkeypatch.setattr(cache, "_measure", boom)
    assert cache.measure_us(op, OpStrategy(1, 1)) == (-1.0, -1.0)
    assert len(cache.failures) == 1
    cache.failures.clear()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="scoped vmem"):
        cache.measure_us(op, OpStrategy(1, 1))


def test_native_build_failure_is_an_error_when_asked_for(monkeypatch,
                                                         tmp_path):
    """A broken src/ffcore: available() says no (and why, in the log);
    require() — what use_native_search calls — raises."""
    from flexflow_tpu import native

    (tmp_path / "Makefile").write_text("all:\n\tfalse\n")
    (tmp_path / "broken.cc").write_text("#error broken\n")
    monkeypatch.setattr(native, "_SRC_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_load_error", None)
    assert native.available() is False
    with pytest.raises(native.NativeBuildError, match="requested"):
        native.require()
