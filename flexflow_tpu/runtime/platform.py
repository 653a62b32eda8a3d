"""The device boundary: which backend runs, whether Pallas kernels are
interpreted, and where compiled programs are cached.

jax reads JAX_PLATFORMS (and XLA_FLAGS) itself, once, when the first
backend client is created — `JAX_PLATFORMS=cpu` in the environment is all
a CPU run needs. `force_platform` exists for callers that decide AFTER
`import jax` (tests/conftest.py, the CPU dryruns of __graft_entry__.py,
the elastic drill): it sets the environment and jax.config together, which
works any time before the first backend client.

Nothing here falls back: a TPU process uses the TPU or fails at start-up,
and Pallas interpret mode is a property of the CPU test backend only.
"""
from __future__ import annotations

import os
import re

_COUNT_OPT = "--xla_force_host_platform_device_count"
_REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", ".."))


def force_platform(name: str, n_host_devices: int | None = None) -> None:
    """Force the JAX platform (and optionally the virtual CPU device count).

    Must be called before any jax backend client exists. Safe to call after
    ``import jax`` / ``import flexflow_tpu`` (neither creates a client at
    import time).
    """
    if n_host_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        if _COUNT_OPT in flags:
            # only raise an existing count, never lower it
            m = re.search(rf"{_COUNT_OPT}=(\d+)", flags)
            if m and int(m.group(1)) < n_host_devices:
                flags = re.sub(
                    rf"{_COUNT_OPT}=\d+", f"{_COUNT_OPT}={n_host_devices}", flags
                )
            os.environ["XLA_FLAGS"] = flags
        else:
            os.environ["XLA_FLAGS"] = (
                f"{flags} {_COUNT_OPT}={n_host_devices}".strip()
            )
    os.environ["JAX_PLATFORMS"] = name

    import jax

    jax.config.update("jax_platforms", name)


def cpu_mesh_from_env(n_host_devices: int = 8) -> None:
    """Give an explicit JAX_PLATFORMS=cpu run the tests' virtual device
    count, so the CLI entry points exercise real sharding on a CPU host.
    No-op for any other (or unset) platform: a TPU run sees its chips."""
    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        force_platform("cpu", n_host_devices=n_host_devices)


_devices_asked = False


def backend_devices():
    """`jax.devices()`. The first call through here is the one-shot phase
    `startup.platform` (obs/startup.py): on a TPU it is the call that makes
    jax create its backend client, seconds long; where something else has
    created the client already the phase reads about nothing."""
    global _devices_asked
    import jax

    if _devices_asked:
        return jax.devices()
    _devices_asked = True
    from ..obs.tracing import get_tracer

    with get_tracer().phase("startup.platform"):
        return jax.devices()


def require_tpu(what: str, min_devices: int = 1):
    """jax's devices, or SystemExit when they are not at least
    `min_devices` TPU chips: what measures the chip (bench.py,
    chip_smoke.py, scripts/) runs there or not at all."""
    devices = backend_devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"{what}: needs a TPU; jax found {devices[0].platform!r}"
            " devices only")
    if len(devices) < min_devices:
        raise SystemExit(
            f"{what}: needs {min_devices} TPU chips; jax sees"
            f" {len(devices)}")
    return devices


def pallas_interpret() -> bool:
    """THE place that decides Pallas interpret mode: compiled on a TPU,
    interpreted on the CPU backend (how the test suite runs the kernels),
    and an error anywhere else — an unknown backend must not silently run
    the kernels in the interpreter."""
    import jax

    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels here target the TPU; backend {backend!r} can"
        " neither compile nor (by policy) interpret them — keep the"
        " reference lowering")


def compile_cache_dir() -> str:
    """Where compiled programs persist: $JAX_COMPILATION_CACHE_DIR when the
    environment places it, else `<checkout>/.jax_cache` — a fixed path,
    because the path is part of every cache key."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache at `compile_cache_dir()`
    and return the directory. When JAX_COMPILATION_CACHE_DIR is set jax has
    already adopted it and nothing is set in code."""
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
