"""Test harness config: run on a virtual 8-device CPU mesh.

Mirrors SURVEY.md §4's implication: the reference can only test multi-device
logic on a real cluster; we test multi-chip sharding without hardware via
XLA's host-platform device-count override.

The tier-1 command already sets JAX_PLATFORMS=cpu; force_platform also adds
the device-count flag and pins jax.config, so a bare `pytest tests/` on a
machine with a chip still runs here and never takes the chip."""
from flexflow_tpu.runtime.platform import force_platform

force_platform("cpu", n_host_devices=8)

import pytest  # noqa: E402


def pytest_configure(config):
    """Register the `slow` marker (no pytest.ini/pyproject marker table in
    this repo): heavy multi-step training tests — MoE transformers
    training to parity, large searched-plan fits — opt out of the tier-1
    sweep, which runs `-m 'not slow'`. A full `pytest tests/` still runs
    them."""
    config.addinivalue_line(
        "markers",
        "slow: heavy training/search tests excluded from the tier-1 "
        "`-m 'not slow'` sweep")


def module_xla_cache():
    """Generator behind the serving modules' module-scoped XLA
    compilation-cache fixture (each module wires it up as
    `_xla_cache = pytest.fixture(scope="module", autouse=True)(
    module_xla_cache)`). Those modules build fresh batchers/replicas per
    test whose per-instance jax.jit dispatches trace to identical HLO
    (same tiny model, same pool geometry), so a per-module disk cache
    turns each repeat compile into a ~5x-cheaper deserialization and
    roughly halves the module's wall clock. Deliberately NOT suite-wide:
    the cache segfaults on the multi-device TRAINING executables other
    test modules compile (donated shard_map buffers on the CPU mesh),
    and single-device serving inference is the only surface it has been
    proven safe on."""
    import jax

    prev_entry = jax.config.jax_persistent_cache_min_entry_size_bytes
    prev_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_compilation_cache_dir", _serving_xla_cache_dir())
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    yield
    jax.config.update("jax_compilation_cache_dir", None)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                      prev_entry)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      prev_secs)


def served_both_ways_counts_add_up(make_batcher, jobs, ops, slots, max_len,
                                   block):
    """`jobs` ((prompt, new tokens), ...) ONE REQUEST AT A TIME through the
    batcher `make_batcher()` builds, with the dense decode core
    (`attention_decode`) forced each way: the same greedy tokens, and the
    counting attention ops `ops` add up — one live slot a decode step and
    `slots - 1` idle ones at their dummy position 0; `rows_read` whole
    `block`-row blocks up to the position under the kernel, every allocated
    row under the reference; `ff_attn_rows_*` mirror them. Returns the
    kernel side's {op: counters} and tokens."""
    import numpy as np

    from flexflow_tpu.kernels.registry import KERNELS
    from flexflow_tpu.ops.latent_attention import wide_count

    steps = sum(n - 1 for _, n in jobs)     # the first token is prefill's
    filled = sum(len(p) + k + 1 for p, n in jobs for k in range(n - 1)) \
        + (slots - 1) * steps
    blocks = sum((len(p) + k) // block + 1 for p, n in jobs
                 for k in range(n - 1)) + (slots - 1) * steps
    tokens, got = {}, {}
    for impl in ("pallas", "reference"):
        with KERNELS.override("attention_decode", impl), \
                make_batcher() as cb:
            tokens[impl] = [np.asarray(cb.submit(p, n).result(timeout=300))
                            for p, n in jobs]
            cb.publish_op_counters()
            got[impl] = cb.op_counters()
            text = cb.registry.render()
        read = block * blocks if impl == "pallas" else steps * slots * max_len
        assert read >= filled
        for name in ops:
            c = got[impl][name]
            assert (int(c["attn_steps"]), wide_count(c["rows_filled"]),
                    wide_count(c["rows_read"])) == (steps, filled, read), name
            assert f'ff_attn_rows_filled_total{{op="{name}"}} {filled}\n' \
                in text, text
            assert f'ff_attn_rows_read_total{{op="{name}"}} {read}\n' in text
    for a, b in zip(tokens["pallas"], tokens["reference"]):
        np.testing.assert_array_equal(a, b)
    return got["pallas"], tokens["pallas"]


def _serving_xla_cache_dir() -> str:
    """ONE fixed cache dir shared by every serving module, beside the
    program's own compile cache (runtime/platform.compile_cache_dir): jax
    latches the persistent-cache instance at first initialization, so the
    path must not change within a session, and a path that never moves
    lets later modules — and later sessions — hit what earlier ones
    compiled."""
    import os

    from flexflow_tpu.runtime.platform import compile_cache_dir

    path = os.path.join(compile_cache_dir(), "tests_serving")
    os.makedirs(path, exist_ok=True)
    return path


@pytest.fixture(autouse=True)
def _reset_plan_cache():
    """The process-wide plan cache (search/plan_cache.py) must not leak
    between tests: a test searching the same (graph, machine, knobs) an
    earlier test searched would HIT and skip enumeration, breaking
    asserts on the search's internals (candidates_simulated, logs)."""
    from flexflow_tpu.search.plan_cache import reset_plan_cache

    reset_plan_cache()
    yield
    reset_plan_cache()


@pytest.fixture(autouse=True)
def _reset_obs_state():
    """Process-wide observability state must not leak between tests: one
    obs.reset_all() zeroes every registry counter family (plan
    diagnostics, checkpoint, watchdog, step stats) and drops buffered
    trace spans — replacing the three separate reset_*_counters calls
    tests previously had to remember."""
    import flexflow_tpu.obs as obs

    obs.reset_all()
    yield
    obs.reset_all()
