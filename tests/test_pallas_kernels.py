"""Pallas fused-kernel tier (ISSUE 9): interpret-mode fwd+bwd parity of
every fused kernel against its jnp reference, the KernelRegistry's
selection semantics, calibration-driven candidacy, simulator pricing,
and token-identical greedy decode through the continuous batcher with
the fused decode kernel forced.

Tolerances: f32 kernels must match the reference to float-roundoff
(1e-5); bf16 I/O kernels accumulate in f32 and are compared at bf16
resolution (2e-2 on normalized outputs).
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.kernels.pallas import (fused_decode_attention,
                                         fused_layernorm, fused_reduce,
                                         fused_rmsnorm, fused_softmax)
from flexflow_tpu.kernels.registry import (KERNELS, PALLAS_COST_GAIN,
                                           KernelRegistry)

F32_TOL = dict(rtol=1e-5, atol=1e-5)
BF16_TOL = dict(rtol=2e-2, atol=2e-2)


def _rand(rng, shape, dtype=np.float32):
    return jnp.asarray(rng.randn(*shape).astype(np.float32)).astype(dtype)


@contextlib.contextmanager
def force_pallas(*families):
    with contextlib.ExitStack() as st:
        for fam in families:
            st.enter_context(KERNELS.override(fam, "pallas"))
        yield


# ---------------------------------------------------------------------
# norm kernels: fwd + bwd parity
# ---------------------------------------------------------------------
def _ref_layernorm(x, g=None, b=None, eps=1e-5):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    if g is not None:
        y = y * g.astype(jnp.float32) + b.astype(jnp.float32)
    return y.astype(x.dtype)


def _ref_rmsnorm(x, g=None, eps=1e-6):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    if g is not None:
        y = y * g.astype(jnp.float32)
    return y.astype(x.dtype)


@pytest.mark.parametrize("dtype,tol", [(np.float32, F32_TOL),
                                       (jnp.bfloat16, BF16_TOL)])
def test_layernorm_fwd_bwd_parity(dtype, tol):
    rng = np.random.RandomState(0)
    x = _rand(rng, (3, 9, 48), dtype)  # 9 rows: exercises row padding
    g = _rand(rng, (48,), dtype)
    b = _rand(rng, (48,), dtype)
    y = fused_layernorm(x, g, b, interpret=True, block_rows=4)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(_ref_layernorm(x, g, b),
                                          np.float32), **tol)

    def loss(fn):
        return lambda x, g, b: jnp.sum(jnp.sin(
            fn(x, g, b).astype(jnp.float32)))

    gf = jax.grad(loss(lambda x, g, b: fused_layernorm(
        x, g, b, interpret=True, block_rows=4)), argnums=(0, 1, 2))(x, g, b)
    gr = jax.grad(loss(_ref_layernorm), argnums=(0, 1, 2))(x, g, b)
    for a, r in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(r, np.float32), **tol)


def test_layernorm_no_affine_parity():
    rng = np.random.RandomState(1)
    x = _rand(rng, (4, 5, 32))
    y = fused_layernorm(x, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(_ref_layernorm(x)),
                               **F32_TOL)
    gf = jax.grad(lambda x: jnp.sum(jnp.sin(
        fused_layernorm(x, interpret=True))))(x)
    gr = jax.grad(lambda x: jnp.sum(jnp.sin(_ref_layernorm(x))))(x)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gr), **F32_TOL)


@pytest.mark.parametrize("dtype,tol", [(np.float32, F32_TOL),
                                       (jnp.bfloat16, BF16_TOL)])
def test_rmsnorm_fwd_bwd_parity(dtype, tol):
    rng = np.random.RandomState(2)
    x = _rand(rng, (2, 7, 64), dtype)
    g = _rand(rng, (64,), dtype)
    y = fused_rmsnorm(x, g, interpret=True, block_rows=4)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(_ref_rmsnorm(x, g), np.float32),
                               **tol)
    gf = jax.grad(lambda x, g: jnp.sum(jnp.sin(fused_rmsnorm(
        x, g, interpret=True, block_rows=4).astype(jnp.float32))),
        argnums=(0, 1))(x, g)
    gr = jax.grad(lambda x, g: jnp.sum(jnp.sin(
        _ref_rmsnorm(x, g).astype(jnp.float32))), argnums=(0, 1))(x, g)
    for a, r in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(r, np.float32), **tol)


@pytest.mark.parametrize("dtype,tol", [(np.float32, F32_TOL),
                                       (jnp.bfloat16, BF16_TOL)])
def test_softmax_fwd_bwd_parity(dtype, tol):
    rng = np.random.RandomState(3)
    x = _rand(rng, (5, 11, 40), dtype)
    ref = jax.nn.softmax(x.astype(jnp.float32), -1).astype(x.dtype)
    y = fused_softmax(x, interpret=True, block_rows=4)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ref, np.float32), **tol)
    gf = jax.grad(lambda x: jnp.sum(jnp.sin(fused_softmax(
        x, interpret=True, block_rows=4).astype(jnp.float32))))(x)
    gr = jax.grad(lambda x: jnp.sum(jnp.sin(jax.nn.softmax(
        x.astype(jnp.float32), -1))))(x)
    np.testing.assert_allclose(np.asarray(gf, np.float32),
                               np.asarray(gr, np.float32), **tol)


# ---------------------------------------------------------------------
# reduction / scan
# ---------------------------------------------------------------------
def test_fused_reduce_parity_and_grads():
    rng = np.random.RandomState(4)
    x = _rand(rng, (7, 33))  # 231 elements: lane + row padding
    np.testing.assert_allclose(float(fused_reduce(x, "sum", interpret=True)),
                               float(jnp.sum(x)), rtol=1e-5)
    np.testing.assert_allclose(float(fused_reduce(x, "mean", interpret=True)),
                               float(jnp.mean(x)), rtol=1e-5)
    assert float(fused_reduce(x, "max", interpret=True)) == float(jnp.max(x))
    for kind, ref in (("sum", jnp.sum), ("mean", jnp.mean)):
        gf = jax.grad(lambda x: fused_reduce(x, kind, interpret=True))(x)  # noqa: B023
        gr = jax.grad(lambda x: ref(x))(x)  # noqa: B023
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr), **F32_TOL)
    with pytest.raises(TypeError, match="forward-only"):
        jax.grad(lambda x: fused_reduce(x, "max", interpret=True))(x)


def test_fused_reduce_tiny_and_empty():
    assert float(fused_reduce(jnp.asarray([3.0]), "sum",
                              interpret=True)) == 3.0
    assert float(fused_reduce(jnp.zeros((0,)), "sum", interpret=True)) == 0.0


# ---------------------------------------------------------------------
# fused decode step
# ---------------------------------------------------------------------
def _per_head(q, cache):
    """The pool's packed (B, M, h*d) cache as the (B, M, h, d) the plain
    references attend."""
    return cache.reshape(cache.shape[:2] + q.shape[2:])


def _ref_decode(q, kc, vc, pos, scale):
    kc, vc = _per_head(q, kc), _per_head(q, vc)
    m = kc.shape[1]
    mask = (jnp.arange(m)[None, :] <= pos[:, None])[:, None, None, :]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kc.astype(q.dtype),
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype),
                      vc.astype(q.dtype))


@pytest.mark.parametrize("block_k", [64, 8])  # single- and multi-block
def test_fused_decode_ragged_positions(block_k):
    rng = np.random.RandomState(6)
    B, M, h, d = 5, 24, 3, 8
    q = _rand(rng, (B, 1, h, d))
    kc = _rand(rng, (B, M, h * d))
    vc = _rand(rng, (B, M, h * d))
    # ragged: includes pos 0 (one live row) and pos M-1 (the whole cache)
    pos = jnp.asarray([0, 3, 11, 23, 7], dtype=jnp.int32)
    scale = 1.0 / np.sqrt(d)
    out = fused_decode_attention(q, kc, vc, pos, scale=scale,
                                 block_k=block_k, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_ref_decode(q, kc, vc, pos, scale)),
        rtol=1e-5, atol=1e-6)


def test_fused_decode_bf16_cache():
    rng = np.random.RandomState(7)
    B, M, h, d = 2, 16, 2, 16
    q = _rand(rng, (B, 1, h, d))
    kc = _rand(rng, (B, M, h * d), jnp.bfloat16)
    vc = _rand(rng, (B, M, h * d), jnp.bfloat16)
    pos = jnp.asarray([5, 15], dtype=jnp.int32)
    scale = 1.0 / np.sqrt(d)
    out = fused_decode_attention(q, kc, vc, pos, scale=scale, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(_ref_decode(q, kc, vc, pos, scale), np.float32),
        **BF16_TOL)


def test_fused_decode_rejects_multi_query():
    q = jnp.zeros((1, 2, 2, 4))
    kc = vc = jnp.zeros((1, 8, 2 * 4))
    with pytest.raises(ValueError, match="one query token"):
        fused_decode_attention(q, kc, vc, jnp.zeros((1,), jnp.int32),
                               scale=1.0, interpret=True)


# ---------------------------------------------------------------------
# KernelRegistry semantics
# ---------------------------------------------------------------------
def test_registry_selection_order():
    # CPU backend: auto is always reference
    assert KERNELS.select("layernorm", record=False).reason == "backend"
    assert not KERNELS.select("layernorm", record=False)
    # param beats everything, both ways
    with KERNELS.override("attention", "reference"):
        assert KERNELS.select("attention", param=True, record=False)
        assert KERNELS.select("attention", param=True,
                              record=False).reason == "param"
    # override beats config; restores the previous override on exit
    with KERNELS.override("softmax", "pallas"):
        c = KERNELS.select("softmax", record=False)
        assert c and c.reason == "override"
        with KERNELS.override("softmax", "reference"):
            assert not KERNELS.select("softmax", record=False)
        assert KERNELS.select("softmax", record=False)
    assert KERNELS.select("softmax", record=False).reason == "backend"
    with pytest.raises(KeyError):
        KERNELS.select("not_a_family")


def test_registry_config_knob_and_parse_spec():
    assert KernelRegistry.parse_spec("auto") == {}
    assert KernelRegistry.parse_spec("pallas")["layernorm"] == "pallas"
    assert KernelRegistry.parse_spec(
        "attention=pallas,softmax=reference") == {
            "attention": "pallas", "softmax": "reference"}
    for bad in ("nope", "attention=fused", "zzz=pallas"):
        with pytest.raises(ValueError, match="kernel-impl"):
            KernelRegistry.parse_spec(bad)
    import flexflow_tpu as ff

    cfg = ff.FFConfig()
    cfg.parse_args(["--kernel-impl", "layernorm=pallas"])
    assert cfg.kernel_impl == "layernorm=pallas"
    reg = KernelRegistry()
    reg.configure(cfg)
    c = reg.select("layernorm", record=False)
    assert c and c.reason == "config"
    # reconfiguring back to auto clears it
    cfg.kernel_impl = "auto"
    reg.configure(cfg)
    assert not reg.select("layernorm", record=False)
    with pytest.raises(ValueError, match="kernel-impl"):
        ff.FFConfig().parse_args(["--kernel-impl", "bogus"])


def test_registry_residual_driven_selection(tmp_path):
    """A fitted profile whose residuals mark layernorm as underpriced
    makes auto select pallas on a TPU backend — the calibration-driven
    loop — while a calibrated family stays on reference."""
    import flexflow_tpu as ff
    from flexflow_tpu.obs.refit import FittedCoefficients, FittedProfile

    path = str(tmp_path / "prof.json")
    FittedProfile(
        chip="cpu-host", backend="cpu",
        coefficients=FittedCoefficients(),
        op_family_residuals={"layernorm": 1.8, "softmax": 1.01},
    ).save(path)
    cfg = ff.FFConfig()
    cfg.fitted_profile_file = path
    reg = KernelRegistry()
    reg.configure(cfg)
    assert reg.residual("layernorm") == 1.8
    c = reg.select("layernorm", backend="tpu", record=False)
    assert c and c.reason == "residual"
    # residual below threshold: falls through to the family default
    assert not reg.select("softmax", backend="tpu", record=False)
    # and on CPU the backend gate still wins
    assert not reg.select("layernorm", backend="cpu", record=False)


def test_registry_decode_inherits_attention_residual_and_defaults(tmp_path):
    """attention_decode never appears as a calibratable graph op: its
    auto selection on TPU rides the attention family's residual.
    reduction (same situation, but with no related family and no SPMD
    partitioning rule for its pallas_call) stays knob-opt-in: reference
    on every backend under auto."""
    import flexflow_tpu as ff
    from flexflow_tpu.obs.refit import FittedCoefficients, FittedProfile

    reg = KernelRegistry()
    assert not reg.select("attention_decode", backend="tpu", record=False)
    assert not reg.select("reduction", backend="tpu", record=False)
    assert not reg.select("reduction", backend="cpu", record=False)
    path = str(tmp_path / "prof.json")
    FittedProfile(chip="x", backend="cpu",
                  coefficients=FittedCoefficients(),
                  op_family_residuals={"attention": 2.0}).save(path)
    cfg = ff.FFConfig()
    cfg.fitted_profile_file = path
    reg.configure(cfg)
    d = reg.select("attention_decode", backend="tpu", record=False)
    assert d and d.reason == "residual"


def test_registry_residual_respects_size_heuristic():
    """Under attention residual evidence, the measured score-bytes
    crossover still gates per instance: a small-context op stays on the
    einsum path even when the profiled model's residual nominated the
    family."""
    from flexflow_tpu.kernels.registry import flash_crossover

    reg = KernelRegistry()
    reg._residuals = {"attention": 2.0}
    big = reg.select("attention", backend="tpu",
                     heuristic=lambda: True, record=False)
    assert big and big.reason == "residual"
    small = reg.select("attention", backend="tpu",
                       heuristic=lambda: False, record=False)
    assert not small and small.reason == "heuristic"
    # the shared helper itself: bert-bench scale crosses, tiny does not
    assert flash_crossover(64, 16, 512, 512, dp=1)
    assert not flash_crossover(2, 4, 64, 64, dp=1)


def test_registry_per_call_config_isolation(tmp_path):
    """Two models with different --kernel-impl knobs in one process:
    select(config=...) resolves each model's own knob regardless of
    which one configure()d the process default last (the retrace-after-
    another-compile hazard)."""
    import flexflow_tpu as ff

    cfg_a = ff.FFConfig()
    cfg_a.kernel_impl = "layernorm=pallas"
    cfg_b = ff.FFConfig()  # auto
    reg = KernelRegistry()
    reg.configure(cfg_b)  # B compiled LAST — the process default
    a = reg.select("layernorm", config=cfg_a, record=False)
    assert a and a.reason == "config"
    assert not reg.select("layernorm", config=cfg_b, record=False)
    # and a config-carrying call ignores the global default entirely
    reg.configure(cfg_a)
    assert not reg.select("layernorm", config=cfg_b, record=False)


def test_cost_model_gates_match_lowering():
    """The simulator never discounts an op the lowering would not fuse:
    non-trailing-axis norms and non-last-axis softmax price at 1.0 even
    with pallas forced."""
    import flexflow_tpu as ff
    from flexflow_tpu.search.machine_model import make_machine_model
    from flexflow_tpu.search.simulator import CostModel, OpStrategy

    cfg = ff.FFConfig()
    cfg.num_devices = 1
    m = ff.FFModel(cfg)
    inp = m.create_tensor([4, 16, 32])
    m.layer_norm(inp, [1], name="ln_axis1")       # NOT trailing
    m.softmax(inp, axis=0, name="sm_axis0")       # NOT last
    ops = {op.name: op for op in m.ops}
    cost = CostModel(make_machine_model(cfg, 1), cfg)
    s = OpStrategy()
    with force_pallas("layernorm", "softmax"):
        assert cost.kernel_time_factor(ops["ln_axis1"], s) == 1.0
        assert cost.kernel_time_factor(ops["sm_axis0"], s) == 1.0


def test_registry_profile_roundtrip_residuals(tmp_path):
    from flexflow_tpu.obs.refit import FittedCoefficients, FittedProfile

    path = str(tmp_path / "p.json")
    FittedProfile(chip="x", backend="cpu",
                  coefficients=FittedCoefficients(),
                  op_family_residuals={"attention": 2.5}).save(path)
    loaded = FittedProfile.load(path, expect_chip="x",
                                expect_backend="cpu")
    assert loaded.op_family_residuals == {"attention": 2.5}


def test_registry_selection_counter():
    from flexflow_tpu.obs import REGISTRY

    fam = REGISTRY.counter("ff_kernel_selected_total",
                           "Kernel-tier selections by op family and "
                           "implementation", labels=("op", "impl"))
    before = fam.value(op="rmsnorm", impl="pallas")
    with KERNELS.override("rmsnorm", "pallas"):
        KERNELS.select("rmsnorm")
        KERNELS.select("rmsnorm", record=False)  # peeks never count
    assert fam.value(op="rmsnorm", impl="pallas") == before + 1


# ---------------------------------------------------------------------
# simulator pricing: the search sees the kernel tier
# ---------------------------------------------------------------------
def test_cost_model_prices_pallas_selection():
    import flexflow_tpu as ff
    from flexflow_tpu.search.machine_model import make_machine_model
    from flexflow_tpu.search.simulator import CostModel, OpStrategy

    cfg = ff.FFConfig()
    cfg.num_devices = 1
    m = ff.FFModel(cfg)
    inp = m.create_tensor([4, 16, 32])
    m.layer_norm(inp, [-1], name="ln")
    ln_op = [op for op in m.ops if op.op_type.value == "layernorm"][0]
    s = OpStrategy(dp=1, tp=1)
    # fresh CostModel per selection regime: the factor memo assumes the
    # policy is stable for one model's lifetime
    t_ref = CostModel(make_machine_model(cfg, 1), cfg).forward_time_us(
        ln_op, s)
    with KERNELS.override("layernorm", "pallas"):
        t_pallas = CostModel(make_machine_model(cfg, 1),
                             cfg).forward_time_us(ln_op, s)
    assert t_pallas == pytest.approx(
        t_ref * PALLAS_COST_GAIN["layernorm"], rel=1e-6)
    assert t_pallas < t_ref


# ---------------------------------------------------------------------
# op lowerings: forced-pallas model matches the reference model
# ---------------------------------------------------------------------
def _tiny_model(seed=0):
    import flexflow_tpu as ff

    cfg = ff.FFConfig()
    cfg.batch_size = 4
    cfg.seed = seed
    m = ff.FFModel(cfg)
    inp = m.create_tensor([4, 6, 32])
    t = m.layer_norm(inp, [-1], name="ln")
    t = m.rms_norm(t, [-1], name="rms")
    t = m.dense(t, 10, name="cls")
    m.softmax(t)
    m.compile(optimizer=ff.SGDOptimizer(m, lr=0.05),
              loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
              metrics=[ff.MetricsType.METRICS_ACCURACY])
    return m


def test_training_parity_reference_vs_forced_pallas():
    """Same data, same seed: a full fit() through the fused layernorm/
    rmsnorm/softmax/reduction kernels lands on the reference run's loss
    to float tolerance — fwd AND bwd exercised end-to-end."""
    rng = np.random.RandomState(8)
    x = rng.randn(8, 6, 32).astype(np.float32)
    y = rng.randint(0, 10, size=(8, 6, 1)).astype(np.int32)
    h_ref = _tiny_model().fit([x], y, batch_size=4, epochs=2)
    with force_pallas("layernorm", "rmsnorm", "softmax", "reduction"):
        h_fused = _tiny_model().fit([x], y, batch_size=4, epochs=2)
    assert h_fused[-1]["loss"] == pytest.approx(h_ref[-1]["loss"],
                                               rel=1e-4, abs=1e-5)
    assert h_fused[-1]["accuracy"] == h_ref[-1]["accuracy"]


def test_rms_norm_op_reference_lowering_correct():
    """The RMSNorm op's reference lowering (and its multi-axis fallback
    route) against a direct jnp computation."""
    import flexflow_tpu as ff

    cfg = ff.FFConfig()
    cfg.batch_size = 2
    cfg.allow_mixed_precision = False  # f32 oracle comparison
    m = ff.FFModel(cfg)
    inp = m.create_tensor([2, 3, 16])
    m.rms_norm(inp, [-1], name="rms")
    m.compile(optimizer=ff.SGDOptimizer(m, lr=0.0),
              loss_type=ff.LossType.LOSS_IDENTITY)
    x = np.random.RandomState(9).randn(2, 3, 16).astype(np.float32)
    out = m.predict(x)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_ref_rmsnorm(jnp.asarray(x))),
        **F32_TOL)


# ---------------------------------------------------------------------
# continuous batcher: fused decode, token-identical, slot reuse
# ---------------------------------------------------------------------
def test_continuous_batcher_fused_decode_token_parity():
    """Greedy decode through the continuous batcher with the fused
    vector-decode kernel FORCED (registry override; interpret mode on
    CPU) is token-identical to the lockstep reference — ragged prompt
    lengths AND slot reuse (3 requests through 2 slots)."""
    from flexflow_tpu.serving.generate import GenerativeSession
    from flexflow_tpu.serving.sched import ContinuousBatcher
    from tests.test_generate import _build_lm

    lm = _build_lm(2, 12)
    rng = np.random.RandomState(10)
    prompts = [rng.randint(1, 50, size=(n,)).astype(np.int32)
               for n in (4, 7, 3)]
    session = GenerativeSession(lm, max_len=12)
    refs = [session.generate(p[None, :], 5)[0] for p in prompts]
    with force_pallas("attention_decode"):
        with ContinuousBatcher(lm, max_len=12, num_slots=2, page_size=4,
                               max_queue=8) as cb:
            outs = [r.result(timeout=300)
                    for r in [cb.submit(p, 5) for p in prompts]]
    for out, ref in zip(outs, refs):
        np.testing.assert_array_equal(out, np.asarray(ref))


def test_calibration_kernel_candidates_ranking():
    """Synthetic calibration rows: the candidates section ranks by
    residual weighted by predicted-step share, and op_family_residuals
    takes the per-family MEDIAN."""
    from flexflow_tpu.obs.calibration import (CalibrationReport,
                                              OpCalibration,
                                              op_family_residuals)

    rows = [
        # layernorm: big residual (x3) but small share
        OpCalibration("ln1", "layernorm", "dp=1", 10.0, 30.0),
        OpCalibration("ln2", "layernorm", "dp=1", 10.0, 50.0),
        OpCalibration("ln3", "layernorm", "dp=1", 10.0, 30.0),
        # attention: modest residual (x1.5) on most of the step
        OpCalibration("attn", "multihead_attention", "dp=1", 400.0, 600.0),
        # linear: not a kernel-tier family — never a candidate
        OpCalibration("fc", "linear", "dp=1", 100.0, 500.0),
        # failed measurement: excluded from residuals
        OpCalibration("sm", "softmax", "dp=1", 5.0, float("nan"),
                      error="x"),
    ]
    fams = op_family_residuals(rows)
    assert fams["layernorm"] == 3.0  # median of [3, 5, 3]
    assert fams["attention"] == 1.5
    assert "softmax" not in fams and "linear" not in fams

    rep = CalibrationReport(backend="cpu", predicted_step_us=1000.0,
                            measured_step_us=1500.0, measured_steps=3,
                            ops=rows)
    cands = rep.kernel_candidates()
    by_fam = {c["family"]: c for c in cands}
    assert set(by_fam) == {"layernorm", "attention", "softmax"}
    # attention: 0.5 residual excess * (400/535) share beats layernorm's
    # 2.0 excess * (30/535)
    assert cands[0]["family"] == "attention"
    assert by_fam["softmax"]["score"] == 0.0  # unmeasurable -> no score
    assert by_fam["layernorm"]["score"] == pytest.approx(
        2.0 * 30.0 / 535.0)
    # the report renders and serializes with the section included
    assert "kernel candidates" in rep.format_kernel_report()
    assert rep.to_dict()["kernel_candidates"][0]["family"] == "attention"


def test_refit_persists_family_residuals(tmp_path):
    """A real refit run records the per-family residuals into the saved
    profile, and a fresh registry configured with that profile sees
    them."""
    import flexflow_tpu as ff
    from flexflow_tpu.obs import calibrate
    from flexflow_tpu.obs.refit import FittedProfile, refit

    m = _tiny_model()
    x = np.random.RandomState(11).randn(8, 6, 32).astype(np.float32)
    y = np.random.RandomState(11).randint(
        0, 10, size=(8, 6, 1)).astype(np.int32)
    m.fit([x], y, batch_size=4, epochs=2)
    rep = calibrate(m)
    measured = rep.measured_step_us or 5000.0
    profile, _ = refit(m, measured, rep.ops, rounds=1, tol=0.15)
    # the tiny model has layernorm+rmsnorm+softmax rows; at least one
    # family must have produced evidence
    assert profile.op_family_residuals
    path = str(tmp_path / "fitted.json")
    profile.save(path)
    assert (FittedProfile.load(path).op_family_residuals
            == profile.op_family_residuals)
    cfg = ff.FFConfig()
    cfg.fitted_profile_file = path
    reg = KernelRegistry()
    reg.configure(cfg)
    assert reg.residual_source == path
    for fam, r in profile.op_family_residuals.items():
        assert reg.residual(fam) == r

# ---------------------------------------------------------------------
# multi-query decode kernel (ISSUE 14)
# ---------------------------------------------------------------------
def _ref_mq_decode(q, kc, vc, pos, scale):
    kc, vc = _per_head(q, kc), _per_head(q, vc)
    b, c = q.shape[0], q.shape[1]
    m = kc.shape[1]
    qpos = pos[:, None] + jnp.arange(c)[None, :]
    mask = (jnp.arange(m)[None, None, :]
            <= qpos[:, :, None])[:, None, :, :]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kc.astype(q.dtype),
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype),
                      vc.astype(q.dtype))


@pytest.mark.parametrize("block_k", [64, 8])  # single- and multi-block
def test_fused_multiquery_decode_parity(block_k):
    from flexflow_tpu.kernels.pallas import (
        fused_multiquery_decode_attention)

    rng = np.random.RandomState(12)
    B, C, M, h, d = 5, 3, 24, 3, 8
    q = _rand(rng, (B, C, h, d))
    kc = _rand(rng, (B, M, h * d))
    vc = _rand(rng, (B, M, h * d))
    # ragged: pos 0 (the query window IS the live prefix) through M-C
    # (the window ends at the last cache row)
    pos = jnp.asarray([0, 3, 11, 21, 7], dtype=jnp.int32)
    scale = 1.0 / np.sqrt(d)
    out = fused_multiquery_decode_attention(
        q, kc, vc, pos, scale=scale, block_k=block_k, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_ref_mq_decode(q, kc, vc, pos, scale)),
        rtol=1e-5, atol=1e-6)


def test_fused_multiquery_decode_bf16_cache():
    from flexflow_tpu.kernels.pallas import (
        fused_multiquery_decode_attention)

    rng = np.random.RandomState(13)
    B, C, M, h, d = 2, 4, 16, 2, 16
    q = _rand(rng, (B, C, h, d))
    kc = _rand(rng, (B, M, h * d), jnp.bfloat16)
    vc = _rand(rng, (B, M, h * d), jnp.bfloat16)
    pos = jnp.asarray([5, 12], dtype=jnp.int32)
    scale = 1.0 / np.sqrt(d)
    for block_k in (64, 8):
        out = fused_multiquery_decode_attention(
            q, kc, vc, pos, scale=scale, block_k=block_k, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32),
            np.asarray(_ref_mq_decode(q, kc, vc, pos, scale), np.float32),
            **BF16_TOL)


def test_fused_multiquery_c1_matches_single_query():
    """C = 1 through the multi-query entry is the single-query kernel's
    exact math (shared body), in both block regimes."""
    from flexflow_tpu.kernels.pallas import (
        fused_multiquery_decode_attention)

    rng = np.random.RandomState(14)
    B, M, h, d = 3, 24, 2, 8
    q = _rand(rng, (B, 1, h, d))
    kc = _rand(rng, (B, M, h * d))
    vc = _rand(rng, (B, M, h * d))
    pos = jnp.asarray([0, 9, 23], dtype=jnp.int32)
    for block_k in (64, 8):
        a = fused_multiquery_decode_attention(
            q, kc, vc, pos, scale=0.3, block_k=block_k, interpret=True)
        b = fused_decode_attention(
            q, kc, vc, pos, scale=0.3, block_k=block_k, interpret=True)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_continuous_batcher_fused_decode_multiblock_token_parity():
    """Satellite 3 (lifts the PR 9 docs caveat): greedy decode through
    the continuous batcher with BOTH fused decode kernels forced and
    flash_block_k SMALLER than the cache span — every decode streams
    multiple KV blocks through the online softmax — stays
    token-identical to the pure-reference run. Ragged prompts, slot
    reuse (4 requests through 2 slots), chunked prefill through the
    multi-query kernel."""
    from flexflow_tpu.serving.sched import ContinuousBatcher
    from tests.test_generate import _build_lm

    rng = np.random.RandomState(15)
    prompts = [rng.randint(1, 50, size=(n,)).astype(np.int32)
               for n in (4, 9, 3, 7)]

    def run(forced):
        lm = _build_lm(2, 12)
        lm.config.flash_block_k = 8  # cache span 24 -> 3 KV blocks
        import contextlib
        with contextlib.ExitStack() as st:
            for fam in forced:
                st.enter_context(KERNELS.override(fam, "pallas"))
            with ContinuousBatcher(lm, max_len=24, num_slots=2,
                                   page_size=4, max_queue=8) as cb:
                return [r.result(timeout=300).tolist()
                        for r in [cb.submit(p, 10) for p in prompts]]

    ref = run(())
    fused = run(("attention_decode", "attention_decode_mq"))
    assert fused == ref


def test_chunk_offset_prefill_lowers_through_mq_kernel():
    """The chunk-offset (scalar-pos) prefill entry lowers through the
    multi-query kernel when selected: a chunked prefill with the kernel
    forced produces the same first token and downstream stream as the
    reference chunk path."""
    from flexflow_tpu.serving.sched import ContinuousBatcher
    from tests.test_generate import _build_lm

    lm = _build_lm(2, 12)
    prompt = np.random.RandomState(16).randint(
        1, 50, size=(9,)).astype(np.int32)

    def run(force):
        import contextlib
        with contextlib.ExitStack() as st:
            if force:
                st.enter_context(KERNELS.override("attention_decode_mq",
                                                  "pallas"))
            with ContinuousBatcher(lm, max_len=16, num_slots=2,
                                   page_size=4, prefill_chunk_tokens=4,
                                   max_queue=4) as cb:
                return cb.submit(prompt, 5).result(timeout=300).tolist()

    assert run(True) == run(False)


# ---------------------------------------------------------------------
# registry: mq family, fitted thresholds, decode pricing
# ---------------------------------------------------------------------
def test_registry_mq_family_aliases_attention_residual(tmp_path):
    import json

    from flexflow_tpu.obs.refit import FittedCoefficients, FittedProfile

    prof = FittedProfile(chip="c", backend="cpu",
                         coefficients=FittedCoefficients(),
                         op_family_residuals={"attention": 1.5})
    path = str(tmp_path / "p.json")
    prof.save(path)
    assert "attention" in json.load(open(path))["op_family_residuals"]
    reg = KernelRegistry()

    class Cfg:
        kernel_impl = "auto"
        fitted_profile_file = path
        kernel_residual_threshold = 1.10

    d = reg.select("attention_decode_mq", backend="tpu", config=Cfg(),
                   record=False)
    assert d and d.reason == "residual"
    # no evidence -> reference
    assert not reg.select("attention_decode_mq", backend="tpu",
                          record=False)


def test_registry_fitted_threshold_overrides_knob(tmp_path):
    """A profile carrying kernel_residual_thresholds wins over the
    hand-set --kernel-residual-threshold default: evidence below the
    knob but above the FITTED threshold selects pallas, and a fitted
    threshold ABOVE the knob demands the stronger evidence."""
    from flexflow_tpu.obs.refit import FittedCoefficients, FittedProfile

    def mk(residual, fitted):
        prof = FittedProfile(
            chip="c", backend="cpu", coefficients=FittedCoefficients(),
            op_family_residuals={"attention": residual},
            kernel_residual_thresholds=(
                {"attention": fitted} if fitted else {}))
        path = str(tmp_path / f"p_{residual}_{fitted}.json")
        prof.save(path)

        class Cfg:
            kernel_impl = "auto"
            fitted_profile_file = path
            kernel_residual_threshold = 1.10

        return Cfg()

    reg = KernelRegistry()
    # residual 1.05 < knob 1.10: reference without a fitted threshold...
    assert not reg.select("attention_decode", backend="tpu",
                          config=mk(1.05, None), record=False)
    # ...but pallas when the PALLAS impl measured at 1.02
    assert reg.select("attention_decode", backend="tpu",
                      config=mk(1.05, 1.03), record=False)
    # a fitted threshold above the knob demands more evidence
    assert not reg.select("attention_decode", backend="tpu",
                          config=mk(1.15, 1.30), record=False)
    assert reg.select("attention_decode", backend="tpu",
                      config=mk(1.35, 1.30), record=False)


def test_fit_kernel_thresholds_from_pallas_rows():
    """The fitted threshold is the fused impl's own median residual x
    margin, floored at 1.0 — derived from before/after measurement rows,
    replacing the hand-set 1.10 constant."""
    from flexflow_tpu.obs.calibration import OpCalibration
    from flexflow_tpu.obs.refit import fit_kernel_thresholds

    rows = [
        OpCalibration("a1", "multihead_attention", "dp=1", 10.0, 10.4),
        OpCalibration("a2", "multihead_attention", "dp=1", 10.0, 10.6),
        OpCalibration("a3", "multihead_attention", "dp=1", 10.0, 10.4),
        # a fused impl BEATING the roofline still floors at 1.0
        OpCalibration("ln", "layernorm", "dp=1", 10.0, 7.0),
        # degenerate rows are excluded
        OpCalibration("sm", "softmax", "dp=1", 5.0, float("nan"),
                      error="x"),
    ]
    th = fit_kernel_thresholds(rows, margin=1.02)
    assert th["attention"] == pytest.approx(1.04 * 1.02)
    assert th["layernorm"] == pytest.approx(1.02)
    assert "softmax" not in th


def test_fitted_thresholds_profile_roundtrip(tmp_path):
    from flexflow_tpu.obs.refit import (FittedCoefficients, FittedProfile)

    prof = FittedProfile(
        chip="c", backend="cpu", coefficients=FittedCoefficients(),
        kernel_residual_thresholds={"attention": 1.07, "layernorm": 1.0})
    path = str(tmp_path / "p.json")
    prof.save(path)
    assert (FittedProfile.load(path, expect_backend="cpu")
            .kernel_residual_thresholds
            == {"attention": 1.07, "layernorm": 1.0})


def test_cost_model_prices_decode_dispatches():
    """decode_step_time_us prices the serving hot dispatches through the
    kernel tier: fused/reference ratio is exactly the family's
    PALLAS_COST_GAIN, the multi-query dispatch costs more than the
    single-query one, and C rides through the mq family."""
    from flexflow_tpu.ffconst import OpType
    from flexflow_tpu.search.machine_model import make_machine_model
    from flexflow_tpu.search.simulator import CostModel
    from tests.test_generate import _build_lm

    lm = _build_lm(2, 12)
    attn = next(op for op in lm.graph.ops.values()
                if op.op_type == OpType.MULTIHEAD_ATTENTION)
    machine = make_machine_model(lm.config, 1)
    cost = CostModel(machine, lm.config)
    ref1 = cost.decode_step_time_us(attn, 4, 64, 1)
    ref4 = cost.decode_step_time_us(attn, 4, 64, 4)
    # the mq dispatch streams the SAME cache once for all C queries —
    # at decode sizes the roofline is bytes-bound, so C is (near) free:
    # that amortization is the whole speculative-decoding win
    assert ref4 >= ref1 > 0
    with force_pallas("attention_decode", "attention_decode_mq"):
        cost2 = CostModel(machine, lm.config)
        assert cost2.decode_step_time_us(attn, 4, 64, 1) / ref1 == \
            pytest.approx(PALLAS_COST_GAIN["attention_decode"])
        assert cost2.decode_step_time_us(attn, 4, 64, 4) / ref4 == \
            pytest.approx(PALLAS_COST_GAIN["attention_decode_mq"])
