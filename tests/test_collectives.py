"""Explicit collective lowering (ISSUE 11, runtime/collectives.py).

Pins the pricing->execution contract: the per-tier reduction schedule
the Unity search synthesizes (docs/machine.md) is LOWERED into real
grouped collectives — numerically parity with the GSPMD path it
replaces, visibly decomposed in the compiled HLO, counted/spanned for
traces, checked by FFTA072 against the priced plan, and measurable by
collective-bench into rows the per-tier refit consumes.
"""
import math

import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.runtime.collectives import (CollectiveLoweringError,
                                              lower_allreduce,
                                              tier_axis_groups)

# two "pods" of four devices on the 8-dev test mesh, DCN-class outer tier
SPEC_4x2 = {"chip": "tpu-v5e", "tiers": [
    {"name": "ici", "degree": 4, "gbps": 45.0, "links": 2},
    {"name": "dcn", "degree": 2, "gbps": 3.125, "links": 1,
     "latency_us": 10.0}]}


def _make_machine(n=8, spec=SPEC_4x2):
    from flexflow_tpu.search.machine_model import HierarchicalMachineModel

    return HierarchicalMachineModel.from_json(spec)


# -- tier group math -------------------------------------------------------

def test_tier_axis_groups_mixed_radix():
    groups = tier_axis_groups(8, [4, 2])
    assert groups[0] == [[0, 1, 2, 3], [4, 5, 6, 7]]
    assert groups[1] == [[0, 4], [1, 5], [2, 6], [3, 7]]
    g3 = tier_axis_groups(8, [2, 2, 2])
    assert g3[0] == [[0, 1], [2, 3], [4, 5], [6, 7]]
    assert g3[1] == [[0, 2], [1, 3], [4, 6], [5, 7]]
    assert g3[2] == [[0, 4], [1, 5], [2, 6], [3, 7]]


def test_tier_axis_groups_bad_product():
    with pytest.raises(CollectiveLoweringError):
        tier_axis_groups(8, [4, 3])


# -- leaf-level lowering vs plain psum -------------------------------------

def _apply_strategy(x_global, strategy, sizes, dtype=np.float32):
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    n = x_global.shape[0]
    mesh = Mesh(np.array(jax.devices()[:n]), ("data",))
    groups = tier_axis_groups(n, sizes)

    def body(x):
        return lower_allreduce(x[0], "data", strategy, sizes, groups)[None]

    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                               out_specs=P("data"), check_vma=False))
    return np.asarray(fn(x_global.astype(dtype)))


@pytest.mark.parametrize("strategy", ["flat", "rs_ar_ag", "hier_ring"])
@pytest.mark.parametrize("length", [16, 13, 3])
def test_lower_allreduce_sums_exactly(strategy, length):
    # length 13/3: not divisible by the inner tier degree — the
    # rs_ar_ag pad/unpad path
    x = np.arange(8 * length, dtype=np.float32).reshape(8, length)
    out = _apply_strategy(x, strategy, [4, 2])
    expected = np.tile(x.sum(axis=0), (8, 1))
    np.testing.assert_allclose(out, expected, rtol=1e-6)


def test_lower_allreduce_bf16():
    import jax.numpy as jnp

    x = np.random.RandomState(0).randn(8, 24).astype(np.float32)
    ref = _apply_strategy(x, "flat", [4, 2], dtype=jnp.bfloat16)
    out = _apply_strategy(x, "rs_ar_ag", [4, 2], dtype=jnp.bfloat16)
    np.testing.assert_allclose(out.astype(np.float32),
                               ref.astype(np.float32), rtol=2e-2,
                               atol=1e-2)


def test_lower_allreduce_three_tiers():
    x = np.random.RandomState(1).randn(8, 10).astype(np.float32)
    out = _apply_strategy(x, "rs_ar_ag", [2, 2, 2])
    np.testing.assert_allclose(out, np.tile(x.sum(axis=0), (8, 1)),
                               rtol=1e-5)


# -- end-to-end parity: explicit vs GSPMD vs 1-dev -------------------------

def _train(lowering, n_dev, mixed=False, spec=SPEC_4x2, epochs=2,
           bucket_bytes=None, overlap=True):
    cfg = ff.FFConfig()
    cfg.num_devices = n_dev
    cfg.batch_size = 16
    cfg.allow_mixed_precision = mixed
    cfg.seed = 7
    cfg.collective_lowering = lowering
    if bucket_bytes is not None:
        cfg.grad_bucket_bytes = bucket_bytes
    cfg.search_overlap_backward_update = overlap
    if n_dev > 1 and spec is not None:
        cfg.machine_model_file = spec
    m = ff.FFModel(cfg)
    x_t = m.create_tensor([16, 64])
    t = m.dense(x_t, 256, ff.ActiMode.AC_MODE_RELU, name="fc_big")
    t = m.dense(t, 64, name="fc_small")
    m.softmax(m.dense(t, 4, name="cls"))
    m.compile(optimizer=ff.SGDOptimizer(m, lr=0.05),
              loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
              metrics=[ff.MetricsType.METRICS_ACCURACY],
              parallel_axes={"data": n_dev} if n_dev > 1 else None)
    x = np.random.RandomState(0).randn(32, 64).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 4, size=(32, 1)).astype(np.int32)
    hist = m.fit([x], y, batch_size=16, epochs=epochs)
    return [h["loss"] for h in hist], m


def test_explicit_parity_f32():
    losses_g, _ = _train("gspmd", 8)
    losses_e, m = _train("explicit", 8)
    losses_1, _ = _train("gspmd", 1)
    lowering = m.executor.grad_sync_lowering
    assert lowering is not None
    # the synthesized plan covers cross-tier strategies, and the
    # executed schedule matches the priced plan (the FFTA072 contract)
    executed = lowering.executed_plan()
    planned = {k: v["strategy"] for k, v in m._reduction_plan.items()}
    for name, strat in planned.items():
        assert executed[name] == strat
    assert any(len(e["sizes"]) > 1 for e in lowering.entries.values())
    for le, lg in zip(losses_e, losses_g):
        assert abs(le - lg) / max(abs(lg), 1e-8) < 1e-5, (losses_e,
                                                          losses_g)
    assert abs(losses_e[-1] - losses_1[-1]) \
        / max(abs(losses_1[-1]), 1e-8) < 2e-3


def test_explicit_parity_bf16():
    losses_g, _ = _train("gspmd", 8, mixed=True)
    losses_e, _ = _train("explicit", 8, mixed=True)
    assert abs(losses_e[-1] - losses_g[-1]) \
        / max(abs(losses_g[-1]), 1e-8) < 5e-3, (losses_e, losses_g)


def test_explicit_on_flat_machine_is_flat_psum():
    # no machine spec: no tiers, the lowering still runs — every sync a
    # flat psum — and parity holds
    losses_g, _ = _train("gspmd", 8, spec=None)
    losses_e, m = _train("explicit", 8, spec=None)
    lowering = m.executor.grad_sync_lowering
    assert lowering is not None
    assert set(lowering.executed_plan().values()) == {"flat"}
    for le, lg in zip(losses_e, losses_g):
        assert abs(le - lg) / max(abs(lg), 1e-8) < 1e-5


def test_auto_lowers_cross_tier_and_skips_flat():
    _, m_tiered = _train("auto", 8)
    assert m_tiered.executor.grad_sync_lowering is not None
    _, m_flat = _train("auto", 8, spec=None)
    # nothing cross-tier on a flat machine: auto keeps GSPMD
    assert m_flat.executor.grad_sync_lowering is None


def test_partial_final_batch_falls_back_to_gspmd():
    # 40 samples at batch 16 -> final batch of 8, which 8 devices still
    # divide; use batch 12 -> 12 % 8 != 0 exercises the trace-time
    # fallback inside the wrapped step
    cfg = ff.FFConfig()
    cfg.num_devices = 8
    cfg.batch_size = 12
    cfg.allow_mixed_precision = False
    cfg.collective_lowering = "explicit"
    cfg.machine_model_file = SPEC_4x2
    m = ff.FFModel(cfg)
    x_t = m.create_tensor([12, 16])
    m.softmax(m.dense(x_t, 4, name="cls"))
    m.compile(optimizer=ff.SGDOptimizer(m, lr=0.05),
              loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
              metrics=[], parallel_axes={"data": 8})
    x = np.random.RandomState(0).randn(12, 16).astype(np.float32)
    y = np.zeros((12, 1), dtype=np.int32)
    hist = m.fit([x], y, batch_size=12, epochs=1)
    assert np.isfinite(hist[0]["loss"])


# -- compiled HLO contains the decomposition -------------------------------

def test_explicit_hlo_contains_reduce_scatter_all_gather():
    import jax

    _, m = _train("explicit", 8, epochs=1)
    assert any(e["strategy"] == "rs_ar_ag"
               for e in m.executor.grad_sync_lowering.entries.values())
    ex = m.executor
    x = np.random.RandomState(0).randn(16, 64).astype(np.float32)
    y = np.zeros((16, 1), dtype=np.int32)
    inputs = {m.input_ops[0].name: ex.shard_batch(x)}
    label = ex.shard_batch(y)
    hlo = m._train_step.__wrapped__.lower(
        m.params, m.opt_state, m.state, inputs, label,
        jax.random.PRNGKey(0)).as_text()
    assert "reduce_scatter" in hlo
    assert "all_gather" in hlo
    # and the GSPMD baseline of the same model does NOT carry the
    # manual grouped decomposition marker
    _, m_g = _train("gspmd", 8, epochs=1)
    hlo_g = m_g._train_step.__wrapped__.lower(
        m_g.params, m_g.opt_state, m_g.state,
        {m_g.input_ops[0].name: m_g.executor.shard_batch(x)},
        m_g.executor.shard_batch(y), jax.random.PRNGKey(0)).as_text()
    assert "reduce_scatter" not in hlo_g


# -- gating ----------------------------------------------------------------

def test_explicit_raises_on_model_axis():
    cfg = ff.FFConfig()
    cfg.num_devices = 8
    cfg.batch_size = 16
    cfg.collective_lowering = "explicit"
    cfg.machine_model_file = SPEC_4x2
    m = ff.FFModel(cfg)
    x_t = m.create_tensor([16, 32])
    m.softmax(m.dense(x_t, 8, name="cls"))
    with pytest.raises(CollectiveLoweringError):
        m.compile(
            optimizer=ff.SGDOptimizer(m, lr=0.05),
            loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
            metrics=[], parallel_axes={"data": 4, "model": 2})


def test_auto_falls_back_on_model_axis_and_stateful_ops():
    cfg = ff.FFConfig()
    cfg.num_devices = 8
    cfg.batch_size = 16
    cfg.collective_lowering = "auto"
    cfg.machine_model_file = SPEC_4x2
    m = ff.FFModel(cfg)
    x_t = m.create_tensor([16, 32])
    m.softmax(m.dense(x_t, 8, name="cls"))
    m.compile(optimizer=ff.SGDOptimizer(m, lr=0.05),
              loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
              metrics=[], parallel_axes={"data": 4, "model": 2})
    assert m.executor.grad_sync_lowering is None
    assert any("model" in r for r in m.executor._grad_sync_reasons)
    # batch-norm running stats need GSPMD's global batch statistics
    cfg2 = ff.FFConfig()
    cfg2.num_devices = 8
    cfg2.batch_size = 16
    cfg2.collective_lowering = "auto"
    cfg2.machine_model_file = SPEC_4x2
    m2 = ff.FFModel(cfg2)
    inp = m2.create_tensor([16, 3, 8, 8])
    t = m2.conv2d(inp, 4, 3, 3, 1, 1, 1, 1, name="c1")
    t = m2.batch_norm(t, name="bn")
    t = m2.flat(t)
    m2.softmax(m2.dense(t, 4, name="cls2"))
    m2.compile(optimizer=ff.SGDOptimizer(m2, lr=0.05),
               loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
               metrics=[], parallel_axes={"data": 8})
    assert m2.executor.grad_sync_lowering is None
    assert any("state" in r for r in m2.executor._grad_sync_reasons)


def test_bad_knob_value_rejected():
    cfg = ff.FFConfig()
    with pytest.raises(ValueError):
        cfg.parse_args(["--collective-lowering", "magic"])
    rest = cfg.parse_args(["--collective-lowering", "auto"])
    assert rest == [] and cfg.collective_lowering == "auto"


# -- observability: counter + spans ----------------------------------------

def test_lowered_counter_and_grad_sync_span():
    from flexflow_tpu.obs import enable_tracing, get_tracer
    from flexflow_tpu.obs.registry import REGISTRY

    enable_tracing()
    _, m = _train("explicit", 8, epochs=1)
    c = REGISTRY.counter(
        "ff_collective_lowered_total",
        "Collectives lowered explicitly, by reduction strategy and tier",
        labels=("strategy", "tier"))
    entries = m.executor.grad_sync_lowering.entries
    for e in entries.values():
        for tier in e["tiers"]:
            assert c.value(strategy=e["strategy"], tier=tier) >= 1
    spans = get_tracer().events("exec.grad_sync")
    assert spans, get_tracer().span_names()
    args = spans[0]["args"]
    assert args["mode"] == "explicit" and args["tensors"] == len(entries)


def test_resharding_transfer_rows_and_span():
    import jax
    from jax.sharding import Mesh

    from flexflow_tpu.obs import enable_tracing, get_tracer
    from flexflow_tpu.resharding.executor import redistribute
    from flexflow_tpu.resharding.plan import (ArraySpec, MeshSpec,
                                              ShardingPlan)

    enable_tracing()
    machine = _make_machine()
    tree = {"w": np.arange(8 * 16, dtype=np.float32).reshape(8, 16)}
    old = ShardingPlan(
        mesh=MeshSpec(device_ids=tuple(range(8)), axes=(("data", 8),)),
        arrays={"w": ArraySpec(degrees=(8, 1), axes=("data", None))})
    new = ShardingPlan(
        mesh=MeshSpec(device_ids=tuple(range(8)), axes=(("data", 8),)),
        arrays={})  # replicate: a pure all-gather move
    res = redistribute(tree, old, new, peak_bytes=1 << 20,
                       machine=machine, collect_timings=True)
    assert res.calibration_rows
    row = res.calibration_rows[0]
    assert row.op == "allgather" and row.measured_us > 0
    # the gather group is the full 8-wide data axis, spanning both
    # tiers of the 4x2 spec
    assert row.tier == "dcn" and row.participants == 8
    assert math.isfinite(row.predicted_us) and row.predicted_us > 0
    assert get_tracer().events("exec.transfer")
    np.testing.assert_array_equal(
        np.asarray(res.tree["w"]), tree["w"])
    # timings are opt-in: the default path keeps rounds async and
    # collects nothing
    res2 = redistribute(tree, old, new, peak_bytes=1 << 20,
                        machine=machine)
    assert res2.calibration_rows == []


def test_intra_pod_allgather_labeled_with_its_groups_tier():
    # on a DCN-spanning mesh, a gather whose group stays inside one
    # ICI pod must label its rows AND its counter series 'ici', not
    # the whole mesh's outermost tier
    from flexflow_tpu.obs.registry import REGISTRY
    from flexflow_tpu.resharding.executor import redistribute
    from flexflow_tpu.resharding.plan import (ArraySpec, MeshSpec,
                                              ShardingPlan)
    from flexflow_tpu.runtime.collectives import lowered_counter

    machine = _make_machine()
    mesh = MeshSpec(device_ids=tuple(range(8)),
                    axes=(("data", 2), ("model", 4)))
    tree = {"w": np.arange(8 * 16, dtype=np.float32).reshape(8, 16)}
    old = ShardingPlan(
        mesh=mesh,
        arrays={"w": ArraySpec(degrees=(1, 4), axes=(None, "model"))})
    new = ShardingPlan(mesh=mesh, arrays={})
    res = redistribute(tree, old, new, peak_bytes=1 << 20,
                       machine=machine, collect_timings=True)
    assert res.calibration_rows
    # the 4-wide 'model' group is innermost (stride 1): one ICI pod
    assert all(r.tier == "ici" and r.participants == 4
               for r in res.calibration_rows)
    assert lowered_counter().value(strategy="allgather", tier="ici") >= 1
    assert REGISTRY.counter(
        "ff_collective_lowered_total", "x",
        labels=("strategy", "tier")).value(
            strategy="allgather", tier="dcn") == 0
    np.testing.assert_array_equal(np.asarray(res.tree["w"]), tree["w"])


# -- per-tier transfer pricing + chunk cap ---------------------------------

def test_transfer_priced_on_tier_path():
    from flexflow_tpu.resharding.cost import step_cost_us
    from flexflow_tpu.resharding.plan import ReshardStep, TRANSFER

    machine = _make_machine()
    step = ReshardStep(kind=TRANSFER, participants=8,
                       bytes_per_chip=1_000_000)
    tiered = step_cost_us(step, machine)
    # the flat-link price is the innermost tier's p2p — crossing the
    # DCN must cost (much) more
    flat_price = machine.p2p_time_us(step.bytes_per_chip)
    assert tiered > 5 * flat_price
    inner_only = step_cost_us(
        ReshardStep(kind=TRANSFER, participants=1,
                    bytes_per_chip=1_000_000), machine)
    assert inner_only == pytest.approx(flat_price)
    # a REPLICATED landing records participants=1 on the step — the
    # device span (n_devices, threaded by schedule_cost_us) must still
    # price the cross-pod hop
    replicated = step_cost_us(
        ReshardStep(kind=TRANSFER, participants=1,
                    bytes_per_chip=1_000_000), machine, n_devices=8)
    assert replicated == pytest.approx(tiered)


def test_schedule_cost_prices_replicated_transfer_on_device_span():
    from flexflow_tpu.resharding.cost import schedule_cost_us
    from flexflow_tpu.resharding.plan import (ArraySpec, MeshSpec,
                                              ShardingPlan,
                                              plan_redistribution)

    machine = _make_machine()
    tree = {"w": np.zeros((8, 1024), dtype=np.float32)}
    old = ShardingPlan(
        mesh=MeshSpec(device_ids=(0, 1, 2, 3), axes=(("data", 4),)),
        arrays={"w": ArraySpec(degrees=(4, 1), axes=("data", None))})
    # cross-mesh move onto all 8 devices, landing REPLICATED: the
    # TRANSFER step's participants is the array degree (1), but the
    # target group spans both pods
    new = ShardingPlan(
        mesh=MeshSpec(device_ids=tuple(range(8)), axes=(("data", 8),)),
        arrays={})
    sched = plan_redistribution(tree, old, new, peak_bytes=1 << 22,
                                machine=machine)
    cost_tiered = schedule_cost_us(sched, machine)
    transfer_bytes = max(
        s.bytes_per_chip for m in sched.moves for s in m.steps
        if s.kind == "transfer")
    # must be at least the DCN hop price of the transfer leg, far above
    # the innermost p2p
    assert cost_tiered > machine.ring_hop_time_us(transfer_bytes, 8) / 2
    assert cost_tiered > machine.p2p_time_us(transfer_bytes)


def test_cross_tier_transfer_chunk_cap():
    from flexflow_tpu.resharding.plan import (TRANSFER_TIER_CHUNK_BYTES,
                                              transfer_chunk_bound)

    machine = _make_machine()
    # 8 devices span the dcn tier -> the cap engages
    cap = transfer_chunk_bound(machine, 8, kept_degree=1, new_total=1)
    assert cap == int(2 * TRANSFER_TIER_CHUNK_BYTES)
    # 4 devices stay inside one pod -> no cap
    assert transfer_chunk_bound(machine, 4, 1, 1) is None
    assert transfer_chunk_bound(None, 8, 1, 1) is None


# -- FFTA072 ----------------------------------------------------------------

def test_ffta072_tolerates_non_factoring_flat_fallback():
    # tier_path's conservative round-up on a non-factoring mesh (e.g.
    # dp=12 on an 8x2 spec) prices rs_ar_ag over groups that do NOT
    # multiply to the sync degree; the lowering's documented fallback
    # syncs flat — legal, and FFTA072 must not reject the compile
    from flexflow_tpu.analysis.passes import (AnalysisContext,
                                              check_executed_reductions)
    from flexflow_tpu.core.graph import Graph

    cfg = ff.FFConfig()
    cfg.num_devices = 1
    m = ff.FFModel(cfg)
    x_t = m.create_tensor([12, 8])
    m.dense(x_t, 4, name="fc")
    graph = Graph(m.ops)
    plan = {"fc": {"strategy": "rs_ar_ag", "degree": 12,
                   "tiers": [{"tier": "ici", "group": 8},
                             {"tier": "dcn", "group": 2}]}}
    ctx = AnalysisContext(graph=graph, reduction_strategies=plan,
                          executed_reductions={"fc": "flat"})
    assert check_executed_reductions(ctx) == []
    # but a flat substitution where the decomposition WAS expressible
    # still fails
    plan_ok = {"fc": {"strategy": "rs_ar_ag", "degree": 16,
                      "tiers": [{"tier": "ici", "group": 8},
                                {"tier": "dcn", "group": 2}]}}
    ctx2 = AnalysisContext(graph=graph, reduction_strategies=plan_ok,
                           executed_reductions={"fc": "flat"})
    assert len(check_executed_reductions(ctx2)) == 1


def test_lowering_falls_back_flat_on_non_factoring_tiers():
    from flexflow_tpu.runtime.collectives import plan_grad_sync_lowering

    _, m = _train("explicit", 8, epochs=1)
    plan = {name: dict(e) for name, e in m._reduction_plan.items()}
    # corrupt one entry's decomposition so it cannot factor dp=8
    name = next(iter(plan))
    plan[name] = dict(plan[name])
    plan[name]["tiers"] = [{"tier": "ici", "group": 3},
                           {"tier": "dcn", "group": 2}]
    lowering, reasons = plan_grad_sync_lowering(
        m.config, m.graph, m.mesh, plan, pipeline_plan=None)
    assert lowering is not None, reasons
    assert lowering.entries[name]["strategy"] == "flat"
    assert lowering.entries[name]["sizes"] == [8]


def test_ffta072_clean_and_divergent():
    from flexflow_tpu.analysis import analyze_plan
    from flexflow_tpu.analysis.passes import (AnalysisContext,
                                              check_executed_reductions)

    _, m = _train("explicit", 8, epochs=1)
    rep = m.analyze_plan()
    assert not rep.by_code("FFTA072"), rep.format()
    # the full pipeline flags a dropped and a renamed entry
    executed = m.executor.grad_sync_lowering.executed_plan()
    bad = dict(executed)
    renamed = next(iter(bad))
    del bad[renamed]
    rep2 = analyze_plan(
        m.graph, strategies=m._op_strategies,
        machine=None, config=m.config,
        mesh_axes=m.parallel_axes,
        reduction_strategies=m._reduction_plan,
        executed_reductions=bad, passes=("tiers",))
    assert rep2.by_code("FFTA072"), rep2.format()
    # direct check: strategy substitution on an expressible (factoring)
    # decomposition also fires — only the documented non-factoring flat
    # fallback is tolerated
    ctx = AnalysisContext(
        graph=m.graph,
        reduction_strategies={"fc_big": {
            "strategy": "rs_ar_ag", "degree": 8,
            "tiers": [{"tier": "ici", "group": 4},
                      {"tier": "dcn", "group": 2}]}},
        executed_reductions={"fc_big": "hier_ring"})
    assert len(check_executed_reductions(ctx)) == 1


def test_compile_gate_rejects_divergent_lowering(monkeypatch):
    from flexflow_tpu.analysis import PlanAnalysisError
    from flexflow_tpu.runtime.collectives import GradSyncLowering

    orig = GradSyncLowering.executed_plan

    def dropped(self):
        out = orig(self)
        out.pop(next(iter(out)))
        return out

    monkeypatch.setattr(GradSyncLowering, "executed_plan", dropped)
    with pytest.raises(PlanAnalysisError) as ei:
        _train("explicit", 8, epochs=1)
    assert ei.value.report.by_code("FFTA072")


# -- collective-bench + per-tier refit -------------------------------------

def test_sweep_collectives_rows():
    from flexflow_tpu.obs.collective_bench import sweep_collectives

    cfg = ff.FFConfig()
    cfg.num_devices = 8
    cfg.machine_model_file = SPEC_4x2
    result = sweep_collectives(cfg, [65536, 262144],
                               ["flat", "rs_ar_ag"], warmup=0, repeats=1)
    rows = result["rows"]
    assert result["tiers"] == ["ici", "dcn"]
    kinds = {(r.op, r.strategy, r.tier) for r in rows}
    assert ("allreduce", "flat", "dcn") in kinds
    assert ("allreduce", "rs_ar_ag", "dcn") in kinds
    assert ("psum", "tier_ring", "ici") in kinds
    assert ("psum", "tier_ring", "dcn") in kinds
    assert all(r.measured_us > 0 and r.predicted_us > 0 for r in rows)


def test_fit_collective_coefficients_round_trip():
    from flexflow_tpu.obs.calibration import CollectiveCalibration
    from flexflow_tpu.obs.refit import fit_collective_coefficients

    machine = _make_machine()
    true_scales = {"ici": 0.5, "dcn": 2.0}
    rows = []
    path = machine.tier_path(8)
    for tier, nj in path:
        for b in (1e5, 1e6, 4e6):
            slope = 2.0 * (nj - 1) / nj / machine.tier_bw(tier) * 1e6
            lat = machine.tier_latency(tier)
            rows.append(CollectiveCalibration(
                op="psum", strategy="tier_ring", tier=tier.name,
                bytes=b, participants=nj,
                predicted_us=slope * b + lat,
                measured_us=slope / true_scales[tier.name] * b + lat))
    coeffs = fit_collective_coefficients(rows, machine)
    for name, want in true_scales.items():
        assert coeffs.tier_link_scales[name] == pytest.approx(want,
                                                              rel=0.1)
    # the fitted scales round-trip through the overlay into the machine
    machine2 = _make_machine()
    machine2.apply_overlay(coeffs)
    assert machine2.tier_scales["ici"] == pytest.approx(0.5, rel=0.1)
    assert machine2.tier_scales["dcn"] == pytest.approx(2.0, rel=0.1)


# -- bucketed/async grad-sync lowering (docs/machine.md "Overlap") ---------

def test_bucketed_lowering_parity_and_executed_schedule():
    """A tiny bucket target forces SEVERAL fused buckets; the bucketed
    schedule must be loss-parity with the per-tensor explicit path and
    GSPMD, and the executed bucket assignment must equal the priced
    plan's (the extended FFTA072 contract)."""
    losses_b, m_b = _train("explicit", 8, bucket_bytes=4096)
    losses_p, _ = _train("explicit", 8, bucket_bytes=0)
    losses_g, _ = _train("gspmd", 8)
    lowering = m_b.executor.grad_sync_lowering
    assert lowering is not None
    buckets = lowering.bucket_map()
    assert len(buckets) >= 2, buckets
    planned = {name: e.get("bucket")
               for name, e in m_b._reduction_plan.items()}
    assert lowering.executed_buckets() == {**lowering.executed_buckets(),
                                           **planned}
    for lb, lp, lg in zip(losses_b, losses_p, losses_g):
        assert abs(lb - lp) / max(abs(lp), 1e-8) < 1e-5, (losses_b,
                                                          losses_p)
        assert abs(lb - lg) / max(abs(lg), 1e-8) < 1e-5, (losses_b,
                                                          losses_g)


def test_bucket_zero_and_blocking_disable_bucketing():
    # per-tensor mode and the legacy blocking knob must both produce an
    # un-bucketed plan (every entry bucket-less, the pre-bucketing
    # schedule)
    _, m_p = _train("explicit", 8, bucket_bytes=0, epochs=1)
    assert m_p.executor.grad_sync_lowering.bucket_map() == {}
    assert all(e.get("bucket") is None
               for e in m_p._reduction_plan.values())
    _, m_k = _train("explicit", 8, overlap=False, epochs=1)
    assert m_k.executor.grad_sync_lowering.bucket_map() == {}
    assert m_k._sync_overlap is None


def test_bucket_counter_and_span():
    from flexflow_tpu.obs import enable_tracing, get_tracer
    from flexflow_tpu.obs.registry import REGISTRY
    from flexflow_tpu.runtime.collectives import overlap_bucket_counter

    enable_tracing()
    _, m = _train("explicit", 8, epochs=1, bucket_bytes=4096)
    lowering = m.executor.grad_sync_lowering
    buckets = lowering.bucket_map()
    assert buckets
    c = overlap_bucket_counter()
    total = sum(v for _, v in c.items())
    assert total >= len(buckets)
    spans = get_tracer().events("exec.grad_sync")
    assert spans and spans[0]["args"]["buckets"] == len(buckets)
    bspans = get_tracer().events("exec.grad_sync.bucket")
    assert len(bspans) >= len(buckets)
    assert {s["args"]["bucket"] for s in bspans} >= set(buckets)
    # the predicted overlap split landed on the gauge
    g = REGISTRY.get("ff_grad_sync_overlap_us")
    assert g is not None
    assert g.value(kind="exposed") >= 0.0


def test_ffta072_bucket_schedule_divergence():
    from flexflow_tpu.analysis.passes import (AnalysisContext,
                                              check_executed_reductions)

    _, m = _train("explicit", 8, epochs=1, bucket_bytes=4096)
    rep = m.analyze_plan()
    assert not rep.by_code("FFTA072"), rep.format()
    lowering = m.executor.grad_sync_lowering
    # regroup one tensor into a different bucket: the extended FFTA072
    # check must reject the divergent bucket schedule
    bad = dict(lowering.executed_buckets())
    name = next(n for n, b in bad.items() if b is not None)
    bad[name] = (bad[name] or 0) + 97
    ctx = AnalysisContext(
        graph=m.graph,
        reduction_strategies=m._reduction_plan,
        executed_reductions=lowering.executed_plan(),
        executed_buckets=bad)
    diags = check_executed_reductions(ctx)
    assert diags and all(d.code == "FFTA072" for d in diags), diags
    # matching buckets stay clean
    ctx_ok = AnalysisContext(
        graph=m.graph,
        reduction_strategies=m._reduction_plan,
        executed_reductions=lowering.executed_plan(),
        executed_buckets=lowering.executed_buckets())
    assert not check_executed_reductions(ctx_ok)


def test_compile_gate_rejects_bucket_divergence(monkeypatch):
    from flexflow_tpu.analysis import PlanAnalysisError
    from flexflow_tpu.runtime.collectives import GradSyncLowering

    orig = GradSyncLowering.executed_buckets

    def regrouped(self):
        out = orig(self)
        for k, v in out.items():
            if v is not None:
                out[k] = v + 1
                break
        return out

    monkeypatch.setattr(GradSyncLowering, "executed_buckets", regrouped)
    with pytest.raises(PlanAnalysisError) as ei:
        _train("explicit", 8, epochs=1, bucket_bytes=4096)
    assert ei.value.report.by_code("FFTA072")
