"""Hierarchical machine model (docs/machine.md): tier-aware collective
pricing, per-tier reduction synthesis, one-tier degeneracy vs the flat
TpuPodModel, fitted-profile overlay round-trips, the FFTA07x cross-tier
legality family."""
import dataclasses
import json

import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.analysis import analyze_plan
from flexflow_tpu.core.graph import Graph
from flexflow_tpu.obs.refit import FittedCoefficients, FittedProfile, refit
from flexflow_tpu.search.machine_model import (CHIP_SPECS,
                                               HierarchicalMachineModel,
                                               TierSpec, TpuPodModel,
                                               make_machine_model)
from flexflow_tpu.search.simulator import CostModel, OpStrategy, Simulator
from flexflow_tpu.search.unity import export_strategy, unity_optimize

CHIP = CHIP_SPECS["tpu-v5e"]


def multipod(ici=8, pods=2, dcn_gbps=3.125, dcn_latency=10.0):
    """ici-chips-per-pod x pods with a DCN tier ~14x slower than ICI."""
    return HierarchicalMachineModel(
        [TierSpec("ici", ici, CHIP.ici_link_gbps, 2),
         TierSpec("dcn", pods, dcn_gbps, 1, dcn_latency)], CHIP)


def one_tier(n=8):
    return HierarchicalMachineModel(
        [TierSpec("ici", n, CHIP.ici_link_gbps, 2)], CHIP)


def mlp_model(cfg, layers=3, width=512):
    m = ff.FFModel(cfg)
    t = m.create_tensor([cfg.batch_size, width])
    for i in range(layers):
        t = m.dense(t, width, ff.ActiMode.AC_MODE_RELU, name=f"fc{i}")
    m.softmax(m.dense(t, 10, name="head"))
    return m


# -- spec parsing -----------------------------------------------------------

def test_from_json_parses_tiers(tmp_path):
    spec = {"chip": "tpu-v5e",
            "tiers": [{"name": "ici", "degree": 4, "gbps": 45.0},
                      {"name": "dcn", "degree": 2, "gbps": 3.125,
                       "links": 1, "latency_us": 10.0}]}
    p = tmp_path / "m.json"
    p.write_text(json.dumps(spec))
    m = HierarchicalMachineModel.from_json(str(p))
    assert m.num_chips == 8
    assert [t.name for t in m.tiers] == ["ici", "dcn"]
    assert m.tiers[0].links == 2 and m.tiers[1].links == 1
    assert m.tiers[1].latency_us == 10.0


def test_from_json_rejects_bad_specs():
    with pytest.raises(ValueError, match="tiers"):
        HierarchicalMachineModel.from_json({"tiers": []})
    with pytest.raises(ValueError, match="bad tier entry"):
        HierarchicalMachineModel.from_json(
            {"tiers": [{"name": "x", "gbps": 1.0}]})  # no degree
    with pytest.raises(ValueError, match="unique"):
        HierarchicalMachineModel.from_json(
            {"tiers": [{"name": "a", "degree": 2, "gbps": 1.0},
                       {"name": "a", "degree": 2, "gbps": 1.0}]})
    with pytest.raises(ValueError, match="num_chips"):
        HierarchicalMachineModel.from_json(
            {"num_chips": 99,
             "tiers": [{"name": "a", "degree": 2, "gbps": 1.0}]})


def test_make_machine_model_dispatches_on_tiers(tmp_path):
    hier = tmp_path / "hier.json"
    hier.write_text(json.dumps(
        {"tiers": [{"name": "ici", "degree": 8, "gbps": 45.0}]}))
    cfg = ff.FFConfig()
    cfg.machine_model_file = str(hier)
    assert isinstance(make_machine_model(cfg, 8), HierarchicalMachineModel)
    net = tmp_path / "net.json"
    net.write_text(json.dumps({"num_chips": 4, "links": [[0, 1, 45.0]]}))
    cfg.machine_model_file = str(net)
    assert not hasattr(make_machine_model(cfg, 4), "tier_path")


def test_machine_spec_flag_is_an_alias():
    cfg = ff.FFConfig()
    rest = cfg.parse_args(["--machine-spec", "some/spec.json"])
    assert rest == [] and cfg.machine_model_file == "some/spec.json"


# -- tier geometry ----------------------------------------------------------

def test_tier_path_respects_inner_nesting():
    m = multipod()
    assert [(t.name, n) for t, n in m.tier_path(8)] == [("ici", 8)]
    # a degree-2 axis nested OUTSIDE the 8 in-pod devices rides the DCN
    assert [(t.name, n) for t, n in m.tier_path(2, inner=8)] == [("dcn", 2)]
    assert [(t.name, n) for t, n in m.tier_path(16)] == [("ici", 8),
                                                         ("dcn", 2)]
    assert not m.crosses_tier_boundary(8)
    assert m.crosses_tier_boundary(2, inner=8)
    # non-dividing groups round up into the next tier (conservative)
    assert [(t.name, n) for t, n in m.tier_path(12)] == [("ici", 8),
                                                         ("dcn", 2)]


# -- pricing ----------------------------------------------------------------

def test_reduction_strategy_tradeoffs():
    m = multipod()
    big = 64e6
    flat = m.allreduce_time_us(big, 16, strategy="flat")
    rs = m.allreduce_time_us(big, 16, strategy="rs_ar_ag")
    ring = m.allreduce_time_us(big, 16, strategy="hier_ring")
    # big tensors: phase overhead is noise, DCN bytes dominate
    assert rs < ring < flat
    assert m.allreduce_time_us(big, 16) == rs  # auto picks the winner
    # tiny tensors: per-phase latency dominates, the 3-phase rs_ar_ag loses
    tiny = 1e3
    assert (m.allreduce_time_us(tiny, 16, strategy="hier_ring")
            < m.allreduce_time_us(tiny, 16, strategy="rs_ar_ag"))
    # auto never picks flat across a boundary (FFTA070 legality), even
    # where flat would be cheapest
    strat, _, tiers = m.reduction_choice(tiny, 16)
    assert strat in ("rs_ar_ag", "hier_ring")
    assert [d["tier"] for d in tiers] == ["ici", "dcn"]
    # inside one pod the only (and legal) choice is flat
    strat, t, tiers = m.reduction_choice(big, 8)
    assert strat == "flat" and len(tiers) == 1
    assert t == m.allreduce_time_us(big, 8)


def test_unknown_strategy_rejected():
    with pytest.raises(ValueError, match="reduction strategy"):
        multipod().allreduce_time_us(1e6, 16, strategy="donut")


def test_collectives_price_dcn_when_crossed():
    m = multipod()
    b = 8e6
    # the same degree is far cheaper while it stays inside the pod
    assert m.allgather_time_us(b, 2, inner=8) > 5 * m.allgather_time_us(b, 2)
    assert (m.reduce_scatter_time_us(b, 2, inner=8)
            > 5 * m.reduce_scatter_time_us(b, 2))
    assert (m.all_to_all_time_us(b, 2, inner=8)
            > 5 * m.all_to_all_time_us(b, 2))
    # tiered allgather beats the flat-bottleneck ring when spanning both
    flat_ag = (16 - 1) * b / m.tier_bw(m.tiers[1]) * 1e6
    assert m.allgather_time_us(b, 16) < flat_ag
    # a ring hop advances at the slowest link the ring crosses: an
    # in-pod seq ring rotates at ICI speed, a cross-pod one at DCN speed
    assert m.ring_hop_time_us(b, 16) > 5 * m.ring_hop_time_us(b, 8)


def test_dcn_step_bytes_by_strategy():
    m = multipod()
    b = 256e3
    assert m.dcn_step_bytes(b, 8) == 0.0  # in-pod: never leaves ICI
    flat = m.dcn_step_bytes(b, 16, strategy="flat")
    rs = m.dcn_step_bytes(b, 16, strategy="rs_ar_ag")
    assert flat == pytest.approx(2 * (1 / 2) * b)
    assert rs == pytest.approx(flat / 8)  # only the 1/8 shard crosses
    # a group living entirely ON the dcn tier (dp=2, one member per
    # pod) rings its full bytes there — not zero
    assert m.dcn_step_bytes(b, 2, inner=8) == pytest.approx(b)


# -- one-tier degeneracy (satellite: bit-for-bit vs TpuPodModel) ------------

@pytest.mark.parametrize("n", [2, 3, 4, 8])
@pytest.mark.parametrize("bytes_", [1e3, 1e6, 1e9])
def test_one_tier_prices_identical_to_flat_pod(n, bytes_):
    one, pod = one_tier(8), TpuPodModel(8, CHIP)
    assert one.allreduce_time_us(bytes_, n) == pod.allreduce_time_us(bytes_, n)
    assert one.allgather_time_us(bytes_, n) == pod.allgather_time_us(bytes_, n)
    assert (one.reduce_scatter_time_us(bytes_, n)
            == pod.reduce_scatter_time_us(bytes_, n))
    assert (one.all_to_all_time_us(bytes_, n)
            == pod.all_to_all_time_us(bytes_, n))
    assert one.p2p_time_us(bytes_) == pod.p2p_time_us(bytes_)
    assert (one.ring_hop_time_us(bytes_, n)
            == pod.p2p_single_path_time_us(bytes_))
    assert one.compute_time_us(1e9, bytes_) == pod.compute_time_us(1e9, bytes_)
    assert one.memory_budget_bytes() == pod.memory_budget_bytes()


def test_one_tier_degeneracy_survives_an_overlay():
    coeffs = FittedCoefficients(
        compute_scale={"bf16": 0.5, "f32": 0.7}, hbm_scale=0.9,
        link_bw_scale=0.25, dispatch_latency_us=2.5,
        collective_latency_us=3.0, step_scale=1.2)
    one, pod = one_tier(8), TpuPodModel(8, CHIP)
    one.apply_overlay(coeffs)
    pod.apply_overlay(coeffs)
    for n in (2, 4, 8):
        assert one.allreduce_time_us(1e6, n) == pod.allreduce_time_us(1e6, n)
        assert one.allgather_time_us(1e6, n) == pod.allgather_time_us(1e6, n)
    assert one.p2p_time_us(1e6) == pod.p2p_time_us(1e6)
    assert one.compute_time_us(1e9, 1e6, 2) == pod.compute_time_us(1e9, 1e6, 2)


def test_one_tier_searched_plan_matches_flat_pod_bit_for_bit():
    def search(machine):
        cfg = ff.FFConfig()
        cfg.num_devices = 8
        cfg.batch_size = 32
        cfg.search_budget = 6
        cfg.use_native_search = False
        model = mlp_model(cfg)
        return unity_optimize(Graph(model.ops), cfg, machine, 32, 8)

    r_one = search(one_tier(8))
    r_pod = search(TpuPodModel(8, CHIP))
    assert r_one.cost_us == r_pod.cost_us
    assert r_one.memory_bytes == r_pod.memory_bytes
    assert r_one.mesh_axes == r_pod.mesh_axes
    by_name_one = {s for s in r_one.strategies.values()}
    by_name_pod = {s for s in r_pod.strategies.values()}
    assert by_name_one == by_name_pod
    # one-tier: every synthesized reduction is flat, single-tier
    assert all(v["strategy"] == "flat" and len(v["tiers"]) == 1
               for v in r_one.reduction_strategies.values())
    assert r_pod.reduction_strategies == {}


# -- overlay: per-tier fitted scales ----------------------------------------

def test_apply_overlay_per_tier_scales_with_global_fallback():
    m = multipod()
    base_ici = m.allreduce_time_us(1e6, 8)
    base_dcn = m.allreduce_time_us(1e6, 2, inner=8)
    coeffs = FittedCoefficients(link_bw_scale=0.5,
                                tier_link_scales={"dcn": 0.25})
    m.apply_overlay(coeffs)
    # dcn keyed explicitly; ici falls back to the global link scale
    assert m.tier_scales == {"ici": 0.5, "dcn": 0.25}
    lat = m.tier_latency(m.tiers[0])
    assert (m.allreduce_time_us(1e6, 8) - lat
            == pytest.approx((base_ici - lat) / 0.5))
    lat_d = m.tier_latency(m.tiers[1])
    assert (m.allreduce_time_us(1e6, 2, inner=8) - lat_d
            == pytest.approx((base_dcn - lat_d) / 0.25))


def test_fitted_profile_round_trips_tier_scales(tmp_path):
    coeffs = FittedCoefficients(tier_link_scales={"ici": 0.8, "dcn": 0.1})
    prof = FittedProfile(chip="tpu-v5e", backend="cpu", coefficients=coeffs)
    path = str(tmp_path / "prof.json")
    prof.save(path)
    loaded = FittedProfile.load(path, expect_chip="tpu-v5e",
                                expect_backend="cpu")
    assert loaded.coefficients.tier_link_scales == {"ici": 0.8, "dcn": 0.1}


def test_old_profiles_without_tier_scales_still_load(tmp_path):
    prof = FittedProfile(chip="tpu-v5e", backend="cpu",
                         coefficients=FittedCoefficients())
    d = prof.to_dict()
    del d["coefficients"]["tier_link_scales"]  # pre-PR-10 profile format
    path = tmp_path / "old.json"
    path.write_text(json.dumps(d))
    loaded = FittedProfile.load(str(path), expect_chip="tpu-v5e",
                                expect_backend="cpu")
    assert loaded.coefficients.tier_link_scales == {}
    multipod().apply_overlay(loaded.coefficients)  # applies cleanly


# -- simulator: degrees price against the tiers they cross ------------------

def _weighted_op(cfg):
    model = mlp_model(cfg, layers=1, width=1024)
    graph = Graph(model.ops)
    op = next(o for o in graph.ops.values() if o.name == "fc0")
    return graph, op


def test_grad_sync_prices_the_tiers_the_dp_axis_crosses():
    cfg = ff.FFConfig()
    cfg.num_devices = 16
    cfg.batch_size = 64
    cost = CostModel(multipod(), cfg)
    _, op = _weighted_op(cfg)
    s = OpStrategy(dp=2)
    inside = cost.grad_sync_time_us(op, s)  # 2 adjacent chips: ICI
    # the SAME op strategy under a tp=8 mesh: its dp groups stride by 8,
    # i.e. one member per pod — the sync rides the DCN and gets pricier
    # even though the bytes are identical (the stride is a property of
    # the realized MESH, not of this op's own degrees)
    cost.set_mesh_degrees(tp=8)
    outside = cost.grad_sync_time_us(op, s)
    assert outside > inside
    # and an op that itself tp-shards syncs 1/8 the bytes, still across
    # the DCN: cheaper than the replicated op's cross-pod sync
    sharded = cost.grad_sync_time_us(op, OpStrategy(dp=2, tp=8))
    assert inside < sharded < outside


def test_reduction_mode_flat_reprices_higher():
    cfg = ff.FFConfig()
    cfg.num_devices = 16
    cfg.batch_size = 64
    graph, _ = _weighted_op(cfg)
    strategies = {g: OpStrategy(dp=16) for g in graph.ops}
    auto = Simulator(multipod(), cfg)
    flat = Simulator(multipod(), cfg)
    flat.cost.reduction_mode = "flat"
    assert auto.simulate(graph, strategies) < flat.simulate(graph,
                                                            strategies)


def test_reduction_plan_records_cross_tier_choices():
    cfg = ff.FFConfig()
    cfg.num_devices = 16
    cfg.batch_size = 64
    graph, _ = _weighted_op(cfg)
    strategies = {g: OpStrategy(dp=16) for g in graph.ops}
    plan = Simulator(multipod(), cfg).cost.reduction_plan(graph, strategies)
    assert plan, "weighted dp-synced ops must appear in the plan"
    for rec in plan.values():
        assert rec["strategy"] in ("rs_ar_ag", "hier_ring")
        assert [t["tier"] for t in rec["tiers"]] == ["ici", "dcn"]
        assert rec["degree"] == 16 and rec["time_us"] > 0
    # flat machines carry no plan
    assert Simulator(TpuPodModel(16, CHIP), cfg).cost.reduction_plan(
        graph, strategies) == {}


def test_export_strategy_serializes_the_tier_decomposition(tmp_path):
    cfg = ff.FFConfig()
    cfg.num_devices = 16
    # large batch: per-chip compute outweighs the sync cost, so the
    # search picks a dp plan whose syncs the export must carry
    cfg.batch_size = 4096
    cfg.search_budget = 4
    cfg.use_native_search = False
    model = mlp_model(cfg, layers=2, width=1024)
    graph = Graph(model.ops)
    result = unity_optimize(graph, cfg, multipod(), cfg.batch_size, 16)
    path = str(tmp_path / "strategy.json")
    export_strategy(result, graph, path)
    data = json.loads(open(path).read())
    assert "reductions" in data
    assert set(data["reductions"]) <= set(data["ops"])
    assert all(r["strategy"] in ("flat", "rs_ar_ag", "hier_ring")
               and r["tiers"]
               for r in data["reductions"].values())


# -- FFTA07x ----------------------------------------------------------------

def _analyze(graph, strategies, machine, cfg, reductions, axes):
    return analyze_plan(graph, strategies=strategies, machine=machine,
                        config=cfg, batch_size=cfg.batch_size,
                        n_devices=16, mesh_axes=axes,
                        reduction_strategies=reductions, passes=("tiers",))


def test_ffta070_flat_sync_across_boundary():
    cfg = ff.FFConfig()
    cfg.num_devices = 16
    cfg.batch_size = 64
    graph, _ = _weighted_op(cfg)
    strategies = {g: OpStrategy(dp=16) for g in graph.ops}
    # a plan that pins NO decomposition (e.g. searched under a flat
    # machine model) is flat across the boundary: error
    rep = _analyze(graph, strategies, multipod(), cfg, {}, {"data": 16})
    assert rep.by_code("FFTA070") and not rep.ok
    # the machine's own synthesized decomposition passes
    plan = Simulator(multipod(), cfg).cost.reduction_plan(graph, strategies)
    rep2 = _analyze(graph, strategies, multipod(), cfg, plan, {"data": 16})
    assert not rep2.by_code("FFTA070") and not rep2.errors()
    # reductions=None means compile() will synthesize: also clean
    rep3 = _analyze(graph, strategies, multipod(), cfg, None, {"data": 16})
    assert not rep3.by_code("FFTA070") and not rep3.errors()
    # in-pod syncs never trigger the pass
    rep4 = _analyze(graph, {g: OpStrategy(dp=8) for g in graph.ops},
                    multipod(), cfg, {}, {"data": 8})
    assert not rep4.diagnostics


def test_ffta071_warns_on_heavy_dcn_traffic():
    cfg = ff.FFConfig()
    cfg.num_devices = 16
    cfg.batch_size = 64
    # 12288^2 f32 = 604 MB: even the rs_ar_ag shard (1/8) crossing the
    # DCN is ~75 MB, above the 64 MB per-step warning threshold
    model = mlp_model(cfg, layers=1, width=12288)
    graph = Graph(model.ops)
    strategies = {g: OpStrategy(dp=16) for g in graph.ops}
    rep = _analyze(graph, strategies, multipod(), cfg, None, {"data": 16})
    warns = rep.by_code("FFTA071")
    assert warns and not rep.errors()  # heavy but legal: warning only
    assert any("tier" in d.message for d in warns)
    # dp=2 one-member-per-pod (tp=8 mesh): the sync group lives ON the
    # dcn tier — flat is its only legal shape, but the full-bytes ring
    # across the DCN still draws the traffic warning (no FFTA070)
    strat2 = {g: OpStrategy(dp=2, tp=8) if graph.ops[g].name == "fc0"
              else OpStrategy(dp=2) for g in graph.ops}
    rep2 = _analyze(graph, strat2, multipod(), cfg, None,
                    {"data": 2, "model": 8})
    assert rep2.by_code("FFTA071") and not rep2.by_code("FFTA070")


def test_flat_machines_skip_the_tier_pass():
    cfg = ff.FFConfig()
    cfg.num_devices = 16
    cfg.batch_size = 64
    graph, _ = _weighted_op(cfg)
    rep = _analyze(graph, {g: OpStrategy(dp=16) for g in graph.ops},
                   TpuPodModel(16, CHIP), cfg, {}, {"data": 16})
    assert not rep.diagnostics


# -- compile wiring ---------------------------------------------------------

def test_compile_synthesizes_and_threads_the_reduction_plan(tmp_path):
    spec = tmp_path / "m.json"
    spec.write_text(json.dumps(
        {"tiers": [{"name": "ici", "degree": 4, "gbps": 45.0},
                   {"name": "dcn", "degree": 2, "gbps": 3.125, "links": 1,
                    "latency_us": 10.0}]}))
    cfg = ff.FFConfig()
    cfg.num_devices = 8
    cfg.batch_size = 32
    cfg.machine_model_file = str(spec)
    model = mlp_model(cfg, layers=2, width=64)
    model.compile(optimizer=ff.SGDOptimizer(model, lr=0.01),
                  loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                  metrics=[], parallel_axes={"data": 8})
    assert model._reduction_plan, "hierarchical compile must synthesize"
    assert model.executor.reduction_plan == model._reduction_plan
    for rec in model._reduction_plan.values():
        assert rec["strategy"] in ("rs_ar_ag", "hier_ring")
    # and the compile-time FFTA07x gate saw it (no errors raised) while
    # a fresh analysis run agrees
    rep = model.analyze_plan(passes=("tiers",))
    assert not rep.errors()
    # end-to-end: one training step executes on the 8-device mesh
    x = np.random.RandomState(0).randn(32, 64).astype(np.float32)
    y = np.zeros((32, 1), dtype=np.int32)
    hist = model.fit([x], y, batch_size=32, epochs=1)
    assert np.isfinite(hist[0]["loss"])


# -- per-tier refit (satellite) ---------------------------------------------

def test_refit_fits_per_tier_scales(tmp_path):
    spec = tmp_path / "m.json"
    spec.write_text(json.dumps(
        {"tiers": [{"name": "ici", "degree": 4, "gbps": 45.0},
                   {"name": "dcn", "degree": 2, "gbps": 3.125, "links": 1,
                    "latency_us": 10.0}]}))
    cfg = ff.FFConfig()
    cfg.num_devices = 8
    cfg.batch_size = 32
    cfg.machine_model_file = str(spec)
    model = mlp_model(cfg, layers=2, width=1024)
    model.compile(optimizer=ff.SGDOptimizer(model, lr=0.01),
                  loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                  metrics=[], parallel_axes={"data": 8})
    model._op_strategies = {g: OpStrategy(dp=8) for g in model.graph.ops}
    machine = make_machine_model(cfg, 8)
    predicted = Simulator(machine, cfg).simulate(model.graph,
                                                 model._op_strategies)
    profile, history = refit(model, measured_step_us=predicted * 4.0,
                             op_rows=[], rounds=3)
    scales = profile.coefficients.tier_link_scales
    # the dp=8 sync crosses both tiers: both get a keyed scale < 1
    assert set(scales) == {"ici", "dcn"}
    assert all(0 < v < 1.0 for v in scales.values()), scales
    # the keyed profile round-trips and applies to a fresh machine
    path = str(tmp_path / "prof.json")
    profile.save(path)
    m2 = make_machine_model(
        dataclasses.replace(cfg, fitted_profile_file=path), 8)
    assert m2.tier_scales["dcn"] == pytest.approx(scales["dcn"])


def test_refit_on_flat_machine_keeps_single_scale():
    cfg = ff.FFConfig()
    cfg.num_devices = 8
    cfg.batch_size = 32
    cfg.machine_model_version = 1  # flat TpuPodModel
    model = mlp_model(cfg, layers=2, width=1024)
    model.compile(optimizer=ff.SGDOptimizer(model, lr=0.01),
                  loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                  metrics=[], parallel_axes={"data": 8})
    model._op_strategies = {g: OpStrategy(dp=8) for g in model.graph.ops}
    machine = make_machine_model(cfg, 8)
    predicted = Simulator(machine, cfg).simulate(model.graph,
                                                 model._op_strategies)
    profile, _ = refit(model, measured_step_us=predicted * 4.0,
                       op_rows=[], rounds=3)
    assert profile.coefficients.tier_link_scales == {}
    assert profile.coefficients.link_bw_scale != 1.0


# -- elastic shrink keeps the hierarchy when whole pods die -----------------

def test_shrink_topology_spec_preserves_tiers_on_whole_pod_loss():
    from flexflow_tpu.elastic.coordinator import shrink_topology_spec

    spec = {"chip": "tpu-v5e", "num_chips": 16,
            "tiers": [{"name": "ici", "degree": 8, "gbps": 45.0},
                      {"name": "dcn", "degree": 2, "gbps": 3.125,
                       "links": 1}]}
    # pod 1 (positions 8..15) drops off the DCN: hierarchy survives
    out = shrink_topology_spec(spec, list(range(8, 16)))
    assert out["num_chips"] == 8
    assert [t["degree"] for t in out["tiers"]] == [8, 1]
    m = HierarchicalMachineModel.from_json(out)
    assert m.num_chips == 8 and not m.crosses_tier_boundary(8)
    # a partial-pod loss cannot keep the uniform hierarchy: flat ring
    # fallback over the survivors at the innermost tier's bandwidth
    out2 = shrink_topology_spec(spec, [3])
    assert "tiers" not in out2 and out2["num_chips"] == 15
    assert all(g == 45.0 for _, _, g in out2["links"])


# -- tier-aware pipeline placement + overlap (docs/machine.md "Overlap") ---

def _transformer_graph(cfg, layers=8):
    from flexflow_tpu.models import TransformerConfig, build_bert_encoder

    m = ff.FFModel(cfg)
    tokens = m.create_tensor([cfg.batch_size, 64], ff.DataType.DT_INT32)
    c = TransformerConfig(hidden_size=256, embedding_size=256,
                          num_heads=4, num_layers=layers,
                          sequence_length=64, vocab_size=1000)
    build_bert_encoder(m, tokens, c)
    return Graph(m.ops)


def _pp_config(n=16, batch=64):
    cfg = ff.FFConfig()
    cfg.num_devices = n
    cfg.batch_size = batch
    cfg.search_budget = 4
    cfg.enable_pipeline_parallel = True
    cfg.pipeline_microbatches = 4
    cfg.use_native_search = False
    return cfg


def test_pipeline_candidate_places_stage_cut_on_pod_boundary():
    """On the 2-pod x 8-chip spec the best pipeline candidate must nest
    the stage axis OUTERMOST with dp covering a whole pod: the stage
    cut lands on the pod edge, DCN carries only the inter-stage
    activation hop, and each stage's dp weight syncs stay on ICI."""
    from flexflow_tpu.search.unity import GraphSearchHelper

    cfg = _pp_config()
    graph = _transformer_graph(cfg)
    machine = multipod(ici=8, pods=2)
    helper = GraphSearchHelper(graph, cfg, machine)
    cands = helper._pipeline_candidates(graph, cfg.batch_size, 16)
    assert cands
    best = min(cands, key=lambda r: r.cost_us)
    pl = best.pipeline_placement
    assert best.mesh_axes == {"stage": 2, "data": 8}, best.log
    assert list(best.mesh_axes)[0] == "stage"  # outermost: pod blocks
    assert pl["order"] == "stage_outer"
    assert pl["cut_on_tier_boundary"], pl
    assert pl["hop_tier"] == "dcn", pl
    # the same (dp, pp) under the legacy strided nesting must cost more:
    # its dp sync groups stride across the DCN
    legacy = [r for r in cands
              if r.mesh_axes.get("stage") == 2
              and r.pipeline_placement["order"] == "stage_inner"]
    assert legacy and legacy[0].cost_us > best.cost_us
    assert legacy[0].pipeline_placement["sync_us"] > pl["sync_us"]


def test_pipeline_stage_hop_priced_on_dcn_tier_not_p2p():
    """The priced stage-boundary transfer of a pod-aligned candidate
    uses the DCN tier via tier_path — not the innermost p2p term the
    flat pricing used."""
    from flexflow_tpu.search.unity import GraphSearchHelper

    cfg = _pp_config()
    graph = _transformer_graph(cfg)
    machine = multipod(ici=8, pods=2)
    helper = GraphSearchHelper(graph, cfg, machine)
    cands = helper._pipeline_candidates(graph, cfg.batch_size, 16)
    best = min(cands, key=lambda r: r.cost_us)
    m = cfg.pipeline_microbatches
    # hop bytes: per-microbatch per-dp-shard activation (seq x hidden,
    # bf16 under the default mixed precision)
    hop_bytes = (cfg.batch_size // m // 8) * 64 * 256 * 2
    want = machine.ring_hop_time_us(hop_bytes, 2, inner=8)
    assert best.pipeline_placement["hop_us"] == pytest.approx(want)
    # DCN-priced: strictly slower than the innermost-tier p2p price
    assert want > machine.p2p_time_us(hop_bytes)


def test_one_tier_pipeline_candidates_match_flat_pod_bit_for_bit():
    from flexflow_tpu.search.unity import GraphSearchHelper

    cfg = _pp_config()
    graph = _transformer_graph(cfg)
    h_one = GraphSearchHelper(graph, cfg, one_tier(16))
    h_flat = GraphSearchHelper(graph, cfg, TpuPodModel(16, CHIP))
    c_one = h_one._pipeline_candidates(graph, cfg.batch_size, 16)
    c_flat = h_flat._pipeline_candidates(graph, cfg.batch_size, 16)
    assert [r.cost_us for r in c_one] == [r.cost_us for r in c_flat]
    assert [r.mesh_axes for r in c_one] == [r.mesh_axes for r in c_flat]
    # one-tier machines keep the legacy nesting only
    assert all(r.pipeline_placement["order"] == "stage_inner"
               for r in c_one)


def test_search_result_reports_overlap_split():
    """The searched multipod plan carries the overlapped/exposed
    grad-sync split; the legacy blocking knob zeroes the overlap term
    (satellite: docs/machine.md "Overlap")."""
    cfg = ff.FFConfig()
    cfg.num_devices = 16
    cfg.batch_size = 512
    cfg.search_budget = 4
    cfg.use_native_search = False
    m = mlp_model(cfg, layers=3, width=512)
    graph = Graph(m.ops)
    res = unity_optimize(graph, cfg, multipod(ici=8, pods=2), 512, 16)
    assert res.exposed_sync_us is not None
    assert res.overlapped_sync_us is not None
    assert res.exposed_sync_us >= 0 and res.overlapped_sync_us >= 0
    cfg2 = ff.FFConfig()
    cfg2.num_devices = 16
    cfg2.batch_size = 512
    cfg2.search_budget = 4
    cfg2.use_native_search = False
    cfg2.search_overlap_backward_update = False
    m2 = mlp_model(cfg2, layers=3, width=512)
    res2 = unity_optimize(Graph(m2.ops), cfg2, multipod(ici=8, pods=2),
                          512, 16)
    assert res2.overlapped_sync_us == 0.0
    assert res2.sync_buckets == 0


def test_reduction_plan_carries_bucket_schedule():
    """Bucketed entries record the priced schedule: bucket mates share
    one strategy and bucket totals; blocking/per-tensor modes stay
    bucket-less (the pre-bucketing plan format)."""
    cfg = ff.FFConfig()
    cfg.batch_size = 64
    cfg.grad_bucket_bytes = 600 * 1024  # several buckets at 512-width
    m = mlp_model(cfg, layers=4, width=512)
    graph = Graph(m.ops)
    strategies = {op.guid: OpStrategy(dp=16) for op in m.ops}
    cm = CostModel(multipod(ici=8, pods=2), cfg)
    plan = cm.reduction_plan(graph, strategies)
    buckets = {}
    for name, e in plan.items():
        assert e["bucket"] is not None
        buckets.setdefault(e["bucket"], []).append(e)
    assert len(buckets) >= 2, plan
    for entries in buckets.values():
        assert len({e["strategy"] for e in entries}) == 1
        assert len({e["bucket_bytes"] for e in entries}) == 1
        got = sum(e["bytes"] for e in entries)
        assert got == pytest.approx(entries[0]["bucket_bytes"])
        # per-op time is the byte share of the bucket's one collective
        assert sum(e["time_us"] for e in entries) == pytest.approx(
            entries[0]["bucket_time_us"])
    cfg.search_overlap_backward_update = False
    plan_blk = CostModel(multipod(ici=8, pods=2), cfg).reduction_plan(
        graph, strategies)
    assert all("bucket" not in e for e in plan_blk.values())
    cfg.search_overlap_backward_update = True
    cfg.grad_bucket_bytes = 0
    plan_pt = CostModel(multipod(ici=8, pods=2), cfg).reduction_plan(
        graph, strategies)
    assert all("bucket" not in e for e in plan_pt.values())


def test_pipeline_placement_stage_count_differs_from_pod_count():
    """examples/machines/multipod_4x4.json (4 pods x 4 chips): stage
    counts that do NOT equal the pod count still cut on pod edges when
    dp covers whole pods — pp=2 puts two pods in each stage, pp=4 one —
    while a half-pod dp lands mid-pod."""
    import os

    from flexflow_tpu.parallel.pipeline_plan import stage_placement_options

    spec = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "examples", "machines",
        "multipod_4x4.json")
    cfg = ff.FFConfig()
    cfg.machine_model_file = spec
    machine = make_machine_model(cfg, 16)
    assert hasattr(machine, "tier_path")
    assert [t.degree for t in machine.tiers] == [4, 4]
    outer2 = stage_placement_options(machine, dp=8, pp=2)[0]
    assert outer2["cut_on_tier_boundary"] and outer2["hop_tier"] == "dcn"
    outer4 = stage_placement_options(machine, dp=4, pp=4)[0]
    assert outer4["cut_on_tier_boundary"] and outer4["hop_tier"] == "dcn"
    outer8 = stage_placement_options(machine, dp=2, pp=8)[0]
    assert not outer8["cut_on_tier_boundary"]


# -- expert-parallel all_to_all tiering (ISSUE 16) --------------------------

def _moe_experts_op(n=8, batch=64, F=16, k=2, H=24):
    cfg = ff.FFConfig()
    cfg.batch_size = batch
    m = ff.FFModel(cfg)
    inp = m.create_tensor([batch, F])
    m.moe(inp, n, k, H, alpha=float(n), fused=True, name="moe")
    graph = Graph(m.ops)
    op = next(o for o in graph.ops.values()
              if o.op_type.value == "experts")
    return graph, op, cfg


def test_expert_a2a_pod_resident_never_prices_dcn():
    """An ep group that fits the innermost tier (ep=8 on an 8-chip pod,
    inner stride 1) routes its all_to_all entirely over ICI: its price is
    a single-tier tier_path and does NOT move when the DCN tier is made
    100x slower — while the cross-pod dp grad sync of the same plan
    does."""
    _, op, cfg = _moe_experts_op(n=8)
    s = OpStrategy(dp=2, ep=8)

    def price(dcn_scale):
        machine = multipod()  # fresh: tier scales and memos reset
        machine.tier_scales["dcn"] = dcn_scale
        sim = Simulator(machine, cfg)
        sim.cost.set_mesh_degrees(tp=1, sp=1, ep=8, ap=1)
        return (sim.cost.ep_collective_time_us(op, s),
                sim.cost.grad_sync_time_us(op, s))

    a2a_fast, sync_fast = price(1.0)
    a2a_slow, sync_slow = price(0.01)
    assert a2a_fast > 0
    assert a2a_slow == pytest.approx(a2a_fast)  # ICI-only: DCN-invariant
    assert sync_slow > sync_fast  # dp=2 strided across the pods pays DCN

    machine = multipod()
    path = machine.tier_path(8, 1)
    assert [t.name for t, _ in path] == ["ici"]


def test_expert_a2a_crossing_pods_prices_the_dcn_tier():
    """The SAME ep degree with a stride that pushes the group across the
    pod boundary (an sp axis nested inside ep) spans both tiers: the
    all_to_all price jumps and now scales with the DCN link speed —
    the cost signal behind the FFTA085 pod-residency prune."""
    _, op, cfg = _moe_experts_op(n=8, batch=64)

    def price(sp_inner, dcn_scale=1.0):
        machine = multipod()
        machine.tier_scales["dcn"] = dcn_scale
        sim = Simulator(machine, cfg)
        sim.cost.set_mesh_degrees(tp=1, sp=sp_inner, ep=8, ap=1)
        s = OpStrategy(dp=16 // (8 * sp_inner) if sp_inner == 1 else 1,
                       ep=8, sp=sp_inner)
        return sim.cost.ep_collective_time_us(op, s)

    resident = price(1)
    crossing = price(2)
    assert crossing > resident
    assert price(2, dcn_scale=0.5) > crossing  # rides the DCN link

    machine = multipod()
    path = machine.tier_path(8, 2)
    assert [t.name for t, _ in path] == ["ici", "dcn"]
