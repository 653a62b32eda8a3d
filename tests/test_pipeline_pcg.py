"""Pipeline parallelism through the PCG: compile(parallel_axes={'stage': S})
routes the repeated-block region through the GPipe kernel, and the Unity
search can choose a 'stage' axis under --enable-pipeline-parallel.

Beyond-reference capability (upstream's OP_PIPELINE enum ffconst.h:159 is
unused there): the pipeline is part of FFModel/compile/search, not a demo
beside them.
"""
import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.core.graph import Graph
from flexflow_tpu.models import TransformerConfig, build_bert_encoder
from flexflow_tpu.parallel.pipeline_plan import (
    find_isomorphic_run,
    find_pipeline_plan,
)

BATCH, SEQ, HID, LAYERS = 8, 16, 32, 4


def _build(axes=None, ndev=1, microbatches=4):
    config = ff.FFConfig()
    config.num_devices = ndev
    config.batch_size = BATCH
    config.pipeline_microbatches = microbatches
    model = ff.FFModel(config)
    tokens = model.create_tensor([BATCH, SEQ], ff.DataType.DT_INT32)
    cfg = TransformerConfig(hidden_size=HID, embedding_size=HID,
                            num_heads=4, num_layers=LAYERS,
                            sequence_length=SEQ, vocab_size=50)
    build_bert_encoder(model, tokens, cfg)
    model.compile(optimizer=ff.SGDOptimizer(model, lr=0.1),
                  loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                  metrics=[], parallel_axes=axes)
    return model


def _data():
    x = np.random.RandomState(0).randint(0, 50, (BATCH, SEQ)).astype(np.int32)
    y = np.random.RandomState(1).randint(0, 2, (BATCH, SEQ, 1)).astype(np.int32)
    return x, y


def test_plan_finds_transformer_body():
    """The run finder recovers one group per encoder layer (period > 1:
    each layer spans two bottleneck segments)."""
    config = ff.FFConfig()
    config.batch_size = BATCH
    model = ff.FFModel(config)
    tokens = model.create_tensor([BATCH, SEQ], ff.DataType.DT_INT32)
    cfg = TransformerConfig(hidden_size=HID, embedding_size=HID,
                            num_heads=4, num_layers=LAYERS,
                            sequence_length=SEQ, vocab_size=50)
    build_bert_encoder(model, tokens, cfg)
    g = Graph(model.ops)
    run_len, run, entries = find_isomorphic_run(g)
    assert run_len == LAYERS
    assert len({len(grp) for grp in run}) == 1  # isomorphic groups
    assert all(tuple(e.dims) == (BATCH, SEQ, HID) for e in entries)
    plan = find_pipeline_plan(g, n_stages=LAYERS)
    assert plan.segs_per_stage == 1
    plan2 = find_pipeline_plan(g, n_stages=LAYERS // 2)
    assert plan2.segs_per_stage == 2


def test_pipeline_fit_steps_per_execution():
    """Chunked fit on a stage mesh: the K-step scan wraps the GPipe scan
    (scan-inside-scan) and trains — loss stays finite and falls."""
    model = _build(axes={"stage": 2}, ndev=2)
    x, y = _data()
    xs = np.tile(x, (4, 1))
    ys = np.tile(y, (4, 1, 1))
    hist = model.fit([xs], ys, epochs=2, steps_per_execution=2)
    assert np.isfinite(hist[-1]["loss"])
    assert hist[-1]["loss"] <= hist[0]["loss"] + 1e-6


def test_plan_loud_on_unpipelineable_graph():
    """No repeated structure -> a loud error naming the constraint."""
    config = ff.FFConfig()
    config.batch_size = 4
    model = ff.FFModel(config)
    t = model.create_tensor([4, 8], ff.DataType.DT_FLOAT)
    t = model.dense(t, 13, name="a")
    t = model.dense(t, 7, name="b")
    model.softmax(t)
    with pytest.raises(ValueError, match="isomorphic"):
        find_pipeline_plan(Graph(model.ops), n_stages=2)


def test_adopt_params_plain_to_plain():
    """adopt_params_from between two sequential compilations: predictions
    become identical; a different-graph source raises loudly."""
    m_a = _build(None, ndev=1)
    m_b = _build(None, ndev=1)
    # different init seeds would be the realistic case; force a difference
    import jax.numpy as jnp

    first = next(n for n in m_b.params if m_b.params[n])
    k0 = next(iter(m_b.params[first]))
    m_b.params[first][k0] = m_b.params[first][k0] + 1.0
    m_b.adopt_params_from(m_a)
    x, y = _data()
    name = m_a.input_ops[0].name
    np.testing.assert_allclose(
        np.asarray(m_a.predict(x)), np.asarray(m_b.predict(x)),
        rtol=1e-6, atol=1e-7)

    config = ff.FFConfig()
    config.batch_size = 4
    other = ff.FFModel(config)
    t = other.create_tensor([4, 8])
    other.softmax(other.dense(t, 3, name="different_head"))
    other.compile(optimizer=ff.SGDOptimizer(other, lr=0.1),
                  loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                  metrics=[])
    with pytest.raises(KeyError, match="no counterpart"):
        m_b.adopt_params_from(other)


def test_pp_matches_sequential_numerics():
    """One fit epoch through a dp=2 x stage=4 mesh matches the sequential
    model when both start from identical weights: GPipe is the same math."""
    m_seq = _build(None, ndev=1)
    m_pp = _build({"data": 2, "stage": 4}, ndev=8)
    plan = m_pp.executor.pipeline_plan
    assert plan is not None and plan.n_stages == 4

    # overwrite the pp model's weights with the sequential model's
    m_pp.adopt_params_from(m_seq)
    # the reverse direction is explicitly unsupported
    with pytest.raises(ValueError, match="sequential source"):
        m_seq.adopt_params_from(m_pp)

    x, y = _data()
    h_seq = m_seq.fit(x, y, epochs=1, verbose=False)
    h_pp = m_pp.fit(x, y, epochs=1, verbose=False)
    assert np.isfinite(h_pp[-1]["loss"])
    np.testing.assert_allclose(h_pp[-1]["loss"], h_seq[-1]["loss"],
                               rtol=2e-2)

    # post-update suffix weights agree (they sit outside the pipeline)
    w_seq = np.asarray(m_seq.params["cls"]["kernel"])
    w_pp = np.asarray(m_pp.params["cls"]["kernel"])
    np.testing.assert_allclose(w_pp, w_seq, atol=2e-2)


def test_pp_pure_stage_mesh():
    """stage-only mesh (no data axis) trains to a finite loss."""
    m = _build({"stage": 4}, ndev=8, microbatches=2)
    x, y = _data()
    h = m.fit(x, y, epochs=1, verbose=False)
    assert np.isfinite(h[-1]["loss"])


def test_pp_truncates_indivisible_run():
    """3 stages on a 4-block body: pipeline 3 blocks, run 1 sequentially."""
    m = _build({"stage": 3}, ndev=8, microbatches=2)
    plan = m.executor.pipeline_plan
    assert plan.n_stages == 3 and len(plan.segments) == 3
    x, y = _data()
    h = m.fit(x, y, epochs=1, verbose=False)
    assert np.isfinite(h[-1]["loss"])


def test_pp_too_many_stages_raises():
    with pytest.raises(ValueError, match="repeats only"):
        _build({"stage": 5}, ndev=8)


def test_pp_weight_accessors():
    """get/set_tensor and get_parameter_by_id reach INTO the stacked
    pipeline tree (reference: ParallelTensor set_tensor/get_tensor work on
    any op's weights regardless of placement)."""
    m = _build({"stage": 4}, ndev=8, microbatches=2)
    # a weight belonging to a stage-2 block
    op = next(o for o in m.graph.topo_order() if o.name == "layer2_ff1")
    w = op.weights[0]
    val = np.asarray(m._get_tensor_value(w))
    got = m.get_parameter_by_id("layer2_ff1", w._weight_spec.name)
    np.testing.assert_array_equal(val, got)
    new = np.full_like(val, 0.25)
    m._set_tensor_value(w, new)
    np.testing.assert_array_equal(
        m.get_parameter_by_id("layer2_ff1", w._weight_spec.name), new)
    # a DIFFERENT stage's copy is untouched
    other = m.get_parameter_by_id("layer1_ff1", w._weight_spec.name)
    assert not np.allclose(other, new)
    x, y = _data()
    h = m.fit(x, y, epochs=1, verbose=False)
    assert np.isfinite(h[-1]["loss"])


def test_pp_checkpoint_roundtrip(tmp_path):
    """Stacked '__pipeline__' params survive save/restore (generic pytree
    flattening) and the restored model trains on."""
    import os

    from flexflow_tpu.runtime.checkpoint import (
        restore_checkpoint,
        save_checkpoint,
    )

    m = _build({"data": 2, "stage": 4}, ndev=8)
    x, y = _data()
    m.fit(x, y, epochs=1, verbose=False)
    p = os.path.join(str(tmp_path), "ckpt.npz")
    save_checkpoint(p, m, step=1)
    m2 = _build({"data": 2, "stage": 4}, ndev=8)
    restore_checkpoint(p, m2)
    k0 = next(iter(m.params["__pipeline__"]))
    for wname, val in m.params["__pipeline__"][k0].items():
        np.testing.assert_array_equal(
            np.asarray(val), np.asarray(m2.params["__pipeline__"][k0][wname]))
    h = m2.fit(x, y, epochs=1, verbose=False)
    assert np.isfinite(h[-1]["loss"])


def test_search_picks_pp_under_memory_pressure():
    """Deep-narrow graph, batch caps dp at 2, TP-indivisible dims: with a
    memory budget that dp-replication busts, the lambda search must buy the
    pipeline's S-way weight sharding (cost model: region memory / pp)."""
    from flexflow_tpu.search.machine_model import make_machine_model
    from flexflow_tpu.search.unity import unity_optimize

    config = ff.FFConfig()
    config.num_devices = 8
    config.batch_size = 4
    config.search_budget = 8
    config.enable_pipeline_parallel = True
    config.pipeline_microbatches = 2
    config.memory_search = True
    model = ff.FFModel(config)
    t = model.create_tensor([4, 97], ff.DataType.DT_FLOAT)
    for i in range(8):  # 97 is prime: no TP divides; batch 4: dp <= 4
        t = model.dense(t, 97, name=f"deep{i}")
    model.softmax(t)
    graph = Graph(model.ops)
    machine = make_machine_model(config, 8)

    # budget below the replicated-weights footprint: only 'stage' sharding
    # of the repeated region can fit
    from flexflow_tpu.search.unity import GraphSearchHelper

    helper = GraphSearchHelper(graph, config, machine)
    full = helper._parallelize(graph, 4, 8)
    pp_cands = helper._pipeline_candidates(graph, 4, 8)
    assert pp_cands, "search produced no pipeline candidates"
    assert any(r.mesh_axes.get("stage", 1) > 1 for r in pp_cands)
    # every pp candidate must report less region memory than replication
    rep_mem = full.memory_bytes
    assert min(r.memory_bytes for r in pp_cands) < rep_mem

    budget = min(r.memory_bytes for r in pp_cands) * 1.5
    best = helper.graph_optimize(4, 8, memory_budget_bytes=budget)
    assert best.mesh_axes.get("stage", 1) > 1, (
        f"memory-aware search did not choose PP: {best.mesh_axes}")


def test_search_pp_compiles_end_to_end():
    """unity_optimize result with a stage axis flows through compile()."""
    config = ff.FFConfig()
    config.num_devices = 8
    config.batch_size = BATCH
    config.search_budget = 4
    config.enable_pipeline_parallel = True
    config.pipeline_microbatches = 4
    model = ff.FFModel(config)
    tokens = model.create_tensor([BATCH, SEQ], ff.DataType.DT_INT32)
    cfg = TransformerConfig(hidden_size=HID, embedding_size=HID,
                            num_heads=4, num_layers=LAYERS,
                            sequence_length=SEQ, vocab_size=50)
    build_bert_encoder(model, tokens, cfg)
    model.compile(optimizer=ff.SGDOptimizer(model, lr=0.1),
                  loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                  metrics=[])
    x, y = _data()
    h = model.fit(x, y, epochs=1, verbose=False)
    assert np.isfinite(h[-1]["loss"])


def test_stage_placement_options_tier_nesting():
    """stage_placement_options (docs/machine.md "Overlap"): multi-tier
    machines offer the stage-OUTER nesting (contiguous per-stage device
    blocks, cut on the pod edge when dp covers whole inner groups);
    flat and one-tier machines keep only the legacy strided nesting so
    their pricing is unchanged bit-for-bit."""
    from flexflow_tpu.parallel.pipeline_plan import stage_placement_options
    from flexflow_tpu.search.machine_model import (CHIP_SPECS,
                                                   HierarchicalMachineModel,
                                                   TierSpec, TpuPodModel)

    chip = CHIP_SPECS["tpu-v5e"]
    hier = HierarchicalMachineModel(
        [TierSpec("ici", 8, 45.0, 2),
         TierSpec("dcn", 2, 3.125, 1, 10.0)], chip)
    opts = stage_placement_options(hier, dp=8, pp=2)
    assert [o["order"] for o in opts] == ["stage_outer", "stage_inner"]
    outer, inner = opts
    assert outer["axes"] == (("stage", 2), ("data", 8))
    assert outer["hop_inner"] == 8 and outer["dp_inner"] == 1
    assert outer["hop_tier"] == "dcn" and outer["cut_on_tier_boundary"]
    assert inner["axes"] == (("data", 8), ("stage", 2))
    assert inner["hop_inner"] == 1 and inner["dp_inner"] == 2
    assert inner["hop_tier"] == "ici" and not inner["cut_on_tier_boundary"]
    # dp=4 covers only half a pod: the cut lands mid-pod
    assert not stage_placement_options(hier, 4, 4)[0]["cut_on_tier_boundary"]
    # flat and one-tier machines: legacy nesting only
    assert [o["order"] for o in stage_placement_options(
        TpuPodModel(16, chip), 8, 2)] == ["stage_inner"]
    one = HierarchicalMachineModel([TierSpec("ici", 16, 45.0, 2)], chip)
    assert [o["order"] for o in stage_placement_options(one, 8, 2)] \
        == ["stage_inner"]


def test_pp_compiles_with_stage_outer_mesh():
    """A stage-OUTERMOST mesh (the tier-aware placement's nesting)
    compiles and trains: make_mesh preserves the axes order, so each
    stage owns a contiguous device block."""
    config = ff.FFConfig()
    config.num_devices = 8
    config.batch_size = BATCH
    # per-microbatch batch must divide over the data axis (BATCH=8,
    # m=2 -> 4 per microbatch over dp=4)
    config.pipeline_microbatches = 2
    model = ff.FFModel(config)
    tokens = model.create_tensor([BATCH, SEQ], ff.DataType.DT_INT32)
    cfg = TransformerConfig(hidden_size=HID, embedding_size=HID,
                            num_heads=4, num_layers=LAYERS,
                            sequence_length=SEQ, vocab_size=50)
    build_bert_encoder(model, tokens, cfg)
    model.compile(optimizer=ff.SGDOptimizer(model, lr=0.1),
                  loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                  metrics=[], parallel_axes={"stage": 2, "data": 4})
    assert tuple(model.mesh.axis_names) == ("stage", "data")
    x, y = _data()
    h = model.fit(x, y, epochs=1, verbose=False)
    assert np.isfinite(h[-1]["loss"])
