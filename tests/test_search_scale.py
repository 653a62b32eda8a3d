"""Search scalability soak: a reference-scale graph —
BERT-24, 170+ ops — searched at 256 devices with every axis enabled must
finish in bounded wall-clock. The reference's memoized DP exists precisely
for this regime (graph.cc:1586); here the budget pyramid is: memoized
segment DP for every mesh factorization, full-graph event simulation once
per factorization, and the expensive cross-segment refinement only for the
top-K seeded candidates (config.refine_top_k).

Local timing ~40s; the bound leaves headroom for slower CI machines.
Scaling datapoint (not asserted): BERT-48, 340 ops, at 512 devices with
every axis + the memory-aware lambda search finishes in ~194s.
"""
import time

import numpy as np

import flexflow_tpu as ff
from flexflow_tpu.core.graph import Graph
from flexflow_tpu.models import TransformerConfig, build_bert_encoder
from flexflow_tpu.search.machine_model import make_machine_model
from flexflow_tpu.search.unity import unity_optimize

WALL_CLOCK_BOUND_S = 240.0


def _bert24_graph():
    config = ff.FFConfig()
    config.num_devices = 256
    config.batch_size = 1024
    config.search_budget = 50
    config.measure_op_costs = False
    config.enable_sequence_parallel = True
    config.enable_pipeline_parallel = True
    config.memory_search = True
    config.memory_budget_mb = 8 * 1024.0
    model = ff.FFModel(config)
    tokens = model.create_tensor([1024, 128], ff.DataType.DT_INT32)
    cfg = TransformerConfig(hidden_size=1024, embedding_size=1024,
                            num_heads=16, num_layers=24,
                            sequence_length=128, vocab_size=30522)
    build_bert_encoder(model, tokens, cfg)
    return Graph(model.ops), config


def test_bert24_search_at_256_devices_bounded():
    graph, config = _bert24_graph()
    assert len(graph.ops) >= 128, "soak graph must be reference-scale"
    machine = make_machine_model(config, 256)
    t0 = time.perf_counter()
    res = unity_optimize(graph, config, machine, 1024, 256)
    dt = time.perf_counter() - t0
    assert dt < WALL_CLOCK_BOUND_S, (
        f"search took {dt:.0f}s (> {WALL_CLOCK_BOUND_S:.0f}s) on a "
        f"{len(graph.ops)}-op graph at 256 devices")
    # the result must be a real full coverage strategy set
    assert set(res.strategies) == set(graph.ops)
    assert res.mesh_axes and np.prod(list(res.mesh_axes.values())) <= 256
    assert np.isfinite(res.cost_us) and res.cost_us > 0
    # memory-aware: the chosen strategy respects the budget when feasible
    assert res.memory_bytes <= config.memory_budget_mb * 1e6 * 1.05


def test_simulate_memoization_consistent():
    """The memoized cost path returns the same numbers as a fresh
    simulator (guards the caches added for the soak)."""
    from flexflow_tpu.search.simulator import OpStrategy, Simulator

    config = ff.FFConfig()
    config.batch_size = 64
    config.measure_op_costs = False
    model = ff.FFModel(config)
    t = model.create_tensor([64, 32], ff.DataType.DT_FLOAT)
    h = model.dense(t, 64, ff.ActiMode.AC_MODE_RELU)
    model.softmax(model.dense(h, 8))
    g = Graph(model.ops)
    machine = make_machine_model(config, 8)
    strategies = {guid: OpStrategy(dp=4, tp=2) for guid in g.ops}

    sim = Simulator(machine, config)
    first = sim.simulate(g, strategies)
    again = sim.simulate(g, strategies)       # memoized path
    fresh = Simulator(machine, config).simulate(g, strategies)
    assert first == again == fresh
