"""Loaded substitution rules as EXECUTABLE GraphXfer rewrites.

The rule-file loader must instantiate real source→target
rewrites (reference: substitution_loader.h:94-187 → GraphXfer::create_xfers,
substitution.h:119-121), not just a TP-degree menu.
"""

import numpy as np
import pytest

import flexflow_tpu as ff
from flexflow_tpu.core.graph import Graph
from flexflow_tpu.ffconst import CompMode, OpType
from flexflow_tpu.runtime.executor import Executor
from flexflow_tpu.search.graph_xfer import GraphXfer, xfers_from_rules
from flexflow_tpu.search.substitution import SEARCH_RULES
from flexflow_tpu.search.substitution_loader import load_substitution_file

from tests.test_substitution_loader import VENDORED_RULES  # noqa: E402

RULES_PATH = "substitutions/tp_rules.json"


def _linear_model():
    config = ff.FFConfig()
    config.batch_size = 4
    model = ff.FFModel(config)
    t = model.create_tensor([4, 6], ff.DataType.DT_FLOAT)
    out = model.dense(t, 8, name="lin")
    return model, config


def test_loaded_rules_build_supported_xfers():
    rules = load_substitution_file(RULES_PATH)
    xfers = xfers_from_rules(rules)
    assert xfers, "no loaded rule produced an executable xfer"
    assert any("partition_linear_combine" in n for n in xfers)


def test_xfer_rewrites_graph_handwritten_rules_do_not_cover():
    """A bare LINEAR: no hand-written trade-off rule matches it, but the
    loaded replicate-linear-combine rule does — and its application inserts
    real parallel ops."""
    model, _ = _linear_model()
    g = Graph(model.ops)
    # hand-written trade-off rules: nothing to do on this graph
    for fn in SEARCH_RULES.values():
        assert fn(g) == []
    rules = load_substitution_file(RULES_PATH)
    xfers = xfers_from_rules(rules)
    name = next(n for n in xfers if "partition_linear_combine_d2" in n)
    apps = xfers[name](g)
    assert len(apps) == 1
    apps[0].apply()
    types = [op.op_type for op in g.topo_order()]
    assert OpType.REPLICATE in types and OpType.COMBINE in types
    # the linear survived (weights reused), wired through the replicate
    lin = next(op for op in g.ops.values() if op.name == "lin")
    assert lin.inputs[0].owner_op.op_type == OpType.REPLICATE
    comb = next(op for op in g.ops.values()
                if op.op_type == OpType.COMBINE)
    assert comb.params["degree"] == 2 and comb.params["dim"] == 1


def test_xfer_preserves_numerics():
    """Rewritten graph computes the identical function (parallel ops are
    identity on values; the linear keeps its weights)."""
    import jax

    m1, config = _linear_model()
    g1 = Graph(m1.ops)
    m2, config2 = _linear_model()
    g2 = Graph(m2.ops)
    rules = load_substitution_file(RULES_PATH)
    xfers = xfers_from_rules(rules)
    name = next(n for n in xfers if "partition_linear_combine_d2" in n)
    xfers[name](g2)[0].apply()

    ex1 = Executor(g1, config)
    ex2 = Executor(g2, config2)
    p1, s1 = ex1.init_params(jax.random.PRNGKey(0))
    p2, s2 = ex2.init_params(jax.random.PRNGKey(0))
    x = np.random.RandomState(0).randn(4, 6).astype(np.float32)
    inp1 = {g1.topo_order()[0].name: x}
    inp2 = {g2.topo_order()[0].name: x}
    v1, _, _ = ex1.forward_values(p1, s1, inp1, None,
                                  CompMode.COMP_MODE_INFERENCE)
    v2, _, _ = ex2.forward_values(p2, s2, inp2, None,
                                  CompMode.COMP_MODE_INFERENCE)
    out1 = v1[g1.topo_order()[-1].outputs[0].guid]
    out2 = v2[g2.resolve_tensor(g2.topo_order()[-1].outputs[0]).guid]
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-5)


def test_xfer_degree_feasibility():
    """Every application any xfer offers applies cleanly (the feasibility
    check filters degree/shape mismatches at match time)."""
    rules = load_substitution_file(RULES_PATH)
    xfers = xfers_from_rules(rules)
    for n, fn in xfers.items():
        g = Graph(_linear_model()[0].ops)
        for app in fn(g):
            app.apply()
            g.topo_order()  # still a DAG


def test_xfers_excluded_from_greedy_fixed_point():
    """Trade-off xfers must NOT diverge the greedy apply_substitutions loop
    (each application re-matches its own output); they are joint-search
    actions only."""
    from flexflow_tpu.search.substitution import apply_substitutions

    model, _ = _linear_model()
    g = Graph(model.ops)
    n_before = len(g.ops)
    rules = load_substitution_file(RULES_PATH)
    applied = apply_substitutions(g, xfers_from_rules(rules))
    assert applied == [] and len(g.ops) == n_before


def test_xfer_does_not_stack_on_own_output():
    """Applying an xfer once removes the site from its own match set."""
    model, _ = _linear_model()
    g = Graph(model.ops)
    rules = load_substitution_file(RULES_PATH)
    xfers = xfers_from_rules(rules)
    name = next(n for n in xfers if "partition_linear_combine_d2" in n)
    apps = xfers[name](g)
    assert len(apps) == 1
    apps[0].apply()
    assert xfers[name](g) == []


def test_osdi_rule_file_weight_semantics():
    """The full 640-rule OSDI file compiles into executable xfers, and
    TASO's shared-weight patterns (two linears referencing ONE weight
    external) correctly do NOT match graphs whose layers hold distinct
    weights — the binding-consistency check, not an arity accident."""
    rules = load_substitution_file(VENDORED_RULES)
    xfers = xfers_from_rules(rules)
    assert len(xfers) > 200  # most of the 640 compile to executable form
    config = ff.FFConfig()
    config.batch_size = 8
    m = ff.FFModel(config)
    t = m.create_tensor([8, 32], ff.DataType.DT_FLOAT)
    a = m.dense(t, 16, name="branch_a")
    b = m.dense(t, 16, name="branch_b")
    m.softmax(m.concat([a, b], 1, name="cat"))
    g = Graph(m.ops)
    # distinct weights: the shared-weight concat-fusion family must not fire
    assert all(fn(g) == [] for fn in xfers.values())


def test_xfer_joint_search_integration():
    """The joint search sees loaded xfers as actions and compile() runs end
    to end with a TASO rule file + search budget."""
    config = ff.FFConfig()
    config.num_devices = 2
    config.batch_size = 4
    config.search_budget = 4
    config.substitution_json_path = RULES_PATH
    model = ff.FFModel(config)
    t = model.create_tensor([4, 6], ff.DataType.DT_FLOAT)
    h = model.dense(t, 8, name="l1")
    model.softmax(model.dense(h, 4, name="l2"))
    model.compile(optimizer=ff.SGDOptimizer(model, lr=0.1),
                  loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                  metrics=[])
    x = np.random.RandomState(0).randn(8, 6).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 4, (8, 1)).astype(np.int32)
    h = model.fit(x, y, epochs=1, verbose=False)
    assert np.isfinite(h[-1]["loss"])
