"""MoE router observability (docs/observability.md "MoE router").

The fused ExpertsOp (ops/moe.py) keeps two pieces of router health in its
functional op state: `dropped` — a monotone count of capacity-overflow
token-assignments — and `load` — the last step's per-expert assignment
fractions. Both are device scalars/vectors living inside the jitted step,
so they cost nothing until something on the host asks.

`publish_moe_metrics(model)` is that ask: it reads the state post-step and
mirrors it into the default registry as

 - ff_moe_router_dropped_tokens_total  Counter, labels=(op,)
 - ff_moe_expert_load                  Gauge,   labels=(op, expert)
 - ff_moe_expert_load_imbalance        Gauge,   labels=(op,)
   (max/mean of the load vector: 1.0 = perfectly balanced, n = collapsed
   onto one expert — the one-number router-health signal dashboards key on)

FFModel.fit publishes once per epoch; the serve-bench moe leg publishes
after its run and asserts the dropped counter stayed at zero.

The dropless GatedExpertsOp (an expert layer told which experts it holds)
drops nothing by construction and exports no dropped family; its state —
threaded through the continuous batcher's decode iterations and handed to
`publish_moe_metrics(..., state=batcher.op_counters())` — is mirrored as

 - ff_moe_local_assignments_total    Counter, labels=(op,): assignments
   that fell on the experts held here
 - ff_moe_experts_hit_total          Counter, labels=(op,): distinct local
   experts that received a token, summed over steps
 - ff_moe_expert_load_max_over_mean  Gauge,   labels=(op,): the last
   step's fullest local expert over the mean one
 - ff_moe_few_rows_steps_total       Counter, labels=(op,): the steps whose
   routed product took the few-rows form (ops/moe.py `few_rows`); over the
   op's `steps` it is the share of decode iterations on that path
"""
from __future__ import annotations

from typing import Dict, Optional

from .registry import REGISTRY, MetricsRegistry


def moe_router_families(registry: Optional[MetricsRegistry] = None):
    """(dropped counter, load gauge, imbalance gauge) — registered
    idempotently; the families render as zeros until first publish."""
    reg = registry if registry is not None else REGISTRY
    c_dropped = reg.counter(
        "ff_moe_router_dropped_tokens_total",
        "Token-assignments dropped by capacity overflow, per experts op",
        labels=("op",))
    g_load = reg.gauge(
        "ff_moe_expert_load",
        "Per-expert share of router assignments, last published step",
        labels=("op", "expert"))
    g_imb = reg.gauge(
        "ff_moe_expert_load_imbalance",
        "max/mean of the expert load vector (1.0 = balanced)",
        labels=("op",))
    return c_dropped, g_load, g_imb


def gated_experts_families(registry: Optional[MetricsRegistry] = None):
    """(local assignments counter, experts hit counter, load max-over-mean
    gauge, few-rows steps counter) of the dropless GatedExpertsOp."""
    reg = registry if registry is not None else REGISTRY
    return (
        reg.counter("ff_moe_local_assignments_total",
                    "Router assignments that fell on the experts held here",
                    labels=("op",)),
        reg.counter("ff_moe_experts_hit_total",
                    "Distinct local experts that received a token, summed"
                    " over steps", labels=("op",)),
        reg.gauge("ff_moe_expert_load_max_over_mean",
                  "Fullest local expert over the mean one, last step",
                  labels=("op",)),
        reg.counter("ff_moe_few_rows_steps_total",
                    "Steps whose routed product took the few-rows form",
                    labels=("op",)))


# per (registry id, op[, counter]) last published total, so the counter
# families only ever receive non-negative deltas
_LAST_PUBLISHED: Dict[tuple, float] = {}


def _inc_to(counter, key: tuple, total: float, **labels) -> None:
    delta = total - _LAST_PUBLISHED.get(key, 0.0)
    if delta > 0:
        counter.inc(delta, **labels)
    _LAST_PUBLISHED[key] = total


def _publish_gated(reg, op, vars_) -> Dict:
    import numpy as np

    c_assign, c_hit, g_mom, c_few = gated_experts_families(reg)
    load = np.asarray(vars_["load"], dtype=np.float64)
    got = {k: float(np.asarray(vars_[k]))
           for k in ("assignments", "experts_hit", "steps", "few_rows_steps")}
    _inc_to(c_assign, (id(reg), op.name, "a"), got["assignments"],
            op=op.name)
    _inc_to(c_hit, (id(reg), op.name, "h"), got["experts_hit"], op=op.name)
    _inc_to(c_few, (id(reg), op.name, "f"), got["few_rows_steps"],
            op=op.name)
    mean = float(load.mean()) if load.size else 0.0
    g_mom.set(float(load.max()) / mean if mean > 0 else 0.0, op=op.name)
    return {**got, "dropped": 0.0, "load": load.tolist()}


def publish_moe_metrics(model, registry: Optional[MetricsRegistry] = None,
                        state: Optional[Dict] = None) -> Dict:
    """Mirror every expert op's router state into the registry (`state`:
    an op-state tree to read in place of `model.state`, e.g. the
    continuous batcher's `op_counters()`). Returns {op name: {"dropped":
    float, "load": [..], ...}} for callers that want the raw numbers (the
    serve-bench moe leg's zero-drop assert)."""
    import numpy as np

    from ..ffconst import OpType

    reg = registry if registry is not None else REGISTRY
    c_dropped, g_load, g_imb = moe_router_families(reg)
    out: Dict[str, Dict] = {}
    state = state if state is not None else (
        getattr(model, "state", None) or {})
    for op in model.graph.ops.values():
        if op.op_type == OpType.GATED_EXPERTS and state.get(op.name):
            out[op.name] = _publish_gated(reg, op, state[op.name])
        if op.op_type != OpType.EXPERTS:
            continue
        vars_ = state.get(op.name)
        if not vars_ or "dropped" not in vars_:
            continue
        dropped = float(np.asarray(vars_["dropped"]))
        load = np.asarray(vars_["load"], dtype=np.float64)
        _inc_to(c_dropped, (id(reg), op.name), dropped, op=op.name)
        for e, frac in enumerate(load):
            g_load.set(float(frac), op=op.name, expert=str(e))
        mean = float(load.mean()) if load.size else 0.0
        g_imb.set(float(load.max()) / mean if mean > 0 else 0.0,
                  op=op.name)
        out[op.name] = {"dropped": dropped, "load": load.tolist()}
    return out


def reset_moe_publisher() -> None:
    """Forget the per-op published baselines (test isolation: the autouse
    obs reset zeroes the registry, so the deltas must restart from 0)."""
    _LAST_PUBLISHED.clear()
