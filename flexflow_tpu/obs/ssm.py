"""A state-space mixer's state traffic, for whoever asks
(docs/observability.md).

`Mamba2MixerOp` (ops/ssm.py) counts, in op state the continuous batcher
threads through its decode iterations, its one-token steps and the slot
rows whose recurrent state a step read and wrote (every row of the pool,
idle slots' too); the batcher itself counts the admissions that started a
sequence from a zeroed state (`ContinuousBatcher.op_counters` hands all
three back). `publish_ssm_metrics(model, state=batcher.op_counters())`
mirrors them as

 - ff_ssm_state_rows_stepped_total  Counter, labels=(op,): slot rows whose
   state a decode step read and wrote, summed over steps
 - ff_ssm_state_resets_total        Counter, labels=(op,): admissions, each
   of which overwrote a slot's state with a freshly prefilled one

rows stepped x the state's bytes x 2 is the decode step's floor on HBM
traffic for the mixer (`ff_kvpool_state_bytes_per_slot` is the bytes a slot
holds across all such ops).
"""
from __future__ import annotations

from typing import Dict, Optional

from .moe import _inc_to
from .registry import REGISTRY, MetricsRegistry


def publish_ssm_metrics(model, registry: Optional[MetricsRegistry] = None,
                        state: Optional[Dict] = None) -> Dict[str, Dict]:
    """Mirror every state-space mixer's counters into the registry
    (`state`: an op-state tree to read in place of `model.state`). Returns
    {op name: {"ssm_steps", "state_rows_stepped", "state_resets"}} as host
    ints."""
    from ..ffconst import OpType
    from ..ops.latent_attention import wide_count

    reg = registry if registry is not None else REGISTRY
    c_rows = reg.counter(
        "ff_ssm_state_rows_stepped_total",
        "Slot rows whose recurrent state a decode step read and wrote",
        labels=("op",))
    c_resets = reg.counter(
        "ff_ssm_state_resets_total",
        "Admissions that overwrote a slot's recurrent state", labels=("op",))
    state = state if state is not None else (
        getattr(model, "state", None) or {})
    out: Dict[str, Dict[str, int]] = {}
    for op in model.graph.ops.values():
        vars_ = state.get(op.name)
        if op.op_type != OpType.SSM or not vars_ \
                or "state_rows_stepped" not in vars_:
            continue
        got = {"ssm_steps": int(vars_["ssm_steps"]),
               "state_rows_stepped": wide_count(vars_["state_rows_stepped"]),
               "state_resets": int(vars_.get("state_resets", 0))}
        _inc_to(c_rows, (id(reg), op.name, "ssm_rows"),
                got["state_rows_stepped"], op=op.name)
        _inc_to(c_resets, (id(reg), op.name, "ssm_resets"),
                got["state_resets"], op=op.name)
        out[op.name] = got
    return out
