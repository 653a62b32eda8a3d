"""The decode read of the two attention ops, for whoever asks
(docs/observability.md).

`LatentAttentionOp` (ops/latent_attention.py) and, where its shapes admit
the decode kernel, `MultiHeadAttentionOp` (ops/attention.py) count, in op
state the continuous batcher threads through its decode iterations, the
cache rows their sequences had FILLED and the rows the core that ran READ.
`publish_attention_row_metrics(model, state=batcher.op_counters())` mirrors
them as

 - ff_mla_rows_filled_total / ff_attn_rows_filled_total  Counter,
   labels=(op,): cache rows at or before each slot's position, summed over
   slots and decode steps — the latent op's / the dense op's
 - ff_mla_rows_read_total / ff_attn_rows_read_total      Counter,
   labels=(op,): rows the decode core fetched — whole blocks up to the
   position under the kernels (kernels/pallas/latent_decode.py,
   decode.py), slots x max_len under the reference

read / filled is the over-read: 1.0 is the algorithm's need, slots x
max_len / filled what the reference pays.

The latent op's prefill chunks are counted on the host, where the batcher
dispatches them (`mla_prefill_chunks_counter`):

 - ff_mla_prefill_chunks_total  Counter, labels=(op, rows): prefill chunks
   by the rows of the batch-1 holder their expanded path ran over (the
   shortest prefix that holds the chunk's last query,
   `LatentAttentionOp.prefill_prefix_rows`); the holder's own length is
   the whole
"""
from __future__ import annotations

from typing import Dict, Optional

from .moe import _inc_to
from .registry import REGISTRY, Counter, MetricsRegistry


def mla_prefill_chunks_counter(
        registry: Optional[MetricsRegistry] = None) -> Counter:
    """The family `ff_mla_prefill_chunks_total{op, rows}`."""
    reg = registry if registry is not None else REGISTRY
    return reg.counter(
        "ff_mla_prefill_chunks_total",
        "Latent attention prefill chunks by the rows of the batch-1 holder"
        " their expanded path ran over", labels=("op", "rows"))


def publish_attention_row_metrics(
        model, registry: Optional[MetricsRegistry] = None,
        state: Optional[Dict] = None) -> Dict[str, Dict[str, int]]:
    """Mirror every counting attention op's row counters into the registry
    (`state`: an op-state tree to read in place of `model.state`). Returns
    {op name: {"attn_steps", "rows_filled", "rows_read"}} as host ints."""
    from ..ffconst import OpType
    from ..ops.latent_attention import wide_count

    reg = registry if registry is not None else REGISTRY
    families = {OpType.LATENT_ATTENTION: ("ff_mla", "Latent cache"),
                OpType.MULTIHEAD_ATTENTION: ("ff_attn", "K/V cache")}
    state = state if state is not None else (
        getattr(model, "state", None) or {})
    out: Dict[str, Dict[str, int]] = {}
    for op in model.graph.ops.values():
        vars_ = state.get(op.name)
        if op.op_type not in families or not vars_ \
                or "rows_read" not in vars_:
            continue
        prefix, what = families[op.op_type]
        c_filled = reg.counter(
            f"{prefix}_rows_filled_total",
            f"{what} rows at or before each slot's position, summed over"
            " slots and decode steps", labels=("op",))
        c_read = reg.counter(
            f"{prefix}_rows_read_total",
            f"{what} rows the decode core fetched", labels=("op",))
        got = {"attn_steps": int(vars_["attn_steps"]),
               "rows_filled": wide_count(vars_["rows_filled"]),
               "rows_read": wide_count(vars_["rows_read"])}
        _inc_to(c_filled, (id(reg), op.name, f"{prefix}_filled"),
                got["rows_filled"], op=op.name)
        _inc_to(c_read, (id(reg), op.name, f"{prefix}_read"),
                got["rows_read"], op=op.name)
        out[op.name] = got
    return out
