"""Reader and shape functions of the per-layer metrics of a model whose
layers mix window and full grouped-KV attention of differing head counts
over sparse experts (`mla_moe_shapes` prices latent attention, `shapes.py`
one head count and no window). A metric's JSON names this module as its
`reducer` and a `kind`:

  roofline     least time by the chip's peaks for what the function `counts`
               names counts / device time under any of the named scopes
               (`parts`; `names`: or of ops whose HLO name holds one of
               these) inside the compiled program `program`, %, with the
               side that bounds it and the ms per run
  mfu          operations the function `counts` names counts / (window x
               peak), %
  gauge        the largest sample of the program's own gauge `gauge` in the
               process-wide registry

Operations and bytes are what THE ALGORITHM needs, whatever implements it:
a decoded token reads, in a full layer, the K and V rows its sequence has
FILLED and, in a window layer, min(position + 1, sliding_window) of them; an
expert's three matrices are read once for every expert that was HIT; a
prompt's positions go through the head, the final norm and the last
layer's MLP once a REQUEST (only the sampled row needs them). A
multiply-add is 2 operations. Counts come from the runner (`work`: decoded
tokens and the rows they attended, the expert layers' own counters) and the
trace (runs of the programs).

The runner counts the rows of a FULL layer (`decode_attended_rows`). A
window layer's are derived here, and only where the traffic's shortest
prompt is at least the window: every decoded token then attends exactly
`sliding_window` rows and every prompt fills the window once. Elsewhere the
metrics that need them are left out.
"""
from __future__ import annotations

from typing import Dict, List

from .. import peaks as peaks_mod
from .mla_moe_shapes import _seconds   # device time by scope part or HLO name

FULL, SLIDING = "full_attention", "sliding_attention"


def layers(cfg: Dict) -> List[Dict]:
    """[{kind, heads, mlp}] of the layers that are built."""
    return [{"kind": cfg["layer_types"][i],
             "heads": int(cfg["num_attention_heads_per_layer"][i]),
             "mlp": cfg["mlp_layer_types"][i]}
            for i in range(int(cfg["num_hidden_layers"]))]


def attention_params(cfg: Dict, heads: int) -> int:
    """W_q, W_k, W_v, W_o and the gate's W_g of one attention."""
    h, d = int(cfg["hidden_size"]), int(cfg["head_dim"])
    kvh = int(cfg["num_key_value_heads"])
    return 2 * h * heads * d + 2 * h * kvh * d + h * heads


def expert_params(cfg: Dict) -> int:
    """One gated routed expert: W_g, W_u, W_d."""
    return 3 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])


def mlp_params_per_token(cfg: Dict, layer: Dict) -> int:
    """Weights of a layer's MLP every token multiplies through whatever the
    router says: the dense MLP, or the router and the shared expert."""
    h = int(cfg["hidden_size"])
    if layer["mlp"] == "dense":
        return 3 * h * int(cfg["intermediate_size"])
    return h * int(cfg["num_experts"]) + 3 * h * int(
        cfg["shared_expert_intermediate_size"])


def head_params(cfg: Dict) -> int:
    return int(cfg["hidden_size"]) * int(cfg["vocab_size"])


def _core(cfg: Dict, kind: str):
    """(operations a row, bytes a row) of the attention core summed over
    the layers of `kind`: QK^T and PV are 4 x heads x head_dim operations
    an attended row; K and V are kv heads x head_dim values each."""
    d, kvh = int(cfg["head_dim"]), int(cfg["num_key_value_heads"])
    of_kind = [l for l in layers(cfg) if l["kind"] == kind]
    return (sum(4.0 * l["heads"] * d for l in of_kind),
            len(of_kind) * 2.0 * kvh * d * int(cfg["kv_cache_bytes_per_value"]))


def full_decode(cfg: Dict, work: Dict):
    """(flops, hbm bytes) of the full layers' decode attention in the
    traced window: each decoded token reads the rows its sequence has
    filled."""
    ops, nbytes = _core(cfg, FULL)
    rows = float(work["decode_attended_rows"])
    return rows * ops, rows * nbytes


def swa_decode(cfg: Dict, work: Dict):
    """(flops, hbm bytes) of the window layers' decode attention: each
    decoded token reads min(position + 1, sliding_window) rows."""
    ops, nbytes = _core(cfg, SLIDING)
    rows = float(work["decode_window_rows"])
    return rows * ops, rows * nbytes


def moe_experts(cfg: Dict, work: Dict):
    """(flops, hbm bytes) of the routed experts in the traced decode
    iterations: 2 per weight for every assignment; the three bf16 matrices
    of every expert that was hit, once a layer and step."""
    p = expert_params(cfg)
    return (2.0 * p * float(work["moe_local_assignments"]),
            2.0 * p * float(work["moe_experts_hit"]))


def serve_forward_flops(cfg: Dict, work: Dict) -> float:
    """Forward operations of every token the traced window processed. A
    decoded token: every layer's attention and MLP weights, its routed
    experts from the layers' own counters, the head, the attention core on
    the rows attended. A prompt token: the same through the last layer's
    attention (routed: k experts a sparse layer), the core on the rows its
    position attends; the last layer's MLP and the head once a request."""
    ls = layers(cfg)
    k = int(cfg["num_experts_per_tok"])
    prompt, decode = float(work["prompt_tokens"]), float(work["decode_tokens"])
    requests = float(work["prefill_requests"])
    attn = sum(attention_params(cfg, l["heads"]) for l in ls)
    mlps = [mlp_params_per_token(cfg, l)
            + (k * expert_params(cfg) if l["mlp"] != "dense" else 0)
            for l in ls]
    decode_dense = attn + sum(mlp_params_per_token(cfg, l) for l in ls) \
        + head_params(cfg)
    full_ops, _ = _core(cfg, FULL)
    swa_ops, _ = _core(cfg, SLIDING)
    return (2.0 * decode * decode_dense
            + 2.0 * expert_params(cfg) * float(work["moe_local_assignments"])
            + 2.0 * prompt * (attn + sum(mlps[:-1]))
            + 2.0 * requests * (mlps[-1] + head_params(cfg))
            + full_ops * (float(work["decode_attended_rows"])
                          + float(work["prefill_attended_rows"]))
            + swa_ops * (float(work["decode_window_rows"])
                         + float(work["prefill_window_rows"])))


SHAPE_FNS = {"full_decode": full_decode, "swa_decode": swa_decode,
             "moe_experts": moe_experts,
             "serve_forward_flops": serve_forward_flops}


def window_rows(cfg: Dict, traffic: Dict, work: Dict) -> Dict[str, float]:
    """The rows the window layers' tokens attended, where the runner's
    counts fix them: every prompt at least a window long. {} otherwise."""
    w = int(cfg["sliding_window"])
    if int(traffic.get("prompt", {}).get("min", 0)) < w or any(
            k not in work for k in ("decode_tokens", "prompt_tokens",
                                    "prefill_requests")):
        return {}
    n = float(work["prefill_requests"])
    return {"decode_window_rows": float(work["decode_tokens"]) * w,
            "prefill_window_rows": n * w * (w + 1) / 2.0
            + (float(work["prompt_tokens"]) - n * w) * w}


def _gauge(name: str):
    from flexflow_tpu.obs.registry import get_registry

    family = get_registry().get(name)
    values = [v for _labels, v in family.items()] if family else []
    return max(values) if values else None


def read(spec: Dict, ctx, rec):
    kind = spec["kind"]
    if kind == "gauge":
        return _gauge(spec["gauge"])
    trace = rec.trace
    if trace is None or not trace.ops:
        return None
    work = dict(rec.work)
    work["prefill_requests"] = float(trace.module_runs("prefill_last_chunk"))
    work.update(window_rows(ctx.config, ctx.traffic, work))
    if any(k not in work for k in spec.get("needs_work", [])):
        return None
    pk = peaks_mod.peaks_for(ctx.devices[0].device_kind)
    if kind == "mfu":
        if trace.window_s <= 0:
            return None
        flops = SHAPE_FNS[spec["counts"]](ctx.config, work)
        return (100.0 * flops / (trace.window_s * pk.bf16_flops_per_s),
                {"bound_by": "mxu"})
    secs = _seconds(spec, trace)
    runs = float(trace.module_runs(spec["program"]))
    if secs <= 0.0 or not runs:
        return None
    if kind == "roofline":
        flops, nbytes = SHAPE_FNS[spec["counts"]](ctx.config, work)
        if flops <= 0.0 and nbytes <= 0.0:
            return None
        least, side = peaks_mod.least_time_s(flops, nbytes, pk)
        return 100.0 * least / secs, {"bound_by": side,
                                      "ms_per_iter": secs / runs * 1e3}
    raise ValueError(f"swa_moe_shapes: unknown kind {kind!r}")
