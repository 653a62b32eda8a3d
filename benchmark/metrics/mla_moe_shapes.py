"""Reader and shape functions of a latent-attention, sparse-expert model's
per-layer metrics (`shapes.SHAPE_FNS` assumes dense K/V layers and one FFN).
A metric's JSON names this module as its `reducer` and a `kind`:

  device_time  device time under any of the named scopes (`parts`; `names`:
               or of ops whose HLO name holds one of these) inside the
               compiled program `program`, per run of it, ms
  roofline     least time by the chip's peaks for what the function `counts` names counts /
               that device time, %, with the side that bounds it
  mfu          operations the function `counts` names counts / (window x peak), %

Operations and bytes are what THE ALGORITHM needs: an expert's three
matrices are read once for every expert that was HIT (not for the experts
held), a decoded token reads the latent rows its sequence has FILLED. A
multiply-add is 2 operations. Counts come from the runner (`work`: decoded
tokens and the rows they attended, the expert layers' own counters) and the
trace (runs of the program).
"""
from __future__ import annotations

from typing import Dict

from .. import peaks as peaks_mod


def dims(cfg: Dict) -> Dict[str, int]:
    return {"h": int(cfg["hidden_size"]), "heads": int(cfg["num_attention_heads"]),
            "qr": int(cfg["q_lora_rank"]), "kvr": int(cfg["kv_lora_rank"]),
            "nope": int(cfg["qk_nope_head_dim"]),
            "rope": int(cfg["qk_rope_head_dim"]), "vd": int(cfg["v_head_dim"]),
            "f": int(cfg["moe_intermediate_size"]),
            "layers": int(cfg["num_hidden_layers"]),
            "held": int(cfg["n_routed_experts"]),
            "total": int(cfg["router_width"]),
            "k": int(cfg["num_experts_per_tok"]),
            "vocab": int(cfg["vocab_size"]),
            "cache_bytes": int(cfg["kv_cache_bytes_per_value"])}


def attention_params(cfg: Dict) -> int:
    """Weights of one latent attention: W_qa, W_qb, W_kva, W_kvb, W_o."""
    d = dims(cfg)
    return (d["h"] * d["qr"] + d["qr"] * d["heads"] * (d["nope"] + d["rope"])
            + d["h"] * (d["kvr"] + d["rope"])
            + d["kvr"] * d["heads"] * (d["nope"] + d["vd"])
            + d["heads"] * d["vd"] * d["h"])


def expert_params(cfg: Dict) -> int:
    """One gated expert: W_g, W_u, W_d."""
    d = dims(cfg)
    return 3 * d["h"] * d["f"]


def dense_params_per_token(cfg: Dict) -> int:
    """Weights every token multiplies through, whatever the router says:
    per layer attention, the shared expert and the router; the head."""
    d = dims(cfg)
    per_layer = attention_params(cfg) + expert_params(cfg) + d["h"] * d["total"]
    return d["layers"] * per_layer + d["h"] * d["vocab"]


def moe_experts(cfg: Dict, work: Dict):
    """(flops, hbm bytes) of the routed experts in the traced decode
    iterations: 2 per weight for every local assignment; the three bf16
    matrices of every expert that was hit, once a layer and step."""
    p = expert_params(cfg)
    return (2.0 * p * float(work["moe_local_assignments"]),
            2.0 * p * float(work["moe_experts_hit"]))


def mla_decode(cfg: Dict, work: Dict):
    """(flops, hbm bytes) of absorbed decode attention in the traced
    window, a layer: each decoded token reads the latent rows its sequence
    has filled (kv_lora_rank + rope values each) for scores over the whole
    row and context over the latent part; W_kvb is read once a step and
    carries the query in and the context out."""
    d = dims(cfg)
    rows, tokens = float(work["decode_attended_rows"]), float(
        work["decode_tokens"])
    steps = float(work["decode_steps"])
    kvb = d["kvr"] * d["heads"] * (d["nope"] + d["vd"])
    flops = (rows * 2.0 * d["heads"] * (2 * d["kvr"] + d["rope"])
             + tokens * 2.0 * kvb)
    nbytes = (rows * (d["kvr"] + d["rope"]) * d["cache_bytes"]
              + steps * kvb * 2.0)
    return d["layers"] * flops, d["layers"] * nbytes


def serve_forward_flops(cfg: Dict, work: Dict) -> float:
    """Forward operations of every token the traced window processed, local
    experts only: the dense part of every token; the routed part of decoded
    tokens from the layers' own counters and of prompt tokens at the
    uniform router's share (prefill does not thread the counters); the
    attention core on the rows attended (absorbed for decode, expanded for
    prefill)."""
    d = dims(cfg)
    prompt, decode = float(work["prompt_tokens"]), float(work["decode_tokens"])
    routed = (float(work["moe_local_assignments"])
              + prompt * d["layers"] * d["k"] * d["held"] / d["total"])
    core = d["layers"] * 2.0 * d["heads"] * (
        float(work["decode_attended_rows"]) * (2 * d["kvr"] + d["rope"])
        + float(work["prefill_attended_rows"])
        * (d["nope"] + d["rope"] + d["vd"]))
    return (2.0 * dense_params_per_token(cfg) * (prompt + decode)
            + 2.0 * expert_params(cfg) * routed + core)


SHAPE_FNS = {"moe_experts": moe_experts, "mla_decode": mla_decode,
             "serve_forward_flops": serve_forward_flops}


def _seconds(spec: Dict, trace) -> float:
    """Device seconds, inside the program, of ops under a scope holding one
    of `parts`, or — `names` — whose HLO instruction name holds one of them:
    the chip's compiler turns `jax.lax.ragged_dot` into custom calls named
    `ragged-dot-*` that carry no scope."""
    ids = trace.program_ids(spec["program"])
    parts, names = spec["parts"], spec.get("names", [])
    return trace.per_chip(lambda o: o.program_id in ids and (
        any(p in o.scope for p in parts) or any(n in o.name for n in names)))


def read(spec: Dict, ctx, rec):
    trace = rec.trace
    if trace is None or not trace.ops:
        return None
    work = dict(rec.work)
    if spec.get("program"):
        work["decode_steps"] = float(trace.module_runs(spec["program"]))
    if any(k not in work for k in spec.get("needs_work", [])):
        return None
    pk = peaks_mod.peaks_for(ctx.devices[0].device_kind)
    kind = spec["kind"]
    if kind == "mfu":
        if trace.window_s <= 0:
            return None
        flops = SHAPE_FNS[spec["counts"]](ctx.config, work)
        return (100.0 * flops / (trace.window_s * pk.bf16_flops_per_s),
                {"bound_by": "mxu"})
    secs = _seconds(spec, trace)
    runs = work.get("decode_steps", 0.0)
    if secs <= 0.0 or not runs:
        return None
    if kind == "device_time":
        return secs / runs * 1e3
    if kind == "roofline":
        flops, nbytes = SHAPE_FNS[spec["counts"]](ctx.config, work)
        if flops <= 0.0 and nbytes <= 0.0:
            return None
        least, side = peaks_mod.least_time_s(flops, nbytes, pk)
        return 100.0 * least / secs, {"bound_by": side,
                                      "ms_per_iter": secs / runs * 1e3}
    raise ValueError(f"mla_moe_shapes: unknown kind {kind!r}")
