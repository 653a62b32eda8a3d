"""Reader and shape functions of the per-layer metrics of a model whose
blocks run a Mamba-2 mixer beside grouped-KV attention (`shapes.SHAPE_FNS`
prices K/V at `num_attention_heads * head_dim` and knows no mixer). A
metric's JSON names this module as its `reducer` and a `kind` (the times
themselves need no count and read through `reducers.device_time`):

  roofline     least time by the chip's peaks for what the function `counts`
               names counts / device time under any of the named scopes
               (`parts`) inside the compiled programs whose name holds
               `program`, %, with the side that bounds and the ms per run
  mfu          operations the function `counts` names counts / (window x
               peak), %

Operations and bytes are what THE ALGORITHM needs. A decode step reads and
writes the recurrent state of EVERY row of the pool, idle slots' too (the
op counts them so: `state_rows_stepped`), and nothing else is priced under
`ssm:state_update` (at the state's STORED size,
`ssm_state_bytes_per_value`): the mixer's weights are read under `ssm:in_proj` and
`ssm:out_proj`, whose time that scope does not hold. A prefill chunk scans
all of its `prefill_chunk_tokens` rows in blocks of `mamba_chunk_size`. A
decoded token reads the K and V rows its sequence has FILLED, of
`num_key_value_heads * head_dim` values each. A multiply-add is 2
operations. Counts come from the runner (`work`) and the trace (runs of the
programs).
"""
from __future__ import annotations

from typing import Dict

from .. import peaks as peaks_mod


def dims(cfg: Dict) -> Dict[str, int]:
    return {"e": int(cfg["hidden_size"]), "f": int(cfg["intermediate_size"]),
            "heads": int(cfg["num_attention_heads"]),
            "kvh": int(cfg["num_key_value_heads"]), "d": int(cfg["head_dim"]),
            "d_ssm": int(cfg["mamba_d_ssm"]), "mh": int(cfg["mamba_n_heads"]),
            "p": int(cfg["mamba_d_head"]), "n": int(cfg["mamba_d_state"]),
            "g": int(cfg["mamba_n_groups"]), "k": int(cfg["mamba_d_conv"]),
            "block": int(cfg["mamba_chunk_size"]),
            "layers": int(cfg["num_hidden_layers"]),
            "vocab": int(cfg["vocab_size"]),
            "cache_bytes": int(cfg["kv_cache_bytes_per_value"]),
            "state_bytes": int(cfg["ssm_state_bytes_per_value"])}


def attention_params(cfg: Dict) -> int:
    d = dims(cfg)
    return (d["e"] * d["heads"] * d["d"] + 2 * d["e"] * d["kvh"] * d["d"]
            + d["heads"] * d["d"] * d["e"])


def mixer_params(cfg: Dict) -> int:
    """W_in and W_out (the convolution and the per-head vectors multiply
    nothing through the matrix unit)."""
    d = dims(cfg)
    conv_dim = d["d_ssm"] + 2 * d["g"] * d["n"]
    return d["e"] * (d["d_ssm"] + conv_dim + d["mh"]) + d["d_ssm"] * d["e"]


def mlp_params(cfg: Dict) -> int:
    d = dims(cfg)
    return 3 * d["e"] * d["f"]


def params_per_token(cfg: Dict) -> int:
    """Weights every token multiplies through: the layers' and the head's."""
    d = dims(cfg)
    return d["layers"] * (attention_params(cfg) + mixer_params(cfg)
                          + mlp_params(cfg)) + d["e"] * d["vocab"]


def state_bytes(cfg: Dict) -> int:
    """One sequence's recurrent state as stored, a layer."""
    d = dims(cfg)
    return d["mh"] * d["p"] * d["n"] * d["state_bytes"]


def ssm_state(cfg: Dict, work: Dict):
    """(flops, hbm bytes) under `ssm:state_update` in the traced decode
    iterations: every row of the pool has its state read and written once a
    layer and step; decay-and-add and the read-out are 2 x 2 a state value."""
    d = dims(cfg)
    rows = (float(work["state_rows_per_layer_step"])
            * float(work["decode_steps"]) * d["layers"])
    values = d["mh"] * d["p"] * d["n"]
    return rows * 4.0 * values, rows * 2.0 * state_bytes(cfg)


def ssm_scan(cfg: Dict, work: Dict):
    """(flops, hbm bytes) under `ssm:scan` in the traced prefill chunks, of
    the blocked form: per block of Q tokens C.B^T for each group (2 Q Q N),
    its product with x for each head (2 Q Q P), what the block adds to the
    state and what its tokens read of the incoming one (2 x 2 Q P N a
    head); bytes of x, B, C (bf16), dt and y (float32) and the carried
    state in and out."""
    d = dims(cfg)
    chunks = float(work["prefill_chunks"])
    t, q = float(work["prefill_chunk_tokens"]), float(d["block"])
    blocks = -(-t // q)
    per_block = (d["g"] * 2.0 * q * q * d["n"] + d["mh"] * 2.0 * q * q * d["p"]
                 + d["mh"] * 4.0 * q * d["p"] * d["n"])
    nbytes = (t * (d["d_ssm"] + 2 * d["g"] * d["n"]) * 2.0 + t * d["mh"] * 4.0
              + t * d["d_ssm"] * 4.0 + 2.0 * state_bytes(cfg))
    return (d["layers"] * chunks * blocks * per_block,
            d["layers"] * chunks * nbytes)


def gqa_decode(cfg: Dict, work: Dict):
    """(flops, hbm bytes) of decode attention in the traced window: each
    decoded token reads the K and V rows its sequence has filled, of kv
    heads x head_dim values each, once a layer; QK^T and PV are 4 x rows x
    heads x head_dim operations a layer."""
    d = dims(cfg)
    rows = float(work["decode_attended_rows"])
    return (d["layers"] * rows * 4.0 * d["heads"] * d["d"],
            d["layers"] * rows * 2.0 * d["kvh"] * d["d"] * d["cache_bytes"])


def serve_forward_flops(cfg: Dict, work: Dict) -> float:
    """Forward operations of every prompt and output token of the traced
    window: 2 a weight a token, head included; attention's rows (a prompt
    token at position p attends p + 1, a decoded token the rows filled);
    the recurrence's update and read-out, 2 x (2 P N) a head, token and
    layer."""
    d = dims(cfg)
    tokens = float(work["prompt_tokens"]) + float(work["decode_tokens"])
    rows = float(work["prefill_attended_rows"]) + float(
        work["decode_attended_rows"])
    return (tokens * (2.0 * params_per_token(cfg)
                      + d["layers"] * 4.0 * d["p"] * d["n"] * d["mh"])
            + d["layers"] * rows * 4.0 * d["heads"] * d["d"])


SHAPE_FNS = {"ssm_state": ssm_state, "ssm_scan": ssm_scan,
             "gqa_decode": gqa_decode,
             "serve_forward_flops": serve_forward_flops}


def _seconds(spec: Dict, trace) -> float:
    ids = trace.program_ids(spec["program"])
    parts = spec["parts"]
    return trace.per_chip(lambda o: o.program_id in ids and any(
        p in o.scope for p in parts))


def read(spec: Dict, ctx, rec):
    trace = rec.trace
    if trace is None or not trace.ops:
        return None
    work = dict(rec.work)
    runs = float(trace.module_runs(spec["program"])) \
        if spec.get("program") else 0.0
    work["decode_steps"] = work["prefill_chunks"] = runs
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
    if secs <= 0.0 or not runs:
        return None
    if kind == "roofline":
        flops, nbytes = SHAPE_FNS[spec["counts"]](ctx.config, work)
        if flops <= 0.0 and nbytes <= 0.0:
            return None
        least, side = peaks_mod.least_time_s(flops, nbytes, pk)
        return 100.0 * least / secs, {"bound_by": side,
                                      "ms_per_run": secs / runs * 1e3}
    raise ValueError(f"hybrid_ssm_shapes: unknown kind {kind!r}")
