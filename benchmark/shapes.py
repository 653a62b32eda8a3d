"""Operations and bytes THE ALGORITHM needs, as functions of a
configuration's shapes — never of an implementation's buffers. A metric's
JSON names one of these by its key in SHAPE_FNS.

Conventions: a multiply-add is 2 operations; the embedding lookup is a
gather and counts nothing; softmax, layer norm, GELU and the optimizer are
left out (MFU is about the matrix unit). Recomputed work never counts.
"""
from __future__ import annotations

from typing import Dict


def _dims(cfg: Dict):
    h = int(cfg["hidden_size"])
    return (int(cfg["num_hidden_layers"]), h, int(cfg["intermediate_size"]),
            int(cfg["num_attention_heads"]) * int(cfg["head_dim"]))


def layer_matmul_params(cfg: Dict) -> int:
    """Weights that every token multiplies through, in one layer."""
    _, h, ffn, hd = _dims(cfg)
    return 3 * h * hd + hd * h + 2 * h * ffn


def head_params(cfg: Dict) -> int:
    out = int(cfg.get("num_labels") or cfg["vocab_size"])
    return int(cfg["hidden_size"]) * out


def forward_flops_per_token(cfg: Dict, attended: float) -> float:
    """One token's forward pass when its query attends `attended` rows:
    2 per weight, plus QK^T and PV (2 * 2 * attended * heads*head_dim a
    layer)."""
    layers, _h, _f, hd = _dims(cfg)
    return (2.0 * (layers * layer_matmul_params(cfg) + head_params(cfg))
            + layers * 4.0 * attended * hd)


def train_flops_per_token(cfg: Dict) -> float:
    """Forward + backward (= 3 x forward) of one token of a full
    bidirectional sequence: every query attends all `sequence_length`
    rows."""
    return 3.0 * forward_flops_per_token(cfg, float(cfg["sequence_length"]))


def flash_attention_train(cfg: Dict, work: Dict):
    """(flops, hbm bytes) of the attention core — QK^T, softmax.V and their
    backward — per optimizer step: forward 4*L*L*hd per sequence and layer,
    backward twice that (dq, dk, dv, and the recomputed scores that the
    algorithm, not the kernel, needs: dP and dS — 4 matmuls; the flash
    backward's recomputation of P is not counted). Bytes: q, k, v, o read or
    written once forward; q, k, v, o, do read and dq, dk, dv written
    backward, in the activation type."""
    layers, _h, _f, hd = _dims(cfg)
    seq = int(cfg["sequence_length"])
    rows = int(work["sequences_per_step_per_chip"])
    act = 2  # bf16 activations (configuration's precision)
    fwd = 4.0 * seq * seq * hd
    flops = layers * rows * (fwd + 2.0 * fwd)
    per_tensor = rows * seq * hd * act
    nbytes = layers * per_tensor * (4 + 8)
    return flops, float(nbytes)


def decode_attention(cfg: Dict, work: Dict):
    """(flops, hbm bytes) of decode attention over the traced window: each
    decoded token reads the K and V rows its sequence has FILLED (not the
    rows the cache allocates), in the cache's type, once a layer; QK^T and
    PV are 4 * rows * heads*head_dim operations a layer."""
    layers, _h, _f, hd = _dims(cfg)
    rows = float(work["decode_attended_rows"])
    cache_bytes = int(cfg["kv_cache_bytes_per_value"])
    return layers * 4.0 * rows * hd, layers * 2.0 * rows * hd * cache_bytes


def serve_forward_flops(cfg: Dict, work: Dict) -> float:
    """Forward operations of every token the window processed: prompt token
    at position p attends p+1 rows, a decoded token the rows filled."""
    base = forward_flops_per_token(cfg, 0.0)
    layers, _h, _f, hd = _dims(cfg)
    tokens = float(work["prompt_tokens"]) + float(work["decode_tokens"])
    rows = float(work["prefill_attended_rows"]) + float(
        work["decode_attended_rows"])
    return tokens * base + layers * 4.0 * rows * hd


def train_flops(cfg: Dict, work: Dict) -> float:
    return float(work["tokens_per_chip"]) * train_flops_per_token(cfg)


SHAPE_FNS = {
    "flash_attention_train": flash_attention_train,
    "decode_attention": decode_attention,
    "serve_forward_flops": serve_forward_flops,
    "train_flops": train_flops,
}
