"""What the two configurations share: the parameter spec of a post-LN
transformer stack in the op names the builders give the program."""
from __future__ import annotations

from typing import Dict


def stack_spec(cfg: Dict, layer_prefix: str, emb: str, head: str,
               head_out: int) -> Dict:
    h = int(cfg["hidden_size"])
    heads = int(cfg["num_attention_heads"])
    d = int(cfg["head_dim"])
    ffn = int(cfg["intermediate_size"])
    spec = {emb: {"weight": ((int(cfg["vocab_size"]), h), "matrix")}}
    for i in range(int(cfg["num_hidden_layers"])):
        p = f"{layer_prefix}{i}"
        spec[f"{p}_attn"] = {
            "wq": ((h, heads, d), "matrix"), "wk": ((h, heads, d), "matrix"),
            "wv": ((h, heads, d), "matrix"), "wo": ((heads, d, h), "matrix"),
            "bq": ((heads, d), "bias"), "bk": ((heads, d), "bias"),
            "bv": ((heads, d), "bias"), "bo": ((h,), "bias")}
        spec[f"{p}_ln1"] = {"gamma": ((h,), "gain"), "beta": ((h,), "bias")}
        spec[f"{p}_ff1"] = {"kernel": ((h, ffn), "matrix"),
                            "bias": ((ffn,), "bias")}
        spec[f"{p}_ff2"] = {"kernel": ((ffn, h), "matrix"),
                            "bias": ((h,), "bias")}
        spec[f"{p}_ln2"] = {"gamma": ((h,), "gain"), "beta": ((h,), "bias")}
    spec[head] = {"kernel": ((h, head_out), "matrix"),
                  "bias": ((head_out,), "bias")}
    return spec


def check_tree(params, spec, what: str) -> None:
    """The program's parameter tree has to be exactly the spec's: a leaf the
    benchmark did not seed would be a weight the reference never sees."""
    got = {(op, w): tuple(v.shape) for op, ws in params.items()
           for w, v in ws.items()}
    want = {(op, w): tuple(shape) for op, ws in spec.items()
            for w, (shape, _k) in ws.items()}
    if got != want:
        diff = sorted(set(got.items()) ^ set(want.items()))[:6]
        raise RuntimeError(f"{what}: the program's parameters are not the"
                           f" configuration's: {diff}")
