"""Plain reference of the `lm_osdi22w` causal language model: token embedding
(no positional term: the configuration has none, order enters through the
causal mask alone), post-LN decoder stack, an untied vocabulary head. One
full causal forward pass over prompt + served tokens in float32 — no cache,
no chunks, no slots.

What is compared: at every served position the gap, in logits, by which the
served token lies below the reference's best token. Greedy decoding that
computed what the configuration states serves the best token or a near-tie
(gap ~ rounding); a wrong cache row, mask, chunk offset or slot shows as a
gap of the order of the logits' spread.
"""
from __future__ import annotations

import functools
import json
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import plain


def logits_fn(params, tokens, cfg: Dict, prec: str):
    """tokens (1, T) -> (T, V) float32 logits."""
    x = params["emb"]["weight"][tokens].astype(plain.act_dtype(prec))
    eps = float(cfg["layer_norm_eps"])
    for i in range(int(cfg["num_hidden_layers"])):
        x = plain.post_ln_layer(x, params, f"l{i}", prec, True, eps)
    hd = params["lm_head"]
    z = plain.mm(x, hd["kernel"], "ble,ev->blv", prec) + hd["bias"].astype(
        x.dtype)
    return z[0].astype(jnp.float32)


@functools.lru_cache(maxsize=None)
def _gaps_fn(cfg_key: str, pad_to: int, control_prec: str):
    """One jitted program per (shapes, control): the weights are ARGUMENTS —
    closed over, they would be baked into the program as 0.7 GB of
    constants, compiled anew for every seed."""
    cfg = json.loads(cfg_key)

    def one(params, tokens, first, n_out):
        z = logits_fn(params, tokens, cfg, "float32")
        # logits at position first-1+j predict output token j
        idx = jnp.clip(first - 1 + jnp.arange(pad_to), 0, pad_to - 1)
        rows = z[idx]
        if control_prec:
            zc = logits_fn(params, tokens, cfg, control_prec)
            picked = jnp.argmax(zc[idx], axis=-1)
        else:
            picked = jnp.roll(tokens[0], -1)[idx]
        best = jnp.max(rows, axis=-1)
        got = jnp.take_along_axis(rows, picked[:, None], axis=-1)[:, 0]
        return jnp.where(jnp.arange(pad_to) < n_out, best - got, 0.0)

    return jax.jit(one)


def served_gaps(params, cfg: Dict, prompts: Sequence[np.ndarray],
                served: Sequence[np.ndarray], pad_to: int,
                control_prec: str = "") -> List[np.ndarray]:
    """For each request the per-position gap max(logits) - logits[token].
    `control_prec` empty: the token is the one the program served. Else the
    token is the one a forward pass in that lower precision puts first at
    the same position (same prompt and served tokens fed) — the control put
    in the program's place."""
    fn = _gaps_fn(json.dumps({k: cfg[k] for k in (
        "num_hidden_layers", "layer_norm_eps")}, sort_keys=True),
        int(pad_to), control_prec)
    out = []
    for p, s in zip(prompts, served):
        n = len(p) + len(s)
        if n > pad_to:
            raise ValueError(f"request of {n} tokens exceeds pad_to={pad_to}")
        toks = np.zeros((1, pad_to), np.int32)
        toks[0, :len(p)] = p
        toks[0, len(p):n] = s
        g = fn(params, jnp.asarray(toks), jnp.int32(len(p)),
               jnp.int32(len(s)))
        out.append(np.asarray(g)[:len(s)])
    return out
