"""Plain reference of the `bert_osdi22` training job: token embedding, post-LN
encoder stack, a per-token classifier, the loss as the configuration states
it, and Adam — float32 throughout, gradients by `jax.grad`.

The configuration's loss is the program's, written down: the model ENDS in a
softmax, and sparse categorical cross-entropy takes log_softmax of what it
is given — so the loss is -mean(log_softmax(softmax(logits))[label]). That
double softmax is a property of the configuration (bench.py trains it so),
noted in PERF.md; the reference follows the configuration.

Departures from the program, all deliberate: f32 `highest` products where the
program multiplies in bf16; f32 Adam moments where the configuration stores
them in bf16; activations kept f32. Those are what the limits allow for.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from . import plain
from ..check import leaf_norms


def logits_fn(params, tokens, cfg: Dict, prec: str):
    x = params["tok_emb"]["weight"][tokens].astype(plain.act_dtype(prec))
    eps = float(cfg["layer_norm_eps"])
    for i in range(int(cfg["num_hidden_layers"])):
        x = plain.post_ln_layer(x, params, f"layer{i}", prec, False, eps)
    c = params["cls"]
    return (plain.mm(x, c["kernel"], "ble,ec->blc", prec)
            + c["bias"].astype(x.dtype)).astype(jnp.float32)


def loss_sum(params, tokens, labels, cfg: Dict, prec: str):
    """Sum over the block's tokens of the per-token loss."""
    z = logits_fn(params, tokens, cfg, prec)
    probs = jax.nn.softmax(z, axis=-1)
    logp = jax.nn.log_softmax(probs, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None].astype(jnp.int32), -1)
    return -jnp.sum(ll)


def adam_step(params, grads, m, v, t: int, opt: Dict):
    b1, b2, eps = float(opt["beta1"]), float(opt["beta2"]), float(opt["epsilon"])
    alpha_t = float(opt["alpha"]) * np.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)

    def upd(w, g, m_, v_):
        m2 = b1 * m_ + (1.0 - b1) * g
        v2 = b2 * v_ + (1.0 - b2) * g * g
        return w - alpha_t * m2 / (jnp.sqrt(v2) + eps), m2, v2

    out = jax.tree.map(upd, params, grads, m, v)
    pick = lambda i: jax.tree.map(lambda t3: t3[i], out,
                                  is_leaf=lambda t3: isinstance(t3, tuple))
    return pick(0), pick(1), pick(2)


def train_steps(params0, x, y, cfg: Dict, opt: Dict, steps: int,
                prec: str = "float32", block_rows: int = 4,
                keep_rows=None) -> Dict:
    """Follow `steps` optimizer steps from `params0` over x (steps, B, L)
    and y (steps, B, L): gradients accumulated over blocks of rows so the
    f32 activations fit beside the program's leftovers. `keep_rows`: a
    planted fault — the batch rows that are used (the mean is over them).
    Returns per-step losses, Adam's first moment after the last step (the
    tree, on the device) and per-leaf norms: the first gradient, that
    moment, and the parameters' change."""
    weights_bf16 = prec == "bf16_weights"
    if weights_bf16:
        prec = "float32"
    steps_, batch, _seq = x.shape
    assert steps_ >= steps
    rows = np.arange(batch) if keep_rows is None else np.asarray(keep_rows)
    block_rows = min(block_rows, len(rows))
    assert len(rows) % block_rows == 0, (len(rows), block_rows)
    n_tok = float(len(rows) * x.shape[2])

    grad_block = jax.jit(jax.value_and_grad(
        lambda p, xb, yb: loss_sum(p, xb, yb, cfg, prec)))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)
    scale = jax.jit(lambda g: jax.tree.map(
        lambda a: a.astype(jnp.float32) / n_tok, g), donate_argnums=0)
    step_fn = jax.jit(lambda p, g, m, v, t: adam_step(p, g, m, v, t, opt),
                      static_argnums=4, donate_argnums=(0, 2, 3))

    # what is stored between steps: float32, or (the control) bf16
    # (reduce_precision, not a cast there and back: the TPU compiler may drop
    # such a pair as "excess precision", and the control then reads 0)
    keep = jax.jit(lambda p: jax.tree.map(
        lambda a: jax.lax.reduce_precision(a.astype(jnp.float32), 8, 7)
        if weights_bf16 else a.astype(jnp.float32) + 0.0, p))
    params = keep(params0)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, g1_norms = [], None
    for s in range(steps):
        acc, tot = None, 0.0
        for b0 in range(0, len(rows), block_rows):
            idx = rows[b0:b0 + block_rows]
            val, g = grad_block(params, jnp.asarray(x[s, idx]),
                                jnp.asarray(y[s, idx]))
            g = jax.tree.map(lambda a: a.astype(jnp.float32), g)
            acc = g if acc is None else add(acc, g)
            tot += float(val)
        grads = scale(acc)
        losses.append(tot / n_tok)
        if s == 0:
            g1_norms = leaf_norms(grads)
        params, m, v = step_fn(params, grads, m, v, s + 1)
        if weights_bf16:
            params = keep(params)
    delta = jax.jit(lambda a, b: jax.tree.map(
        lambda p1, p0: p1 - p0.astype(jnp.float32), a, b))(params, params0)
    return {"losses": losses, "grad1_norms": g1_norms, "moments": m,
            "moment_norms": leaf_norms(m), "delta_norms": leaf_norms(delta)}
