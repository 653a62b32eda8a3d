"""Plain reference of a latent-attention, sparse-expert causal language
model as ONE CHIP'S SHARE of an expert-parallel deployment holds it
(`configs/mistral_small4_ep4.json` has the layer's equations and their
source). Float32 `jax.numpy` at `highest` precision, expanded attention
only (every row's per-head keys and values are materialised), no cache, no
chunks, no slots, every local expert applied to every token under a mask.
Nothing is imported from flexflow_tpu.

    h = x + MLA(RMSNorm(x));  y = h + MoE(RMSNorm(h));  final RMSNorm; head

The weights come ONE GROUP AT A TIME from a callable (`group(name)`: "emb",
"l0" .. "l<n-1>", "head"), bf16-valued, and are upcast here: all of them in
float32 at once are more than the chip holds. So the loop runs layers
outside and requests inside.

What is compared, per served position: the gap, in logits, by which the
served token lies below the reference's best token. Beside it the
reference's OWN router margin at that position: the least, over the
layers, of (4th - 5th largest router logit). A bf16 hidden state against a
float32 one flips the last chosen expert where that margin is a rounding
error wide, and a flip moves the token's logits far more than rounding
does; `fragile` positions (margin under a stated threshold) are set aside
by the comparison, by the reference's margins alone.

`prec` of a forward pass:
  "float32"   THE reference
  "bfloat16"  operands and stored activations in bf16 (what the
              configuration states; read for information)
  "fp8"       matmul operands rounded to float8_e4m3, bf16 activations: the
              control, the nearest precision below the configuration's
  "fp8_kv"    float32 throughout, but the latent rows a cache would hold
              are rounded to float8_e4m3: the second control
A control's token at a position is the one ITS forward pass puts first
there (same prompt and served tokens fed).
"""
from __future__ import annotations

import functools
import json
import math
from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import plain

QUERY_BLOCK = 512


# -- rotary positions (YaRN, interleaved pairs) --------------------------------
def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def inv_freq(dim: int, rp: Dict) -> np.ndarray:
    theta = float(rp["rope_theta"])
    extra = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rp.get("rope_type") != "yarn":
        return extra
    factor, ctx = float(rp["factor"]), int(
        rp["original_max_position_embeddings"])

    def corr(turns):
        return dim * math.log(ctx / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(corr(float(rp["beta_fast"]))), 0)
    high = min(math.ceil(corr(float(rp["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return extra / factor * (1.0 - mask) + extra * mask


def rope_tables(positions, dim: int, rp: Dict):
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        inv_freq(dim, rp), jnp.float32)
    m = 1.0
    if rp.get("rope_type") == "yarn":
        m = (_yarn_mscale(float(rp["factor"]), float(rp.get("mscale", 1)))
             / _yarn_mscale(float(rp["factor"]),
                            float(rp.get("mscale_all_dim", 0))))
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def rotate(x, cos, sin):
    """Pairs (x[2j], x[2j+1]) rotated; x (..., T, dim) or (T, H, dim) with
    cos / sin broadcast by the caller."""
    xf = x.astype(jnp.float32)
    a, b = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(xf.shape).astype(x.dtype)


def softmax_scale(cfg: Dict) -> float:
    rp = cfg["rope_parameters"]
    scale = float(cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    if rp.get("rope_type") == "yarn" and rp.get("mscale_all_dim"):
        m = _yarn_mscale(float(rp["factor"]), float(rp["mscale_all_dim"]))
        scale *= m * m
    return scale


# -- blocks ------------------------------------------------------------------
def _mm(a, b, spec, prec):
    return plain.mm(a, b, spec, "float32" if prec == "fp8_kv" else prec)


def _dt(prec):
    return plain.act_dtype("float32" if prec == "fp8_kv" else prec)


def rms_norm(x, gamma, eps, prec):
    xf = x.astype(jnp.float32)
    y = xf / jnp.sqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (y * gamma.astype(jnp.float32)).astype(_dt(prec))


def _fp8_store(x):
    """What a float8_e4m3 cache would give back (one scale per tensor)."""
    return jax.lax.stop_gradient(plain._fp8(x)).astype(x.dtype)


def latent_attention(x, p, cfg: Dict, prec: str):
    """x (T, E) at positions 0..T-1 -> (T, E), causal."""
    heads = int(cfg["num_attention_heads"])
    kvr = int(cfg["kv_lora_rank"])
    nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    eps = float(cfg["rms_norm_eps"])
    rp = cfg["rope_parameters"]
    t = x.shape[0]
    pos = jnp.arange(t)
    cos, sin = rope_tables(pos, rope, rp)
    c_q = rms_norm(_mm(x, p["wq_a"], "te,er->tr", prec), p["q_norm"], eps,
                   prec)
    q = _mm(c_q, p["wq_b"], "tr,rhd->thd", prec)
    q_nope = q[..., :nope]
    q_rope = rotate(q[..., nope:], cos[:, None, :], sin[:, None, :])
    kv = _mm(x, p["wkv_a"], "te,er->tr", prec)
    c_kv = rms_norm(kv[..., :kvr], p["kv_norm"], eps, prec)
    k_r = rotate(kv[..., kvr:], cos, sin)
    if prec == "fp8_kv":
        c_kv, k_r = _fp8_store(c_kv), _fp8_store(k_r)
    kvb = _mm(c_kv, p["wkv_b"], "tc,chd->thd", prec)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    scale = softmax_scale(cfg)
    beta = rp.get("llama_4_scaling_beta")
    a_t = jnp.ones((t,), jnp.float32)
    if beta:
        a_t = 1.0 + float(beta) * jnp.log1p(jnp.floor(
            pos / int(rp["original_max_position_embeddings"])).astype(
                jnp.float32))
    heads_v = v.shape[-1]

    def block(args):
        qn, qr, qpos, a = args          # (Q, H, .), (Q,), (Q,)
        s = (_mm(qn, k_nope, "qhn,khn->hqk", prec).astype(jnp.float32)
             + _mm(qr, k_r, "qhr,kr->hqk", prec).astype(jnp.float32))
        s = s * scale * a[None, :, None]
        s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s, -1e30)
        s = s - jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s)
        pr = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(_dt(prec))
        return _mm(pr, v, "hqk,khv->qhv", prec)

    qb = min(QUERY_BLOCK, t)
    if t % qb:
        raise ValueError(f"reference: length {t} is no multiple of {qb}")
    split = lambda z: z.reshape((t // qb, qb) + z.shape[1:])
    o = jax.lax.map(block, (split(q_nope), split(q_rope), split(pos),
                            split(a_t)))
    o = o.reshape(t, heads, heads_v)
    return _mm(o, p["wo"], "thv,hve->te", prec)


def gated_mlp(x, wg, wu, wd, prec):
    g = _mm(x, wg, "te,ef->tf", prec).astype(jnp.float32)
    u = _mm(x, wu, "te,ef->tf", prec).astype(jnp.float32)
    h = (g * jax.nn.sigmoid(g) * u).astype(_dt(prec))
    return _mm(h, wd, "tf,fe->te", prec)


def router_logits(x, w, prec: str):
    """(T, n) float32: accumulated in float32 in every precision; a lower
    precision rounds the OPERANDS."""
    if prec in ("float32", "fp8_kv"):
        return plain.mm(x, w, "te,en->tn", "float32")
    if prec == "fp8":
        x, w = plain._fp8(x), plain._fp8(w)
    return jnp.einsum("te,en->tn", x.astype(jnp.bfloat16),
                      w.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def moe(x, lp, name: str, cfg: Dict, prec: str):
    """x (T, E) -> (routed part of the experts held here + shared expert
    (T, E), router margin (T,): k-th minus (k+1)-th largest logit)."""
    k = int(cfg["num_experts_per_tok"])
    first = int(cfg.get("first_local_expert", 0))
    logits = router_logits(x, lp[f"{name}_router"]["kernel"], prec)
    top, idx = jax.lax.top_k(logits, k + 1)
    margin = top[:, k - 1] - top[:, k]
    w = jax.nn.softmax(top[:, :k], axis=-1) * float(
        cfg.get("routed_scaling_factor", 1))
    idx = idx[:, :k]
    ex = lp[f"{name}_experts"]

    def one(acc, args):
        e, wg, wu, wd = args
        gate = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        y = gated_mlp(x, wg, wu, wd, prec).astype(jnp.float32)
        return acc + gate[:, None] * y, None

    n_local = ex["w_gate"].shape[0]
    routed, _ = jax.lax.scan(
        one, jnp.zeros(x.shape, jnp.float32),
        (jnp.arange(n_local), ex["w_gate"], ex["w_up"], ex["w_down"]))
    shared = gated_mlp(x, lp[f"{name}_shared_gate"]["kernel"],
                       lp[f"{name}_shared_up"]["kernel"],
                       lp[f"{name}_shared_down"]["kernel"], prec)
    return (routed + shared.astype(jnp.float32)).astype(_dt(prec)), margin


def layer(x, lp, i: int, cfg: Dict, prec: str):
    """One pre-norm block on x (T, E) -> (x', router margin (T,))."""
    eps = float(cfg["rms_norm_eps"])
    n = f"l{i}"
    h = x + latent_attention(
        rms_norm(x, lp[f"{n}_ln1"]["gamma"], eps, prec), lp[f"{n}_attn"],
        cfg, prec).astype(x.dtype)
    y, margin = moe(rms_norm(h, lp[f"{n}_ln2"]["gamma"], eps, prec), lp, n,
                    cfg, prec)
    return h + y.astype(x.dtype), margin


def head(x, hp, cfg: Dict, prec: str):
    x = rms_norm(x, hp["final_norm"]["gamma"], float(cfg["rms_norm_eps"]),
                 prec)
    return _mm(x, hp["lm_head"]["kernel"], "te,ev->tv", prec).astype(
        jnp.float32)


# -- the comparison ------------------------------------------------------------
_KEYS = ("num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
         "qk_rope_head_dim", "rms_norm_eps", "rope_parameters",
         "num_experts_per_tok", "routed_scaling_factor",
         "first_local_expert")


def _cfg_key(cfg: Dict) -> str:
    return json.dumps({k: cfg[k] for k in _KEYS if k in cfg}, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _layer_fn(cfg_key: str, prec: str):
    cfg = json.loads(cfg_key)
    # the layer's NAMES carry its number; one program serves every layer
    # because the weights arrive renamed to layer 0
    return jax.jit(lambda lp, x: layer(x, lp, 0, cfg, prec))


@functools.lru_cache(maxsize=None)
def _head_fn(cfg_key: str, prec: str):
    cfg = json.loads(cfg_key)
    return jax.jit(lambda hp, x: head(x, hp, cfg, prec))


def _as_layer0(lp: Dict, i: int) -> Dict:
    return {"l0" + k[len(f"l{i}"):]: v for k, v in lp.items()}


def forward(group: Callable[[str], Dict], cfg: Dict,
            tokens: Sequence[np.ndarray], precs: Sequence[str],
            rows: Sequence[np.ndarray] = None):
    """tokens: R arrays (P,) of one padded length. Returns {prec: [R
    arrays (P, V) of float32 logits]} and, from the float32 pass, [R arrays
    (P,)] of the least router margin over the layers. `rows`: per request
    the positions whose logits are wanted, (len(rows[r]), V) each: R whole
    (P, V) arrays at the cell's size are a third of the chip."""
    key = _cfg_key(cfg)
    emb = group("emb")["emb"]["weight"]
    xs = {p: [emb[jnp.asarray(t)].astype(_dt(p)) for t in tokens]
          for p in precs}
    margins = [None] * len(tokens)
    del emb
    for i in range(int(cfg["num_hidden_layers"])):
        lp = _as_layer0(group(f"l{i}"), i)
        for p in precs:
            fn = _layer_fn(key, p)
            for r in range(len(tokens)):
                xs[p][r], m = fn(lp, xs[p][r])
                if p == "float32":
                    margins[r] = m if margins[r] is None else jnp.minimum(
                        margins[r], m)
        del lp
    hp = group("head")
    if rows is None:
        rows = [np.arange(len(t)) for t in tokens]
    # whole query blocks of rows (padded with row 0), so the head compiles
    # for a few shapes
    padded = [np.concatenate([rw, np.zeros(-len(rw) % QUERY_BLOCK, rw.dtype)])
              for rw in rows]
    logits = {p: [_head_fn(key, p)(hp, x[jnp.asarray(pad)])[:len(rw)]
                  for x, pad, rw in zip(xs[p], padded, rows)] for p in precs}
    return logits, margins


def pad_length(longest: int, cap: int) -> int:
    """The smallest power-of-two multiple of QUERY_BLOCK that holds
    `longest` (so a run compiles one shape, and all runs a few), and never
    more than `cap` where that holds it too (a test's short `max_len`)."""
    n = QUERY_BLOCK
    while n < longest:
        n *= 2
    return min(n, max(cap, longest))


def served_gaps(group: Callable[[str], Dict], cfg: Dict,
                prompts: Sequence[np.ndarray], served: Sequence[np.ndarray],
                pad_to: int, controls: Sequence[str] = ()) -> Dict:
    """{"program": [per request (n_out,) gaps of the served tokens],
    <control>: [gaps of that control's own first tokens],
    "margin": [per request (n_out,) least router margin at the position
    that predicts each served token]}."""
    toks, spans = [], []
    for p, s in zip(prompts, served):
        n = len(p) + len(s)
        if n > pad_to:
            raise ValueError(f"request of {n} tokens exceeds pad_to={pad_to}")
        t = np.zeros((pad_to,), np.int32)
        t[:len(p)] = p
        t[len(p):n] = s
        toks.append(t)
        spans.append((len(p), len(s)))
    precs = ["float32"] + [c for c in controls if c != "float32"]
    # logits at position first-1+j predict output token j
    at = [np.arange(first - 1, first - 1 + n_out) for first, n_out in spans]
    logits, margins = forward(group, cfg, toks, precs, rows=at)
    out: Dict[str, List[np.ndarray]] = {k: [] for k in
                                        ["program", "margin", *controls]}
    for r, (rows, (_first, n_out)) in enumerate(zip(at, spans)):
        z = np.asarray(logits["float32"][r])
        best = z.max(axis=-1)
        out["program"].append(best - z[np.arange(n_out), toks[r][rows + 1]])
        out["margin"].append(np.asarray(margins[r])[rows])
        for c in controls:
            pick = np.asarray(jnp.argmax(logits[c][r], axis=-1))
            out[c].append(best - z[np.arange(n_out), pick])
    return out
