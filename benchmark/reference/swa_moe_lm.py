"""Plain reference of a causal language model whose layers mix WINDOW and
FULL grouped-KV rotary attention of differing head counts, a per-head gate
on every attention, and sparse experts chosen by a sigmoid-scored router
beside one shared expert (`configs/laguna_xs2_1chip.json` has the layer's
equations and their source). Float32 `jax.numpy` at `highest` precision; a
window is a band mask over the whole sequence (no cache, no ring, no
chunks, no slots); a token goes through its k chosen experts only
(assignments sorted by expert and multiplied block by block: every row
through all 256 experts would be 19 TFLOP a layer in float32). Nothing is
imported from flexflow_tpu.

    x0 = E[ids]
    h = RMSNorm(x);  x = x + Attn_l(h);  u = RMSNorm(x);  x = x + MLP_l(u)
    logits = RMSNorm(x_L) W_head

The weights come ONE GROUP AT A TIME from a callable (`group(name)`: "emb",
"l0" .. "l<n-1>", "head"), bf16-valued, and are upcast where they are
multiplied: a layer's experts alone are 3.2 GB in float32. So the loop runs
layers outside and requests inside, and the head is applied to the served
positions alone.

What is compared, per served position: the gap, in logits, by which the
served token lies below the reference's best token. Beside it the
reference's OWN router margin at that position: the least, over the expert
layers, of (k-th minus (k+1)-th largest router logit). A bf16 hidden state
against a float32 one flips the last chosen expert where that margin is a
rounding error wide; `fragile` positions (margin under a stated threshold)
are set aside by the comparison, by the reference's margins alone.

`prec` of a forward pass:
  "float32"   THE reference
  "bfloat16"  operands and stored activations in bf16 (what the
              configuration states; read for information)
  "fp8"       matmul operands rounded to float8_e4m3, bf16 activations: the
              control, the nearest precision below the configuration's
and two FAULTS of a serving system, put in the program's place the same way
(float32 otherwise):
  "fault_no_window"   the window layers attend every earlier position
  "fault_stale_ring"  a window layer's query also sees the ring rows its
              sequence has not written yet, holding what a previous tenant
              of the slot (the same text) left there: row j holds that
              text's last position congruent to j mod `ring_rows`
A control's or fault's token at a position is the one ITS forward pass puts
first there (same prompt and served tokens fed).
"""
from __future__ import annotations

import functools
import json
from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import plain
# pure helpers that do not depend on the architecture: YaRN's frequency
# table, a layer's weights renamed to layer 0, the padded length
from .mla_moe_lm import _as_layer0, inv_freq, pad_length  # noqa: F401

QUERY_BLOCK = 512
EXPERT_BLOCK = 256      # sorted assignments multiplied at a time
_F32_MODES = ("float32", "fault_no_window", "fault_stale_ring")


def _base(prec: str) -> str:
    return "float32" if prec in _F32_MODES else prec


def _mm(a, b, spec, prec):
    return plain.mm(a, b, spec, _base(prec))


def _dt(prec):
    return plain.act_dtype(_base(prec))


def rms_norm(x, gamma, eps, prec):
    xf = x.astype(jnp.float32)
    y = xf / jnp.sqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (y * gamma.astype(jnp.float32)).astype(_dt(prec))


# -- rotary positions: default and YaRN tables, a part of the head rotated ----
def rope_tables(positions, head_dim: int, rp: Dict):
    """cos, sin (T, rot/2) with rot = partial_rotary_factor * head_dim, times
    YaRN's `attention_factor` where the group is YaRN's."""
    rot = int(head_dim * float(rp.get("partial_rotary_factor", 1.0)))
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        inv_freq(rot, rp), jnp.float32)
    m = float(rp["attention_factor"]) if rp.get("rope_type") == "yarn" else 1.0
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def rotate(x, cos, sin):
    """x (T, heads, dim): the leading 2 * (cos's width) values of a head
    rotated as pairs (x[j], x[j + width]), the rest passed through."""
    xf = x.astype(jnp.float32)
    half = cos.shape[-1]
    a, b, rest = xf[..., :half], xf[..., half:2 * half], xf[..., 2 * half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * c - b * s, a * s + b * c, rest],
                           axis=-1).astype(x.dtype)


def attention(h, p, kind: str, cfg: Dict, prec: str, length=None):
    """h (T, E) at positions 0..T-1 -> (T, E). `kind`: "full_attention"
    (causal) or "sliding_attention" (key s visible to query t iff
    0 <= t - s < sliding_window); the head count is the weights'. `length`:
    the text's real tokens (the stale-ring fault alone reads it)."""
    kvh, d = int(cfg["num_key_value_heads"]), int(cfg["head_dim"])
    heads = p["wq"].shape[1]
    window = int(cfg["sliding_window"])
    t = h.shape[0]
    pos = jnp.arange(t)
    cos, sin = rope_tables(pos, d, cfg["rope_parameters"][kind])
    q = rotate(_mm(h, p["wq"], "te,ehd->thd", prec), cos, sin)
    k = rotate(_mm(h, p["wk"], "te,ehd->thd", prec), cos, sin)
    v = _mm(h, p["wv"], "te,ehd->thd", prec)
    g = heads // kvh
    scale = 1.0 / np.sqrt(d)
    banded = kind == "sliding_attention" and prec != "fault_no_window"

    def visible(qpos):                                  # (Q,) -> (Q, T)
        behind = qpos[:, None] - pos[None, :]
        see = behind >= 0
        if banded:
            see &= behind < window
        if kind == "sliding_attention" and prec == "fault_stale_ring":
            r = int(cfg["ring_rows"])
            last = length - 1
            # key s is what the previous tenant left in row s mod r, and
            # the new sequence has not reached that row yet
            stale = ((pos > last - r) & (pos <= last))[None, :] & (
                (pos % r)[None, :] > qpos[:, None])
            see |= stale
        return see

    def block(args):
        qb, qpos = args                                 # (Q, h, d), (Q,)
        qg = qb.reshape(qb.shape[0], kvh, g, d)
        s = _mm(qg, k, "qngd,knd->ngqk", prec).astype(jnp.float32) * scale
        s = jnp.where(visible(qpos)[None, None], s, -1e30)
        s = s - jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s)
        pr = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(_dt(prec))
        return _mm(pr, v, "ngqk,knd->qngd", prec).reshape(-1, heads, d)

    qb = min(QUERY_BLOCK, t)
    if t % qb:
        raise ValueError(f"reference: length {t} is no multiple of {qb}")
    split = lambda z: z.reshape((t // qb, qb) + z.shape[1:])
    o = jax.lax.map(block, (split(q), split(pos))).reshape(t, heads, d)
    gate = jax.nn.sigmoid(
        _mm(h, p["wg"], "te,eh->th", prec).astype(jnp.float32))
    o = (o.astype(jnp.float32) * gate[:, :, None]).astype(_dt(prec))
    return _mm(o, p["wo"], "thd,hde->te", prec)


# -- the MLPs -----------------------------------------------------------------
def gated_mlp(x, wg, wu, wd, prec):
    g = _mm(x, wg, "te,ef->tf", prec).astype(jnp.float32)
    u = _mm(x, wu, "te,ef->tf", prec).astype(jnp.float32)
    h = (g * jax.nn.sigmoid(g) * u).astype(_dt(prec))
    return _mm(h, wd, "tf,fe->te", prec)


def router_logits(x, w, prec: str):
    """(T, n) float32: accumulated in float32 in every precision; a lower
    precision rounds the OPERANDS."""
    if _base(prec) == "float32":
        return plain.mm(x, w, "te,en->tn", "float32")
    if prec == "fp8":
        x, w = plain._fp8(x), plain._fp8(w)
    return jnp.einsum("te,en->tn", x.astype(jnp.bfloat16),
                      w.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def route(logits, k: int, scale: float):
    """(weights (T, k) float32, ids (T, k), margin (T,)): the k largest
    sigmoid scores, divided by their sum, times `scale`; the margin is the
    k-th minus the (k+1)-th largest LOGIT."""
    top, idx = jax.lax.top_k(logits, k + 1)
    margin = top[:, k - 1] - top[:, k]
    score = jax.nn.sigmoid(top[:, :k])
    return (score / jnp.sum(score, axis=-1, keepdims=True) * scale,
            idx[:, :k], margin)


def routed_experts(x, w, idx, ex, prec):
    """sum_i w_i E_{id_i}(x) for x (T, E), every token through its own k
    experts: the T*k assignments sorted by expert, EXPERT_BLOCK sorted rows
    of ONE expert multiplied at a time (an expert's last block is padded
    with rows that weigh nothing)."""
    t, k = idx.shape
    n = ex["w_gate"].shape[0]
    flat = idx.reshape(-1).astype(jnp.int32)
    order = jnp.argsort(flat, stable=True)          # assignment ids, sorted
    sizes = jnp.bincount(flat, length=n)
    first = jnp.cumsum(sizes) - sizes               # an expert's first row
    blocks = -(-sizes // EXPERT_BLOCK)
    ends = jnp.cumsum(blocks)
    total = -(-t * k // EXPERT_BLOCK) + n           # no routing needs more
    wflat = w.reshape(-1).astype(jnp.float32)

    def one(acc, b):
        e = jnp.minimum(jnp.searchsorted(ends, b, side="right"), n - 1)
        j = b - (ends[e] - blocks[e])
        at = first[e] + j * EXPERT_BLOCK + jnp.arange(EXPERT_BLOCK)
        keep = (b < ends[-1]) & (at < first[e] + sizes[e])
        a = order[jnp.clip(at, 0, t * k - 1)]
        y = gated_mlp(x[a // k], ex["w_gate"][e], ex["w_up"][e],
                      ex["w_down"][e], prec).astype(jnp.float32)
        gate = jnp.where(keep, wflat[a], 0.0)
        return acc.at[a // k].add(gate[:, None] * y), None

    out, _ = jax.lax.scan(one, jnp.zeros(x.shape, jnp.float32),
                          jnp.arange(total))
    return out


def moe(u, lp, name: str, cfg: Dict, prec: str):
    """u (T, E) -> (routed experts + shared expert (T, E), router margin
    (T,))."""
    logits = router_logits(u, lp[f"{name}_router"]["kernel"], prec)
    w, idx, margin = route(logits, int(cfg["num_experts_per_tok"]),
                           float(cfg["moe_routed_scaling_factor"]))
    routed = routed_experts(u, w, idx, lp[f"{name}_experts"], prec)
    shared = gated_mlp(u, lp[f"{name}_shared_gate"]["kernel"],
                       lp[f"{name}_shared_up"]["kernel"],
                       lp[f"{name}_shared_down"]["kernel"], prec)
    return (routed + shared.astype(jnp.float32)).astype(_dt(prec)), margin


def layer(x, lp, i: int, kind: str, mlp: str, cfg: Dict, prec: str,
          length=None):
    """One pre-norm block on x (T, E) -> (x', router margin (T,): +inf for
    a dense layer)."""
    eps = float(cfg["rms_norm_eps"])
    n = f"l{i}"
    name = f"{n}_attn" if kind == "full_attention" else f"{n}_swa"
    f32 = jnp.float32
    a = attention(rms_norm(x, lp[f"{n}_ln1"]["gamma"], eps, prec), lp[name],
                  kind, cfg, prec, length)
    x = (x.astype(f32) + a.astype(f32)).astype(_dt(prec))
    u = rms_norm(x, lp[f"{n}_ln2"]["gamma"], eps, prec)
    if mlp == "dense":
        y = gated_mlp(u, lp[f"{n}_mlp_gate"]["kernel"],
                      lp[f"{n}_mlp_up"]["kernel"],
                      lp[f"{n}_mlp_down"]["kernel"], prec)
        margin = jnp.full((x.shape[0],), jnp.inf, f32)
    else:
        y, margin = moe(u, lp, n, cfg, prec)
    return (x.astype(f32) + y.astype(f32)).astype(_dt(prec)), margin


def head(x, hp, cfg: Dict, prec: str):
    x = rms_norm(x, hp["final_norm"]["gamma"], float(cfg["rms_norm_eps"]),
                 prec)
    return _mm(x, hp["lm_head"]["kernel"], "te,ev->tv", prec).astype(
        jnp.float32)


# -- the comparison ------------------------------------------------------------
_KEYS = ("num_key_value_heads", "head_dim", "sliding_window", "ring_rows",
         "rope_parameters", "rms_norm_eps", "num_experts_per_tok",
         "moe_routed_scaling_factor")


def _cfg_key(cfg: Dict) -> str:
    return json.dumps({k: cfg[k] for k in _KEYS if k in cfg}, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _layer_fn(cfg_key: str, kind: str, mlp: str, prec: str):
    cfg = json.loads(cfg_key)
    # the layer's NAMES carry its number; one program serves every layer of
    # a kind because the weights arrive renamed to layer 0
    return jax.jit(lambda lp, x, length: layer(x, lp, 0, kind, mlp, cfg,
                                               prec, length))


@functools.lru_cache(maxsize=None)
def _head_fn(cfg_key: str, prec: str):
    cfg = json.loads(cfg_key)
    return jax.jit(lambda hp, x: head(x, hp, cfg, prec))


def hidden_states(group: Callable[[str], Dict], cfg: Dict,
                  tokens: Sequence[np.ndarray], lengths: Sequence[int],
                  precs: Sequence[str]):
    """tokens: R arrays (P,) of one padded length, `lengths` their real
    tokens. Returns ({prec: [R arrays (P, E)]}: the stream after the last
    block, and from the float32 pass [R arrays (P,)]: the least router
    margin over the expert layers)."""
    key = _cfg_key(cfg)
    emb = group("emb")["emb"]["weight"]
    xs = {p: [emb[jnp.asarray(t)].astype(_dt(p)) for t in tokens]
          for p in precs}
    margins = [None] * len(tokens)
    del emb
    for i in range(int(cfg["num_hidden_layers"])):
        lp = _as_layer0(group(f"l{i}"), i)
        kind, mlp = cfg["layer_types"][i], cfg["mlp_layer_types"][i]
        for p in precs:
            fn = _layer_fn(key, kind, mlp, p)
            for r in range(len(tokens)):
                xs[p][r], m = fn(lp, xs[p][r],
                                 jnp.asarray(lengths[r], jnp.int32))
                if p == "float32":
                    margins[r] = m if margins[r] is None else jnp.minimum(
                        margins[r], m)
        del lp
    return xs, margins


def forward(group: Callable[[str], Dict], cfg: Dict, tokens: np.ndarray,
            prec: str = "float32"):
    """(P, V) float32 logits of ONE sequence (P a multiple of QUERY_BLOCK
    or shorter than it) — the tests' full forward pass."""
    xs, _ = hidden_states(group, cfg, [np.asarray(tokens)], [len(tokens)],
                          [prec])
    return _head_fn(_cfg_key(cfg), prec)(group("head"), xs[prec][0])


def served_gaps(group: Callable[[str], Dict], cfg: Dict,
                prompts: Sequence[np.ndarray], served: Sequence[np.ndarray],
                pad_to: int, controls: Sequence[str] = ()) -> Dict:
    """{"program": [per request (n_out,) gaps of the served tokens],
    <control>: [gaps of that control's or fault's own first tokens],
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
    xs, margins = hidden_states(group, cfg, toks,
                                [first + n_out for first, n_out in spans],
                                precs)
    hp = group("head")
    key = _cfg_key(cfg)
    out: Dict[str, List[np.ndarray]] = {k: [] for k in
                                        ["program", "margin", *controls]}
    for r, (first, n_out) in enumerate(spans):
        # logits at position first-1+j predict output token j; whole query
        # blocks of rows (padded with row 0), so the head compiles for a
        # few shapes
        rows = np.arange(first - 1, first - 1 + n_out)
        pad = np.concatenate([rows, np.zeros(-n_out % QUERY_BLOCK, rows.dtype)])
        z = _head_fn(key, "float32")(hp, xs["float32"][r][jnp.asarray(pad)])
        z = z[:n_out]
        best = jnp.max(z, axis=-1)
        at = lambda pick: np.asarray(best - jnp.take_along_axis(
            z, jnp.asarray(pick)[:, None], axis=-1)[:, 0])
        out["program"].append(at(toks[r][rows + 1]))
        out["margin"].append(np.asarray(margins[r])[rows])
        for c in controls:
            zc = z if c == "float32" else _head_fn(key, c)(
                hp, xs[c][r][jnp.asarray(pad)])[:n_out]
            out[c].append(at(jnp.argmax(zc, axis=-1)))
            del zc
        del z
    return out
