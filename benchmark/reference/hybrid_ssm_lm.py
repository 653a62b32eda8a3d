"""Plain reference of a hybrid causal language model whose every block runs
a Mamba-2 state-space mixer and grouped-KV rotary attention SIDE BY SIDE on
one normed input (`configs/falcon_h1_34b_1chip.json` has the block's
equations and their source). Float32 `jax.numpy` at `highest` precision; the
recurrence is a sequential `lax.scan` over tokens (no blocks, no carried
chunks), attention materialises every row's keys and values (no cache), one
request at a time (no slots). Nothing is imported from flexflow_tpu.

    x0 = E[ids] * embedding_multiplier
    h = RMSNorm(x);  x = x + ssm_out * Mixer(ssm_in * h) + attn_out * Attn(attn_in * h)
    x = x + MLP(RMSNorm(x));   logits = (RMSNorm(x_L) W_head) * lm_head_multiplier

The weights come ONE GROUP AT A TIME from a callable (`group(name)`: "emb",
"l0" .. "l<n-1>", "head"), bf16-valued, and are upcast here: the embedding
and the head alone are 5.3 GB in float32. So the loop runs layers outside
and requests inside, and the head is applied to the served positions alone,
a request at a time (a request's float32 logits are up to 1 GB).

What is compared, per served position: the gap, in logits, by which the
served token lies below the reference's best token.

`prec` of a forward pass:
  "float32"     THE reference
  "bfloat16"    operands and stored activations in bf16, the recurrent state
                in the configuration's `ssm_state_dtype` (what the
                configuration states; read for information)
  "fp8"         matmul operands rounded to float8_e4m3, bf16 activations,
                the state as stated: the control, the nearest precision
                below the configuration's
  "bf16_state"  float32 throughout, but the recurrent state is rounded to
                bf16 after every token (the recurrence carries that rounding
                through every later token): what the state's type alone
                costs
and two FAULTS of a serving system, put in the program's place the same way:
  "fault_no_reset"  every sequence starts from the state and convolution
                tail the same text left behind (a reused slot whose state
                admission did not reset), float32 otherwise
  "fault_no_mixer"  the mixer's branch is left out of every block
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

QUERY_BLOCK = 512
_F32_MODES = ("float32", "bf16_state", "fault_no_reset", "fault_no_mixer")


def _base(prec: str) -> str:
    """The precision of everything but what a mode singles out."""
    return "float32" if prec in _F32_MODES else prec


def _mm(a, b, spec, prec):
    return plain.mm(a, b, spec, _base(prec))


def _dt(prec):
    return plain.act_dtype(_base(prec))


def rms_norm(x, gamma, eps, prec):
    xf = x.astype(jnp.float32)
    y = xf / jnp.sqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (y * gamma.astype(jnp.float32)).astype(_dt(prec))


def silu(x):
    xf = x.astype(jnp.float32)
    return xf * jax.nn.sigmoid(xf)


# -- attention: grouped KV heads, rotary positions on half-split pairs --------
def rope_tables(positions, dim: int, theta: float):
    inv = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    return jnp.cos(ang), jnp.sin(ang)


def rotate(x, cos, sin):
    """x (T, heads, dim), pairs (x[j], x[j + dim/2]); cos / sin (T, dim/2)."""
    xf = x.astype(jnp.float32)
    half = xf.shape[-1] // 2
    a, b = xf[..., :half], xf[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * c - b * s, a * s + b * c],
                           axis=-1).astype(x.dtype)


def attention(u, p, cfg: Dict, prec: str):
    """u (T, E) at positions 0..T-1 -> (T, E), causal; query head i reads
    KV head i // (heads / kv_heads)."""
    heads = int(cfg["num_attention_heads"])
    kvh = int(cfg["num_key_value_heads"])
    d = int(cfg["head_dim"])
    t = u.shape[0]
    pos = jnp.arange(t)
    cos, sin = rope_tables(pos, d, float(cfg["rope_theta"]))
    q = rotate(_mm(u, p["wq"], "te,ehd->thd", prec), cos, sin)
    k = _mm(u, p["wk"], "te,ehd->thd", prec)
    k = rotate((k.astype(jnp.float32) * float(cfg["key_multiplier"])).astype(
        k.dtype), cos, sin)
    v = _mm(u, p["wv"], "te,ehd->thd", prec)
    g = heads // kvh
    scale = 1.0 / np.sqrt(d)

    def block(args):
        qb, qpos = args                                 # (Q, h, d), (Q,)
        qg = qb.reshape(qb.shape[0], kvh, g, d)
        s = _mm(qg, k, "qngd,knd->ngqk", prec).astype(jnp.float32) * scale
        s = jnp.where(pos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -1e30)
        s = s - jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s)
        pr = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(_dt(prec))
        return _mm(pr, v, "ngqk,knd->qngd", prec).reshape(-1, heads, d)

    qb = min(QUERY_BLOCK, t)
    if t % qb:
        raise ValueError(f"reference: length {t} is no multiple of {qb}")
    split = lambda z: z.reshape((t // qb, qb) + z.shape[1:])
    o = jax.lax.map(block, (split(q), split(pos))).reshape(t, heads, d)
    return _mm(o, p["wo"], "thd,hde->te", prec)


# -- the mixer: a sequential recurrence over tokens -------------------------------
def mixer(u, p, cfg: Dict, prec: str, start=None):
    """u (T, E) -> (out (T, E), (state after the last token (H, P, N), the
    last d_conv - 1 rows of xBC)). `start`: the (state, tail) the sequence
    comes in with; None = zeros, which is what a sequence starts from."""
    d_ssm, heads = int(cfg["mamba_d_ssm"]), int(cfg["mamba_n_heads"])
    groups, n = int(cfg["mamba_n_groups"]), int(cfg["mamba_d_state"])
    hd, k = int(cfg["mamba_d_head"]), int(cfg["mamba_d_conv"])
    conv_dim = d_ssm + 2 * groups * n
    t = u.shape[0]
    f32 = jnp.float32
    proj = _mm(u, p["w_in"], "te,ew->tw", prec).astype(f32)
    sizes = (d_ssm, d_ssm, groups * n, groups * n, heads)
    proj = proj * jnp.asarray(np.repeat(
        np.asarray(cfg["ssm_multipliers"], np.float32), sizes))
    z = proj[:, :d_ssm]
    xbc = proj[:, d_ssm:d_ssm + conv_dim].astype(_dt(prec))
    dt = jax.nn.softplus(proj[:, d_ssm + conv_dim:]
                         + p["dt_bias"].astype(f32))              # (T, H)
    tail0 = jnp.zeros((k - 1, conv_dim), xbc.dtype) if start is None \
        else start[1].astype(xbc.dtype)
    window = jnp.concatenate([tail0, xbc], axis=0)                # (T+k-1, C)
    w = p["conv_w"].astype(f32)
    acc = sum(window[j:j + t].astype(f32) * w[j] for j in range(k))
    xbc_c = silu(acc + p["conv_b"].astype(f32)).astype(_dt(prec))
    xs = xbc_c[:, :d_ssm].reshape(t, heads, hd).astype(f32)
    bm = xbc_c[:, d_ssm:d_ssm + groups * n].reshape(t, groups, n).astype(f32)
    cm = xbc_c[:, d_ssm + groups * n:].reshape(t, groups, n).astype(f32)
    a = -jnp.exp(p["A_log"].astype(f32))                          # (H,)
    per = heads // groups
    bh = jnp.repeat(bm, per, axis=1)                              # (T, H, N)
    ch = jnp.repeat(cm, per, axis=1)

    def step(h, args):
        x_t, b_t, c_t, dt_t = args
        h = (jnp.exp(dt_t * a)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        if round_state:     # reduce_precision: a cast there and back is
            # dropped by the TPU compiler as excess precision
            h = jax.lax.reduce_precision(h, exponent_bits=8, mantissa_bits=7)
        y_t = jnp.einsum("hpn,hn->hp", h, c_t,
                         precision=jax.lax.Precision.HIGHEST)
        return h, y_t

    round_state = prec == "bf16_state" or (
        prec in ("bfloat16", "fp8")
        and cfg.get("ssm_state_dtype") == "bfloat16")
    h0 = jnp.zeros((heads, hd, n), f32) if start is None else start[0]
    h_last, y = jax.lax.scan(step, h0, (xs, bh, ch, dt))
    y = y + p["D"].astype(f32)[None, :, None] * xs
    y = y.reshape(t, d_ssm) * silu(z)
    yg = y.reshape(t, groups, d_ssm // groups)
    yg = yg / jnp.sqrt(jnp.mean(jnp.square(yg), axis=-1, keepdims=True)
                       + float(cfg["rms_norm_eps"]))
    y = (yg.reshape(t, d_ssm) * p["norm"].astype(f32)).astype(_dt(prec))
    return (_mm(y, p["w_out"], "td,de->te", prec),
            (h_last, window[t:t + k - 1]))


def gated_mlp(x, lp, name: str, cfg: Dict, prec: str):
    m_gate, m_down = (float(m) for m in cfg["mlp_multipliers"])
    g = _mm(x, lp[f"{name}_gate"]["kernel"], "te,ef->tf", prec).astype(
        jnp.float32) * m_gate
    up = _mm(x, lp[f"{name}_up"]["kernel"], "te,ef->tf", prec).astype(
        jnp.float32)
    h = (silu(g) * up).astype(_dt(prec))
    y = _mm(h, lp[f"{name}_down"]["kernel"], "tf,fe->te", prec)
    return (y.astype(jnp.float32) * m_down).astype(_dt(prec))


def layer(x, lp, i: int, cfg: Dict, prec: str):
    """One block on x (T, E) -> x'."""
    eps = float(cfg["rms_norm_eps"])
    n = f"l{i}"
    f32 = jnp.float32
    h = rms_norm(x, lp[f"{n}_ln1"]["gamma"], eps, prec)
    scaled = lambda t, m: (t.astype(f32) * float(m)).astype(_dt(prec))
    branch = scaled(attention(scaled(h, cfg["attention_in_multiplier"]),
                              lp[f"{n}_attn"], cfg, prec),
                    cfg["attention_out_multiplier"]).astype(f32)
    if prec != "fault_no_mixer":
        u = scaled(h, cfg["ssm_in_multiplier"])
        start = None
        if prec == "fault_no_reset":
            _, start = mixer(u, lp[f"{n}_mixer"], cfg, prec)
        m, _ = mixer(u, lp[f"{n}_mixer"], cfg, prec, start)
        branch = branch + scaled(m, cfg["ssm_out_multiplier"]).astype(f32)
    x = (x.astype(f32) + branch).astype(_dt(prec))
    y = gated_mlp(rms_norm(x, lp[f"{n}_ln2"]["gamma"], eps, prec), lp,
                  f"{n}_mlp", cfg, prec)
    return (x.astype(f32) + y.astype(f32)).astype(_dt(prec))


def head(x, hp, cfg: Dict, prec: str):
    x = rms_norm(x, hp["final_norm"]["gamma"], float(cfg["rms_norm_eps"]),
                 prec)
    z = _mm(x, hp["lm_head"]["kernel"], "te,ev->tv", prec)
    return z.astype(jnp.float32) * float(cfg["lm_head_multiplier"])


# -- the comparison ------------------------------------------------------------
_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
         "rope_theta", "key_multiplier", "attention_in_multiplier",
         "attention_out_multiplier", "mamba_d_ssm", "mamba_n_heads",
         "mamba_n_groups", "mamba_d_state", "mamba_d_head", "mamba_d_conv",
         "ssm_multipliers", "ssm_in_multiplier", "ssm_out_multiplier",
         "mlp_multipliers", "rms_norm_eps", "lm_head_multiplier",
         "embedding_multiplier")


def _cfg_key(cfg: Dict) -> str:
    return json.dumps({**{k: cfg[k] for k in _KEYS},
                       "ssm_state_dtype": cfg.get("ssm_state_dtype",
                                                  "float32")}, sort_keys=True)


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


def hidden_states(group: Callable[[str], Dict], cfg: Dict,
                  tokens: Sequence[np.ndarray], precs: Sequence[str]):
    """tokens: R arrays (P,) of one padded length. Returns {prec: [R arrays
    (P, E)]}: the stream after the last block."""
    key = _cfg_key(cfg)
    emb = group("emb")["emb"]["weight"]
    m = float(cfg["embedding_multiplier"])
    xs = {p: [(emb[jnp.asarray(t)].astype(jnp.float32) * m).astype(_dt(p))
              for t in tokens] for p in precs}
    del emb
    for i in range(int(cfg["num_hidden_layers"])):
        lp = _as_layer0(group(f"l{i}"), i)
        for p in precs:
            fn = _layer_fn(key, p)
            for r in range(len(tokens)):
                xs[p][r] = fn(lp, xs[p][r])
        del lp
    return xs


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
    <control>: [gaps of that control's own first tokens]}."""
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
    xs = hidden_states(group, cfg, toks, precs)
    hp = group("head")
    key = _cfg_key(cfg)
    out: Dict[str, List[np.ndarray]] = {k: [] for k in ["program", *controls]}
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
        for c in controls:
            zc = z if c == "float32" else _head_fn(key, c)(
                hp, xs[c][r][jnp.asarray(pad)])[:n_out]
            out[c].append(at(jnp.argmax(zc, axis=-1)))
            del zc
        del z
    return out
