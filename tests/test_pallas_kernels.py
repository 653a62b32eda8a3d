"""Kernels and the one decision that picks them (kernels/registry.py):
the norm/softmax reference lowerings against a float64 numpy oracle,
interpret-mode parity of the Pallas decode kernels against their jnp
reference, `KERNELS.select`'s decision table, simulator pricing, and
token-identical greedy decode through the continuous batcher with the
decode kernels forced.

Tolerances: f32 must match the oracle to float-roundoff (1e-5); bf16 I/O
accumulates in f32 and is compared at bf16 resolution (2e-2 on
normalized outputs).
"""
import contextlib
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.kernels.pallas import (fused_decode_attention,
                                         latent_decode)
from flexflow_tpu.kernels.registry import FLASH_COST_GAIN, KERNELS

F32_TOL = dict(rtol=1e-5, atol=1e-5)
BF16_TOL = dict(rtol=2e-2, atol=2e-2)


def _rand(rng, shape, dtype=np.float32):
    return jnp.asarray(rng.randn(*shape).astype(np.float32)).astype(dtype)


@contextlib.contextmanager
def force_pallas(*families):
    with contextlib.ExitStack() as st:
        for fam in families:
            st.enter_context(KERNELS.override(fam, "pallas"))
        yield


# ---------------------------------------------------------------------
# norm / softmax reference lowerings: fwd + bwd against a float64 oracle
# ---------------------------------------------------------------------
def _ref_rmsnorm(x, g=None, eps=1e-6):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    if g is not None:
        y = y * g.astype(jnp.float32)
    return y.astype(x.dtype)


def _lowering(kind, shape, affine=True):
    """The op's own `lower` (ops/norm.py) as a function of (x, *weights)."""
    import flexflow_tpu as ff

    m = ff.FFModel(ff.FFConfig())
    inp = m.create_tensor(list(shape))
    if kind == "softmax":
        m.softmax(inp)
    else:
        getattr(m, kind)(inp, [-1], elementwise_affine=affine)
    op = m.ops[-1]
    names = [w.name for w in op.weight_specs()]
    return lambda x, *ws: op.lower(None, [x], dict(zip(names, ws)))[0]


def _f64(*arrays):
    return [np.asarray(a.astype(jnp.float32), np.float64) for a in arrays]


def _oracle_layernorm(dy, x, g=None, b=None, eps=1e-5):
    """float64 numpy: y, and the cotangent dy pulled back to (x, g, b)."""
    mean = x.mean(-1, keepdims=True)
    rstd = 1.0 / np.sqrt(x.var(-1, keepdims=True) + eps)
    xhat = (x - mean) * rstd
    y = xhat if g is None else xhat * g + b
    dxhat = dy if g is None else dy * g
    dx = rstd * (dxhat - dxhat.mean(-1, keepdims=True)
                 - xhat * (dxhat * xhat).mean(-1, keepdims=True))
    lead = tuple(range(x.ndim - 1))
    return y, (dx, (dy * xhat).sum(lead), dy.sum(lead))


def _oracle_rmsnorm(dy, x, g, eps=1e-6):
    r = 1.0 / np.sqrt((x * x).mean(-1, keepdims=True) + eps)
    y = x * r * g
    dyg = dy * g
    dx = r * (dyg - x * r * r * (dyg * x).mean(-1, keepdims=True))
    return y, (dx, (dy * x * r).sum(tuple(range(x.ndim - 1))))


def _oracle_softmax(dy, x):
    e = np.exp(x - x.max(-1, keepdims=True))
    y = e / e.sum(-1, keepdims=True)
    return y, (y * (dy - (dy * y).sum(-1, keepdims=True)),)


def _check_fwd_bwd(fn, oracle, args, tol):
    """The lowering's output and its VJP of one random cotangent (in the
    I/O dtype, like the inputs) against the oracle's, in float64 from the
    same stored values."""
    dy = _rand(np.random.RandomState(99), args[0].shape, args[0].dtype)
    want_y, want_grads = oracle(*_f64(dy, *args))
    y, pull = jax.vjp(fn, *args)
    np.testing.assert_allclose(np.asarray(y, np.float32), want_y, **tol)
    for a, r in zip(pull(dy), want_grads):
        np.testing.assert_allclose(np.asarray(a, np.float32), r, **tol)


@pytest.mark.parametrize("dtype,tol", [(np.float32, F32_TOL),
                                       (jnp.bfloat16, BF16_TOL)])
def test_layernorm_fwd_bwd_parity(dtype, tol):
    rng = np.random.RandomState(0)
    x = _rand(rng, (3, 9, 48), dtype)
    g = _rand(rng, (48,), dtype)
    b = _rand(rng, (48,), dtype)
    _check_fwd_bwd(_lowering("layer_norm", x.shape), _oracle_layernorm,
                   (x, g, b), tol)


def test_layernorm_no_affine_parity():
    x = _rand(np.random.RandomState(1), (4, 5, 32))

    def oracle(dy, x):
        y, (dx, _, _) = _oracle_layernorm(dy, x)
        return y, (dx,)

    _check_fwd_bwd(_lowering("layer_norm", x.shape, affine=False), oracle,
                   (x,), F32_TOL)


@pytest.mark.parametrize("dtype,tol", [(np.float32, F32_TOL),
                                       (jnp.bfloat16, BF16_TOL)])
def test_rmsnorm_fwd_bwd_parity(dtype, tol):
    rng = np.random.RandomState(2)
    x = _rand(rng, (2, 7, 64), dtype)
    g = _rand(rng, (64,), dtype)
    _check_fwd_bwd(_lowering("rms_norm", x.shape), _oracle_rmsnorm,
                   (x, g), tol)


@pytest.mark.parametrize("dtype,tol", [(np.float32, F32_TOL),
                                       (jnp.bfloat16, BF16_TOL)])
def test_softmax_fwd_bwd_parity(dtype, tol):
    x = _rand(np.random.RandomState(3), (5, 11, 40), dtype)
    _check_fwd_bwd(_lowering("softmax", x.shape), _oracle_softmax,
                   (x,), tol)


# ---------------------------------------------------------------------
# fused decode step
# ---------------------------------------------------------------------
def _per_head(q, cache):
    """The pool's packed (B, M, h*d) cache as the (B, M, h, d) the plain
    references attend."""
    return cache.reshape(cache.shape[:2] + q.shape[2:])


def _ref_decode(q, kc, vc, pos, scale):
    kc, vc = _per_head(q, kc), _per_head(q, vc)
    m = kc.shape[1]
    mask = (jnp.arange(m)[None, :] <= pos[:, None])[:, None, None, :]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kc.astype(q.dtype),
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype),
                      vc.astype(q.dtype))


@pytest.mark.parametrize("block_k", [64, 16])  # single- and multi-block
def test_fused_decode_ragged_positions(block_k, monkeypatch):
    # the block is derived from the cache's length (`block_rows`)
    monkeypatch.setattr(latent_decode, "BLOCK_ROWS", block_k)
    rng = np.random.RandomState(6)
    B, M, h, d = 5, 32, 3, 8
    q = _rand(rng, (B, 1, h, d))
    kc = _rand(rng, (B, M, h * d))
    vc = _rand(rng, (B, M, h * d))
    # ragged: includes pos 0 (one live row) and pos M-1 (the whole cache)
    pos = jnp.asarray([0, 3, 16, 31, 7], dtype=jnp.int32)
    scale = 1.0 / np.sqrt(d)
    out = fused_decode_attention(q, kc, vc, pos, scale=scale, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_ref_decode(q, kc, vc, pos, scale)),
        rtol=1e-5, atol=1e-6)


def test_fused_decode_bf16_cache():
    rng = np.random.RandomState(7)
    B, M, h, d = 2, 16, 2, 16
    q = _rand(rng, (B, 1, h, d))
    kc = _rand(rng, (B, M, h * d), jnp.bfloat16)
    vc = _rand(rng, (B, M, h * d), jnp.bfloat16)
    pos = jnp.asarray([5, 15], dtype=jnp.int32)
    scale = 1.0 / np.sqrt(d)
    out = fused_decode_attention(q, kc, vc, pos, scale=scale, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(_ref_decode(q, kc, vc, pos, scale), np.float32),
        **BF16_TOL)


def test_fused_decode_rejects_multi_query():
    q = jnp.zeros((1, 2, 2, 4))
    kc = vc = jnp.zeros((1, 8, 2 * 4))
    with pytest.raises(ValueError, match="one query token"):
        fused_decode_attention(q, kc, vc, jnp.zeros((1,), jnp.int32),
                               scale=1.0, interpret=True)


# the dense decode kernel against the op's own reference chain: 4 blocks of
# 16 rows; positions at the first row, a block's last and the next block's
# first, the cache's last, an idle slot's dummy position, mid-block
_DECODE_POS = (0, 15, 16, 63, 0, 37)
# what the rows past each slot's position hold: NaN, 1e30, or the rows of a
# previous tenant of the slot that was longer (the whole cache random)
_PAST = {"nan": jnp.nan, "1e30": 1e30, "tenant": None}


@pytest.mark.parametrize("past", sorted(_PAST))
@pytest.mark.parametrize("stored", ["bfloat16", "float32"])
@pytest.mark.parametrize("heads,kv_heads,width", [
    (48, 8, 128), (20, 4, 128), (16, 16, 64)])
def test_fused_decode_equals_the_reference_chain(
        heads, kv_heads, width, stored, past, monkeypatch):
    """`fused_decode_attention` (interpret mode) against
    `MultiHeadAttentionOp._masked_core` — the chain the op lowers to where
    the registry says reference — at the head counts of
    `laguna_xs2_1chip`'s full layers, `falcon_h1_34b_1chip` and
    `lm_osdi22w`: grouped KV heads read from their own 128-lane slice of
    the packed row, ragged positions over several blocks. What lies past a
    slot's position never reaches the output: the blocks past it are not
    copied, the block that holds it is masked in scores and in values. The
    reference is handed the same cache with those rows zeroed."""
    import flexflow_tpu as ff
    from flexflow_tpu.core.op import LoweringContext
    from flexflow_tpu.ffconst import CompMode

    monkeypatch.setattr(latent_decode, "BLOCK_ROWS", 16)
    b, m = len(_DECODE_POS), 64
    sdt = jnp.dtype(stored)
    cdt = sdt       # a bf16 model computes in bf16, a float32 one in float32
    mdl = ff.FFModel(ff.FFConfig())
    x = mdl.create_tensor([b, 1, 64])
    mdl.multihead_attention(x, x, x, 64, heads, kdim=width, vdim=width,
                            causal=True, kv_heads=kv_heads, name="attn")
    op = mdl.ops[-1]
    ks = jax.random.split(jax.random.PRNGKey(heads), 3)
    q = jax.random.normal(ks[0], (b, 1, heads, width), cdt)
    kc = jax.random.normal(ks[1], (b, m, kv_heads * width), sdt)
    vc = jax.random.normal(ks[2], (b, m, kv_heads * width), sdt)
    pos = jnp.asarray(_DECODE_POS, jnp.int32)
    beyond = jnp.arange(m)[None, :, None] > pos[:, None, None]
    scale = width ** -0.5
    ctx = LoweringContext(mdl.config, CompMode.COMP_MODE_INFERENCE)
    want = op._masked_core(
        ctx, q, jnp.where(beyond, 0, kc), jnp.where(beyond, 0, vc),
        (~beyond[:, :, 0])[:, None, None, :], scale)
    if _PAST[past] is not None:
        kc = jnp.where(beyond, _PAST[past], kc).astype(sdt)
        vc = jnp.where(beyond, _PAST[past], vc).astype(sdt)
    got = fused_decode_attention(q, kc, vc, pos, scale=scale, interpret=True)
    assert got.shape == want.shape == (b, 1, heads, width)
    assert got.dtype == q.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **(BF16_TOL if stored == "bfloat16" else F32_TOL))


# ---------------------------------------------------------------------
# KERNELS.select: param > override > platform > shape
# ---------------------------------------------------------------------
def test_registry_selection_order():
    # CPU backend: no param, no override -> always reference
    c = KERNELS.select("attention", scores=(64, 16, 512, 512, 1),
                       record=False)
    assert not c and c.reason == "backend"
    # param beats everything, both ways
    with KERNELS.override("attention", "reference"):
        c = KERNELS.select("attention", param=True, record=False)
        assert c and c.reason == "param"
    with KERNELS.override("attention", "pallas"):
        assert not KERNELS.select("attention", param=False, record=False)
    # override beats platform; restores the previous override on exit
    with KERNELS.override("attention_decode", "pallas"):
        c = KERNELS.select("attention_decode", record=False)
        assert c and c.reason == "override"
        with KERNELS.override("attention_decode", "reference"):
            assert not KERNELS.select("attention_decode", record=False)
        assert KERNELS.select("attention_decode", record=False)
    assert KERNELS.select("attention_decode",
                          record=False).reason == "backend"
    for gone in ("not_a_family", "layernorm", "reduction"):
        with pytest.raises(KeyError):
            KERNELS.select(gone)
        with pytest.raises(KeyError):
            with KERNELS.override(gone, "pallas"):
                pass
    with pytest.raises(ValueError):
        with KERNELS.override("attention", "fused"):
            pass


# the three configurations' own attention shapes at published widths:
# (declared batch, heads, q_len, k_len, data-parallel degree)
BERT_1CHIP = (64, 16, 512, 512, 1)      # bert_osdi22, 64 rows a chip
BERT_DATA4 = (256, 16, 512, 512, 4)     # the same at global 256 over data: 4
LM_WINDOW = (1, 16, 1024, 1024, 1)      # lm_osdi22w's lockstep window
SEQ_128 = (64, 16, 128, 128, 1)

# (platform, family, use_flash, override, scores) -> (impl, reason)
# bytes of one expert's matrix: laguna_xs2_1chip's, mistral_small4_ep4's
LGX_EXPERT, MS4_EXPERT = 2048 * 512 * 2, 4096 * 2048 * 2
DECISIONS = {
    "cpu-bert": ("cpu", "attention", None, None, BERT_1CHIP,
                 "reference", "backend"),
    "cpu-decode": ("cpu", "attention_decode", None, None, None,
                   "reference", "backend"),
    "cpu-mq": ("cpu", "attention_decode_mq", None, None, None,
               "reference", "backend"),
    "cpu-use_flash-forces": ("cpu", "attention", True, None, SEQ_128,
                             "pallas", "param"),
    "cpu-override-forces": ("cpu", "attention", None, "pallas", SEQ_128,
                            "pallas", "override"),
    "cpu-decode-override": ("cpu", "attention_decode", None, "pallas", None,
                            "pallas", "override"),
    "tpu-bert_osdi22": ("tpu", "attention", None, None, BERT_1CHIP,
                        "pallas", "shape"),
    "tpu-bert_osdi22-data4": ("tpu", "attention", None, None, BERT_DATA4,
                              "pallas", "shape"),
    "tpu-bert-global256-one-chip": ("tpu", "attention", None, None,
                                    (256, 16, 512, 512, 1),
                                    "pallas", "shape"),
    "tpu-lm_osdi22w-window": ("tpu", "attention", None, None, LM_WINDOW,
                              "reference", "shape"),
    "tpu-seq128": ("tpu", "attention", None, None, SEQ_128,
                   "reference", "shape"),
    "tpu-seq128-data4": ("tpu", "attention", None, None,
                         (256, 16, 128, 128, 4), "reference", "shape"),
    "tpu-no-shape": ("tpu", "attention", None, None, None,
                     "reference", "shape"),
    "tpu-decode": ("tpu", "attention_decode", None, None, None,
                   "reference", "shape"),
    "tpu-mq": ("tpu", "attention_decode_mq", None, None, None,
               "reference", "shape"),
    "tpu-decode-ignores-shape": ("tpu", "attention_decode", None, None,
                                 BERT_1CHIP, "reference", "shape"),
    "tpu-decode-override": ("tpu", "attention_decode", None, "pallas", None,
                            "pallas", "override"),
    # one query a slot on the dense cache (head width, bytes of a cached
    # value, cache rows): the kernel that reads the filled rows where the
    # head is whole 128-lane tiles, the cache bf16 and its length divisible
    "tpu-decode-laguna_xs2-full": ("tpu", "attention_decode", None, None,
                                   dict(decode=(128, 2, 12288)),
                                   "pallas", "shape"),
    "tpu-decode-falcon_h1": ("tpu", "attention_decode", None, None,
                             dict(decode=(128, 2, 2048)), "pallas", "shape"),
    "tpu-decode-lm_osdi22w": ("tpu", "attention_decode", None, None,
                              dict(decode=(64, 4, 2048)),
                              "reference", "shape"),
    "tpu-decode-64-wide-bf16": ("tpu", "attention_decode", None, None,
                                dict(decode=(64, 2, 2048)),
                                "reference", "shape"),
    "tpu-decode-float32-cache": ("tpu", "attention_decode", None, None,
                                 dict(decode=(128, 4, 2048)),
                                 "reference", "shape"),
    "tpu-decode-undividable-length": ("tpu", "attention_decode", None, None,
                                      dict(decode=(128, 2, 1000)),
                                      "reference", "shape"),
    "tpu-decode-short-cache-one-block": ("tpu", "attention_decode", None,
                                         None, dict(decode=(256, 2, 200)),
                                         "pallas", "shape"),
    "cpu-decode-laguna_xs2-full": ("cpu", "attention_decode", None, None,
                                   dict(decode=(128, 2, 12288)),
                                   "reference", "backend"),
    "tpu-decode-laguna_xs2-override-reference": (
        "tpu", "attention_decode", None, "reference",
        dict(decode=(128, 2, 12288)), "reference", "override"),
    "tpu-mq-ignores-decode-shape": ("tpu", "attention_decode_mq", None, None,
                                    dict(decode=(128, 2, 12288)),
                                    "reference", "shape"),
    "tpu-mq-override": ("tpu", "attention_decode_mq", None, "pallas", None,
                        "pallas", "override"),
    "tpu-bert-override-reference": ("tpu", "attention", None, "reference",
                                    BERT_1CHIP, "reference", "override"),
    "tpu-seq128-override-pallas": ("tpu", "attention", None, "pallas",
                                   SEQ_128, "pallas", "override"),
    "tpu-bert-use_flash-false": ("tpu", "attention", False, None,
                                 BERT_1CHIP, "reference", "param"),
    "tpu-seq128-use_flash-true": ("tpu", "attention", True, None, SEQ_128,
                                  "pallas", "param"),
    "tpu-param-beats-override": ("tpu", "attention", False, "pallas",
                                 BERT_1CHIP, "reference", "param"),
    "tpu-param-true-beats-override": ("tpu", "attention", True, "reference",
                                      SEQ_128, "pallas", "param"),
    "cpu-param-beats-override": ("cpu", "attention", False, "pallas",
                                 BERT_1CHIP, "reference", "param"),
    "gpu-is-not-a-tpu": ("gpu", "attention", None, None, BERT_1CHIP,
                         "reference", "backend"),
    # the latent decode core (one query a slot, asked by its call site)
    "cpu-latent": ("cpu", "latent_decode", None, None, None,
                   "reference", "backend"),
    "cpu-latent-override": ("cpu", "latent_decode", None, "pallas", None,
                            "pallas", "override"),
    "tpu-latent": ("tpu", "latent_decode", None, None, None,
                   "pallas", "shape"),
    "tpu-latent-override-reference": ("tpu", "latent_decode", None,
                                      "reference", None,
                                      "reference", "override"),
    "gpu-latent": ("gpu", "latent_decode", None, None, None,
                   "reference", "backend"),
    # routed experts over few token rows (rows, experts a token, experts
    # held, experts in all, bytes of one matrix): the tiled grouped matmul
    # where a matrix is one VMEM block, past the ridge or where the
    # assignments are expected to miss a good part of the held experts
    "tpu-experts-lgx-chunk": ("tpu", "grouped_experts", None, None,
                              (512, 8, 256, 256, LGX_EXPERT), "pallas",
                              "shape"),
    "tpu-experts-lgx-decode": ("tpu", "grouped_experts", None, None,
                               (40, 8, 256, 256, LGX_EXPERT), "pallas",
                               "shape"),
    "tpu-experts-lgx-8-slots": ("tpu", "grouped_experts", None, None,
                                (8, 8, 256, 256, LGX_EXPERT), "pallas",
                                "shape"),
    "tpu-experts-lgx-128-slots": ("tpu", "grouped_experts", None, None,
                                  (128, 8, 256, 256, LGX_EXPERT),
                                  "reference", "shape"),
    "tpu-experts-ms4-chunk": ("tpu", "grouped_experts", None, None,
                              (512, 4, 32, 128, MS4_EXPERT), "reference",
                              "shape"),
    "tpu-experts-ms4-decode": ("tpu", "grouped_experts", None, None,
                               (128, 4, 32, 128, MS4_EXPERT), "reference",
                               "shape"),
    "cpu-experts-lgx-chunk": ("cpu", "grouped_experts", None, None,
                              (512, 8, 256, 256, LGX_EXPERT), "reference",
                              "backend"),
    "cpu-experts-lgx-decode": ("cpu", "grouped_experts", None, None,
                               (40, 8, 256, 256, LGX_EXPERT), "reference",
                               "backend"),
    "cpu-experts-override": ("cpu", "grouped_experts", None, "pallas",
                             (128, 8, 256, 256, LGX_EXPERT), "pallas",
                             "override"),
}


@pytest.mark.parametrize("case", sorted(DECISIONS))
def test_select_decision_table(case):
    platform, family, use_flash, override, scores, impl, reason = \
        DECISIONS[case]
    forced = (KERNELS.override(family, override) if override
              else contextlib.nullcontext())
    with mock.patch.object(jax, "default_backend", lambda: platform), forced:
        shape = "experts" if family == "grouped_experts" else "scores"
        given = scores if isinstance(scores, dict) else {shape: scores}
        choice = KERNELS.select(family, param=use_flash, record=False,
                                **given)
    assert (choice.impl, choice.reason) == (impl, reason)
    assert bool(choice) == (impl == "pallas")


def _attention_ops(batch, seq, **attn):
    import flexflow_tpu as ff

    cfg = ff.FFConfig()
    cfg.num_devices = 1
    m = ff.FFModel(cfg)
    inp = m.create_tensor([batch, seq, 1024])
    m.multihead_attention(inp, inp, inp, 1024, 16, name="attn", **attn)
    m.layer_norm(inp, [-1], name="ln")
    return cfg, {op.name: op for op in m.ops}


def test_cost_model_gates_match_lowering():
    """The simulator never discounts an op the lowering would not run as
    flash, and answers as the lowering does for the same op and strategy:
    attention-prob dropout and an explicit use_flash=False price at 1.0
    even past the crossover on a TPU; a data-parallel degree that takes
    the per-chip scores under the crossover does too; no other op type is
    ever discounted."""
    from flexflow_tpu.search.machine_model import make_machine_model
    from flexflow_tpu.search.simulator import CostModel, OpStrategy

    class Ctx:  # what _use_flash reads of a LoweringContext
        mesh = None

    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        for attn, dp, want in (({}, 1, FLASH_COST_GAIN),
                               ({"dropout": 0.1}, 1, 1.0),
                               ({"use_flash": False}, 1, 1.0),
                               ({}, 2, 1.0)):
            cfg, ops = _attention_ops(8, 512, **attn)
            cost = CostModel(make_machine_model(cfg, dp), cfg)
            s = OpStrategy(dp=dp)
            assert cost.kernel_time_factor(ops["attn"], s) == want, attn
            assert cost.kernel_time_factor(ops["ln"], s) == 1.0
            if dp == 1 and not attn.get("dropout"):
                # dropout is a gate of lower() itself, past _use_flash
                assert ops["attn"]._use_flash(Ctx()) == (
                    want == FLASH_COST_GAIN)


def test_registry_profile_roundtrip_residuals(tmp_path):
    import json

    from flexflow_tpu.obs.refit import FittedCoefficients, FittedProfile

    path = str(tmp_path / "p.json")
    FittedProfile(chip="x", backend="cpu",
                  coefficients=FittedCoefficients(),
                  op_family_residuals={"multihead_attention": 2.5}
                  ).save(path)
    loaded = FittedProfile.load(path, expect_chip="x",
                                expect_backend="cpu")
    assert loaded.op_family_residuals == {"multihead_attention": 2.5}
    # a profile written by an older tree (it carried per-family selection
    # thresholds) still loads: keys the dataclass no longer has are ignored
    with open(path) as f:
        d = json.load(f)
    d["thresholds_of_an_older_tree"] = {"attention": 1.07}
    with open(path, "w") as f:
        json.dump(d, f)
    again = FittedProfile.load(path, expect_chip="x", expect_backend="cpu")
    assert again.op_family_residuals == loaded.op_family_residuals
    assert "thresholds_of_an_older_tree" not in again.to_dict()


def test_registry_selection_counter():
    from flexflow_tpu.obs import REGISTRY

    fam = REGISTRY.counter("ff_kernel_selected_total",
                           "Kernel-tier selections by op family and "
                           "implementation", labels=("op", "impl"))
    before = fam.value(op="attention_decode_mq", impl="pallas")
    with KERNELS.override("attention_decode_mq", "pallas"):
        KERNELS.select("attention_decode_mq")
        KERNELS.select("attention_decode_mq", record=False)  # never counts
    assert fam.value(op="attention_decode_mq", impl="pallas") == before + 1


# ---------------------------------------------------------------------
# simulator pricing: the search sees the kernel the lowering emits
# ---------------------------------------------------------------------
def test_cost_model_prices_pallas_selection():
    """Flash priced at the measured ratio, the einsum core at 1.0."""
    from flexflow_tpu.search.machine_model import make_machine_model
    from flexflow_tpu.search.simulator import CostModel, OpStrategy

    cfg, ops = _attention_ops(4, 16)
    s = OpStrategy(dp=1, tp=1)
    cost = CostModel(make_machine_model(cfg, 1), cfg)
    t_ref = cost.forward_time_us(ops["attn"], s)
    assert cost.kernel_time_factor(ops["attn"], s) == 1.0  # CPU: einsum
    with KERNELS.override("attention", "pallas"):
        t_pallas = cost.forward_time_us(ops["attn"], s)
        t_ln = cost.forward_time_us(ops["ln"], s)
    assert t_pallas == pytest.approx(t_ref * FLASH_COST_GAIN, rel=1e-6)
    assert FLASH_COST_GAIN == 0.89 and t_pallas < t_ref
    assert t_ln == cost.forward_time_us(ops["ln"], s)


# ---------------------------------------------------------------------
# op lowerings through a whole model
# ---------------------------------------------------------------------
def _tiny_model(seed=0):
    import flexflow_tpu as ff

    cfg = ff.FFConfig()
    cfg.batch_size = 4
    cfg.seed = seed
    m = ff.FFModel(cfg)
    inp = m.create_tensor([4, 6, 32])
    t = m.layer_norm(inp, [-1], name="ln")
    t = m.rms_norm(t, [-1], name="rms")
    t = m.dense(t, 10, name="cls")
    m.softmax(t)
    m.compile(optimizer=ff.SGDOptimizer(m, lr=0.05),
              loss_type=ff.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
              metrics=[ff.MetricsType.METRICS_ACCURACY])
    return m


def test_rms_norm_op_reference_lowering_correct():
    """The RMSNorm op's reference lowering (and its multi-axis fallback
    route) against a direct jnp computation."""
    import flexflow_tpu as ff

    cfg = ff.FFConfig()
    cfg.batch_size = 2
    cfg.allow_mixed_precision = False  # f32 oracle comparison
    m = ff.FFModel(cfg)
    inp = m.create_tensor([2, 3, 16])
    m.rms_norm(inp, [-1], name="rms")
    m.compile(optimizer=ff.SGDOptimizer(m, lr=0.0),
              loss_type=ff.LossType.LOSS_IDENTITY)
    x = np.random.RandomState(9).randn(2, 3, 16).astype(np.float32)
    out = m.predict(x)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_ref_rmsnorm(jnp.asarray(x))),
        **F32_TOL)


# ---------------------------------------------------------------------
# continuous batcher: fused decode, token-identical, slot reuse
# ---------------------------------------------------------------------
def test_continuous_batcher_fused_decode_token_parity():
    """Greedy decode through the continuous batcher with the fused
    vector-decode kernel FORCED (registry override; interpret mode on
    CPU) is token-identical to the lockstep reference — ragged prompt
    lengths AND slot reuse (3 requests through 2 slots)."""
    from flexflow_tpu.serving.generate import GenerativeSession
    from flexflow_tpu.serving.sched import ContinuousBatcher
    from tests.test_generate import _build_lm

    lm = _build_lm(2, 12)
    rng = np.random.RandomState(10)
    prompts = [rng.randint(1, 50, size=(n,)).astype(np.int32)
               for n in (4, 7, 3)]
    session = GenerativeSession(lm, max_len=12)
    refs = [session.generate(p[None, :], 5)[0] for p in prompts]
    with force_pallas("attention_decode"):
        with ContinuousBatcher(lm, max_len=12, num_slots=2, page_size=4,
                               max_queue=8) as cb:
            outs = [r.result(timeout=300)
                    for r in [cb.submit(p, 5) for p in prompts]]
    for out, ref in zip(outs, refs):
        np.testing.assert_array_equal(out, np.asarray(ref))


def test_calibration_kernel_candidates_ranking():
    """Synthetic calibration rows: the candidates section ranks op types,
    by their own names, by residual weighted by predicted-step share, and
    op_family_residuals takes the per-type MEDIAN."""
    from flexflow_tpu.obs.calibration import (CalibrationReport,
                                              OpCalibration,
                                              op_family_residuals)

    rows = [
        # layernorm: big residual (x3) but small share
        OpCalibration("ln1", "layernorm", "dp=1", 10.0, 30.0),
        OpCalibration("ln2", "layernorm", "dp=1", 10.0, 50.0),
        OpCalibration("ln3", "layernorm", "dp=1", 10.0, 30.0),
        # attention: modest residual (x1.5) on most of the step
        OpCalibration("attn", "multihead_attention", "dp=1", 400.0, 600.0),
        # linear: faster than predicted — listed, no headroom
        OpCalibration("fc", "linear", "dp=1", 100.0, 50.0),
        # failed measurement: excluded from residuals
        OpCalibration("sm", "softmax", "dp=1", 5.0, float("nan"),
                      error="x"),
    ]
    fams = op_family_residuals(rows)
    assert fams == {"layernorm": 3.0,  # median of [3, 5, 3]
                    "multihead_attention": 1.5, "linear": 0.5}

    rep = CalibrationReport(backend="cpu", predicted_step_us=1000.0,
                            measured_step_us=1500.0, measured_steps=3,
                            ops=rows)
    cands = rep.kernel_candidates()
    by_fam = {c["family"]: c for c in cands}
    assert set(by_fam) == {"layernorm", "multihead_attention", "linear",
                           "softmax"}
    # attention: 0.5 residual excess * (400/535) share beats layernorm's
    # 2.0 excess * (30/535)
    assert [c["family"] for c in cands] == [
        "multihead_attention", "layernorm", "linear", "softmax"]
    assert by_fam["softmax"]["score"] == 0.0  # unmeasurable -> no score
    assert by_fam["linear"]["score"] == 0.0   # under the roofline
    assert by_fam["layernorm"]["score"] == pytest.approx(
        2.0 * 30.0 / 535.0)
    # the report renders and serializes with the section included
    assert "kernel candidates" in rep.format_kernel_report()
    assert (rep.to_dict()["kernel_candidates"][0]["family"]
            == "multihead_attention")


def test_refit_persists_family_residuals(tmp_path):
    """A real refit run records the per-op-type residuals into the saved
    profile, and they survive the round trip; selecting reads none of
    it."""
    from flexflow_tpu.obs import calibrate
    from flexflow_tpu.obs.refit import FittedProfile, refit

    m = _tiny_model()
    x = np.random.RandomState(11).randn(8, 6, 32).astype(np.float32)
    y = np.random.RandomState(11).randint(
        0, 10, size=(8, 6, 1)).astype(np.int32)
    m.fit([x], y, batch_size=4, epochs=2)
    rep = calibrate(m)
    measured = rep.measured_step_us or 5000.0
    profile, _ = refit(m, measured, rep.ops, rounds=1, tol=0.15)
    # the tiny model has layernorm+rmsnorm+linear+softmax rows; at least
    # one type must have produced evidence, under its own name
    assert profile.op_family_residuals
    assert set(profile.op_family_residuals) <= {
        "layernorm", "rmsnorm", "linear", "softmax"}
    path = str(tmp_path / "fitted.json")
    profile.save(path)
    assert (FittedProfile.load(path).op_family_residuals
            == profile.op_family_residuals)


# ---------------------------------------------------------------------
# multi-query decode kernel (ISSUE 14)
# ---------------------------------------------------------------------
def _ref_mq_decode(q, kc, vc, pos, scale):
    kc, vc = _per_head(q, kc), _per_head(q, vc)
    b, c = q.shape[0], q.shape[1]
    m = kc.shape[1]
    qpos = pos[:, None] + jnp.arange(c)[None, :]
    mask = (jnp.arange(m)[None, None, :]
            <= qpos[:, :, None])[:, None, :, :]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kc.astype(q.dtype),
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype),
                      vc.astype(q.dtype))


@pytest.mark.parametrize("block_k", [64, 8])  # single- and multi-block
def test_fused_multiquery_decode_parity(block_k):
    from flexflow_tpu.kernels.pallas import (
        fused_multiquery_decode_attention)

    rng = np.random.RandomState(12)
    B, C, M, h, d = 5, 3, 24, 3, 8
    q = _rand(rng, (B, C, h, d))
    kc = _rand(rng, (B, M, h * d))
    vc = _rand(rng, (B, M, h * d))
    # ragged: pos 0 (the query window IS the live prefix) through M-C
    # (the window ends at the last cache row)
    pos = jnp.asarray([0, 3, 11, 21, 7], dtype=jnp.int32)
    scale = 1.0 / np.sqrt(d)
    out = fused_multiquery_decode_attention(
        q, kc, vc, pos, scale=scale, block_k=block_k, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_ref_mq_decode(q, kc, vc, pos, scale)),
        rtol=1e-5, atol=1e-6)


def test_fused_multiquery_decode_bf16_cache():
    from flexflow_tpu.kernels.pallas import (
        fused_multiquery_decode_attention)

    rng = np.random.RandomState(13)
    B, C, M, h, d = 2, 4, 16, 2, 16
    q = _rand(rng, (B, C, h, d))
    kc = _rand(rng, (B, M, h * d), jnp.bfloat16)
    vc = _rand(rng, (B, M, h * d), jnp.bfloat16)
    pos = jnp.asarray([5, 12], dtype=jnp.int32)
    scale = 1.0 / np.sqrt(d)
    for block_k in (64, 8):
        out = fused_multiquery_decode_attention(
            q, kc, vc, pos, scale=scale, block_k=block_k, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32),
            np.asarray(_ref_mq_decode(q, kc, vc, pos, scale), np.float32),
            **BF16_TOL)


def test_fused_multiquery_c1_matches_single_query():
    """C = 1 through the multi-query entry is the single-query kernel's
    math in both block regimes (two bodies since PR 34: the single-query
    one walks the filled blocks alone, so equal to float rounding)."""
    from flexflow_tpu.kernels.pallas import (
        fused_multiquery_decode_attention)

    rng = np.random.RandomState(14)
    B, M, h, d = 3, 24, 2, 8
    q = _rand(rng, (B, 1, h, d))
    kc = _rand(rng, (B, M, h * d))
    vc = _rand(rng, (B, M, h * d))
    pos = jnp.asarray([0, 9, 23], dtype=jnp.int32)
    for block_k in (64, 8):
        a = fused_multiquery_decode_attention(
            q, kc, vc, pos, scale=0.3, block_k=block_k, interpret=True)
        b = fused_decode_attention(q, kc, vc, pos, scale=0.3, interpret=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **F32_TOL)


def test_continuous_batcher_fused_decode_multiblock_token_parity(monkeypatch):
    """Satellite 3 (lifts the PR 9 docs caveat): greedy decode through
    the continuous batcher with BOTH fused decode kernels forced and
    the kernels' blocks SMALLER than the cache span — decodes stream
    multiple KV blocks through the online softmax — stays
    token-identical to the pure-reference run. Ragged prompts, slot
    reuse (4 requests through 2 slots), chunked prefill through the
    multi-query kernel."""
    from flexflow_tpu.serving.sched import ContinuousBatcher
    from tests.test_generate import _build_lm

    rng = np.random.RandomState(15)
    prompts = [rng.randint(1, 50, size=(n,)).astype(np.int32)
               for n in (4, 9, 3, 7)]

    import functools

    from flexflow_tpu.kernels.pallas import decode

    def run(forced):
        lm = _build_lm(2, 12)
        with contextlib.ExitStack() as st:
            for fam in forced:
                st.enter_context(KERNELS.override(fam, "pallas"))
            # cache span 32 -> 4 KV blocks of the multi-query kernel, 2
            # of the single-query one (its block is derived: `block_rows`)
            fn = "fused_multiquery_decode_attention"
            st.enter_context(mock.patch.object(
                decode, fn, functools.partial(getattr(decode, fn), block_k=8)))
            with ContinuousBatcher(lm, max_len=32, num_slots=2,
                                   page_size=4, max_queue=8) as cb:
                return [r.result(timeout=300).tolist()
                        for r in [cb.submit(p, 10) for p in prompts]]

    monkeypatch.setattr(latent_decode, "BLOCK_ROWS", 16)
    ref = run(())
    fused = run(("attention_decode", "attention_decode_mq"))
    assert fused == ref


def test_chunk_offset_prefill_lowers_through_mq_kernel():
    """The chunk-offset (scalar-pos) prefill entry lowers through the
    multi-query kernel when selected: a chunked prefill with the kernel
    forced produces the same first token and downstream stream as the
    reference chunk path."""
    from flexflow_tpu.serving.sched import ContinuousBatcher
    from tests.test_generate import _build_lm

    lm = _build_lm(2, 12)
    prompt = np.random.RandomState(16).randint(
        1, 50, size=(9,)).astype(np.int32)

    def run(force):
        with contextlib.ExitStack() as st:
            if force:
                st.enter_context(KERNELS.override("attention_decode_mq",
                                                  "pallas"))
            with ContinuousBatcher(lm, max_len=16, num_slots=2,
                                   page_size=4, prefill_chunk_tokens=4,
                                   max_queue=4) as cb:
                return cb.submit(prompt, 5).result(timeout=300).tolist()

    assert run(True) == run(False)


# ---------------------------------------------------------------------
# decode pricing
# ---------------------------------------------------------------------
def test_cost_model_prices_decode_dispatches():
    """decode_step_time_us prices the serving hot dispatches at the
    roofline of the reference chain: the multi-query dispatch costs no
    less than the single-query one, and forcing the decode kernels (which
    no cell has timed) changes no price."""
    from flexflow_tpu.ffconst import OpType
    from flexflow_tpu.search.machine_model import make_machine_model
    from flexflow_tpu.search.simulator import CostModel
    from tests.test_generate import _build_lm

    lm = _build_lm(2, 12)
    attn = next(op for op in lm.graph.ops.values()
                if op.op_type == OpType.MULTIHEAD_ATTENTION)
    machine = make_machine_model(lm.config, 1)
    cost = CostModel(machine, lm.config)
    ref1 = cost.decode_step_time_us(attn, 4, 64, 1)
    ref4 = cost.decode_step_time_us(attn, 4, 64, 4)
    # the mq dispatch streams the SAME cache once for all C queries —
    # at decode sizes the roofline is bytes-bound, so C is (near) free:
    # that amortization is the whole speculative-decoding win
    assert ref4 >= ref1 > 0
    with force_pallas("attention_decode", "attention_decode_mq"):
        assert cost.decode_step_time_us(attn, 4, 64, 1) == ref1
        assert cost.decode_step_time_us(attn, 4, 64, 4) == ref4
