"""The chip's compiler, without the chip: every Pallas entry point a
registry family can select is compiled for a DESCRIBED TPU v5e at the
widths the main paths use (BERT bench: batch 8, seq 512, hidden 1024, 16
heads; serving: 16 slots x 2048 cache rows).

Interpret mode (every other kernel test) cannot see what these see: a block
the TPU tiling rules refuse, more scoped VMEM than a kernel may have, a
primitive Mosaic does not lower. Nothing executes — a pass here is not a
chip run (that is chip_smoke.py) — but a refusal here is exactly what the
chip would raise deep inside a train or decode step.

Skipped, not failed, where the TPU compiler is not installed and the
topology cannot be described.
"""
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # before libtpu starts

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from flexflow_tpu.kernels.flash_attention import (  # noqa: E402
    flash_attention, flash_attention_packed)
from flexflow_tpu.kernels.pallas import (  # noqa: E402
    fused_decode_attention, fused_layernorm,
    fused_multiquery_decode_attention, fused_reduce, fused_rmsnorm,
    fused_softmax)
from flexflow_tpu.kernels.pallas.norm import softmax_block_rows  # noqa: E402

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
BERT = (8, 512, 1024)   # batch, seq, hidden of the bench config
HEADS = 16
SLOTS, ROWS, HEAD_DIM = 16, 2048, 64
# the widest softmax row the selector admits (ops/norm.py gate)
WIDEST_ROW = max(n for n in range(39000, 40000) if softmax_block_rows(n))


@pytest.fixture(scope="module")
def v5e():
    """SingleDeviceSharding on chip 0 of a described v5e 2x2 host. The
    persistent compile cache is off for the module: a described-topology
    executable written to it cannot be read back without a chip, and the
    next compile would warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sum_grad(fn, n_args):
    """fwd+bwd of fn: grad of its f32 sum wrt the first n_args."""
    return jax.grad(lambda *a: fn(*a).astype(F32).sum(),
                    argnums=tuple(range(n_args)))


def _decode(fn):
    return lambda q, k, v, pos: fn(q, k, v, pos, scale=HEAD_DIM ** -0.5)


_CACHE = ((SLOTS, ROWS, HEADS * HEAD_DIM), BF16)  # as the pool stores it
# name -> (function, [(shape, dtype), ...])
CASES = {
    "flash_packed_fwd_bwd": (
        _sum_grad(lambda q, k, v: flash_attention_packed(q, k, v, HEADS), 3),
        [(BERT, BF16)] * 3),
    "flash_packed_causal_fwd_bwd": (
        _sum_grad(lambda q, k, v: flash_attention_packed(
            q, k, v, HEADS, causal=True), 3),
        [(BERT, BF16)] * 3),
    "flash_blhd_fwd_bwd": (  # the tensor-parallel mesh's kernel
        _sum_grad(lambda q, k, v: flash_attention(q, k, v), 3),
        [((8, 512, HEADS, HEAD_DIM), BF16)] * 3),
    "decode_c1": (
        _decode(fused_decode_attention),
        [((SLOTS, 1, HEADS, HEAD_DIM), BF16), _CACHE, _CACHE,
         ((SLOTS,), I32)]),
    "decode_mq_c5": (
        _decode(fused_multiquery_decode_attention),
        [((SLOTS, 5, HEADS, HEAD_DIM), BF16), _CACHE, _CACHE,
         ((SLOTS,), I32)]),
    "decode_mq_c16_f32": (  # a prefill chunk of the f32 serve-bench LM
        _decode(fused_multiquery_decode_attention),
        [((SLOTS, 16, HEADS, HEAD_DIM), F32),
         (_CACHE[0], F32), (_CACHE[0], F32), ((SLOTS,), I32)]),
    "layernorm_fwd_bwd": (
        _sum_grad(lambda x, g, b: fused_layernorm(x, g, b), 3),
        [(BERT, BF16), ((1024,), BF16), ((1024,), BF16)]),
    "rmsnorm_fwd_bwd": (
        _sum_grad(lambda x, g: fused_rmsnorm(x, g), 2),
        [(BERT, BF16), ((1024,), BF16)]),
    "softmax_widest_row_fwd_bwd": (
        _sum_grad(fused_softmax, 1), [((64, WIDEST_ROW), F32)]),
    "softmax_vocab_fwd_bwd": (
        _sum_grad(fused_softmax, 1), [((8, 512, 30522), BF16)]),
    "reduce_mean_1d": (
        lambda x: fused_reduce(x, "mean"), [((4096,), F32)]),
    "reduce_max": (
        lambda x: fused_reduce(x, "max"), [((8, 512, 1), F32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(v5e, name):
    fn, specs = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
            for shape, dtype in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_all_copies_no_whole_cache_on_v5e(v5e):
    """The continuous batcher's decode step over the PACKED cache
    (slots, max_len, heads*head_dim), one layer at 8 slots x 256 rows x 16
    heads of 64: the chip's compiler scatters into every cache and
    contracts on it in place. A cache stored (…, 16, 64) arrives rows-minor
    and is copied whole — relaid out, its 64 lanes padded to 128 — before
    the scatter and again for the donated output: four `copy` ops a layer
    in the entry computation (85 of the 119 ms decode iteration, ledger
    PR 25)."""
    import re
    from unittest import mock

    from flexflow_tpu.serving.sched.continuous import ContinuousBatcher
    from tests.test_generate import _build_lm

    slots, rows = 8, 256
    with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
            jax.default_matmul_precision("highest"):
        model = _build_lm(1, 64, vocab=512, hidden=HEADS * HEAD_DIM,
                          heads=HEADS, layers=1)
        batcher = ContinuousBatcher(model, max_len=rows, num_slots=slots,
                                    page_size=16, prefill_chunk_tokens=64)
        on_chip = lambda tree: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
            tree)
        vec = lambda *shape, dtype=I32: jax.ShapeDtypeStruct(
            shape, dtype, sharding=v5e)
        compiled = batcher._decode_fn.lower(
            on_chip(model.params), on_chip(model.state),
            on_chip(batcher._caches), vec(slots), vec(slots),
            vec(slots, 2, dtype=jnp.uint32)).compile()

    cache = batcher._caches["l0_attn"]["k_cache"]
    assert cache.shape == (slots, rows, HEADS * HEAD_DIM)
    text = compiled.as_text()
    entry = text[text.index("ENTRY "):]
    entry = entry[:entry.index("\n}")]
    whole_cache_copies = [
        line.strip()[:120] for line in entry.splitlines()
        for m in [re.match(r"\s*(?:ROOT )?\S+ = \w+\[([\d,]+)\]\S* copy\(",
                           line)]
        if m and np.prod([int(d) for d in m.group(1).split(",")]) >= cache.size
    ]
    assert not whole_cache_copies, whole_cache_copies


def test_decode_all_copies_no_latent_cache_on_v5e(v5e):
    """The twin of the test above for the LATENT cache (ops/
    latent_attention.py): one layer at the published widths (32 heads, q
    1,024 / kv 256 / nope 64 / rope 64 / v 128) in bf16, 8 slots x 256
    rows. `c_kv` (…, 256) and `k_rope` (…, 128: the 64-wide rotary key
    padded to a lane tile) are scattered into and contracted on in place;
    stored as one (…, 320) row or with a (…, 64) key the chip's compiler
    hands them over rows-minor and copies each whole twice an iteration."""
    import re
    from unittest import mock

    from benchmark import harness
    from benchmark.configs import mla_moe_lm as builder

    slots, rows = 8, 256
    cfg = harness.load_config("mistral_small4_ep4")
    cfg.update(num_hidden_layers=1, vocab_size=512, n_routed_experts=4,
               moe_intermediate_size=256)
    cfg["deployment"] = dict(cfg["deployment"], window=64, num_slots=slots,
                             max_len=rows, prefill_chunk_tokens=64)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        model = builder.build_model(cfg, 0)
        batcher = builder.build_batcher(model, cfg)
        on_chip = lambda tree: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
            tree)
        vec = lambda *shape, dtype=I32: jax.ShapeDtypeStruct(
            shape, dtype, sharding=v5e)
        compiled = batcher._decode_fn.lower(
            on_chip(model.params), on_chip(model.state),
            on_chip(batcher._caches), vec(slots), vec(slots),
            vec(slots, 2, dtype=jnp.uint32)).compile()

    caches = batcher._caches["l0_attn"]
    assert {k: v.shape for k, v in caches.items()} == {
        "c_kv": (slots, rows, 256), "k_rope": (slots, rows, 128)}
    assert caches["c_kv"].dtype == jnp.bfloat16
    text = compiled.as_text()
    entry = text[text.index("ENTRY "):]
    entry = entry[:entry.index("\n}")]
    smallest = min(int(a.size) for a in caches.values())
    whole_cache_copies = [
        line.strip()[:120] for line in entry.splitlines()
        for m in [re.match(r"\s*(?:ROOT )?\S+ = \w+\[([\d,]+)\]\S* copy\(",
                           line)]
        if m and np.prod([int(d) for d in m.group(1).split(",")]) >= smallest
    ]
    assert not whole_cache_copies, whole_cache_copies


def test_decode_all_streams_the_expert_stacks_in_place_on_v5e(v5e):
    """`mistral_small4_ep4`'s serving programs, one layer at the published
    widths (32 held experts of 4,096 x 2,048, 128 slots x 4,096 rows) with
    shapes for parameters. The decode iteration's 128 token rows take the
    few-rows form of the routed product (ops/moe.py `few_rows`): no
    grouped-GEMM kernel (at 4 rows a group its one 512-row tile is
    multiplied through all 32 groups: 29.7 of the 42 ms decode iteration,
    ledger PR 27) and no copy or transpose of a 0.5 GB expert stack. A
    prefill chunk of more rows than `FEW_ROWS_MAX` still holds the grouped
    product (`jax.lax.ragged_dot` becomes three grouped-GEMM kernels)."""
    import re
    from unittest import mock

    from benchmark import harness
    from benchmark.tests.compile_ms4_for_v5e import programs
    from flexflow_tpu.ops.moe import FEW_ROWS_MAX

    cfg = harness.load_config("mistral_small4_ep4")
    cfg["num_hidden_layers"] = 1
    chunk = 1024
    cfg["deployment"] = dict(cfg["deployment"], prefill_chunk_tokens=chunk)
    assert cfg["deployment"]["num_slots"] <= FEW_ROWS_MAX < chunk
    stack = (int(cfg["n_routed_experts"]) * int(cfg["hidden_size"])
             * int(cfg["moe_intermediate_size"]))
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        progs = programs(cfg, v5e)
        jax.clear_caches()
        text = {name: progs[name][0].lower(*progs[name][1]).compile()
                .as_text() for name in ("decode_all", "prefill_chunk")}

    moved = [
        line.strip()[:120] for line in text["decode_all"].splitlines()
        for m in [re.match(
            r"\s*(?:ROOT )?\S+ = \w+\[([\d,]+)\]\S* (?:copy|transpose)\(",
            line)]
        if m and np.prod([int(d) for d in m.group(1).split(",")]) >= stack
    ]
    assert not moved, moved
    assert "ragged-dot" not in text["decode_all"]
    assert "tpu_custom_call" not in text["decode_all"]
    assert text["prefill_chunk"].count("ragged-dot") >= 3
