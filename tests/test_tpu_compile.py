"""The chip's compiler, without the chip: every Pallas entry point a
registry family can select, and every program a benchmark cell times, is
compiled for a DESCRIBED TPU v5e — the kernels at the
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
    fused_decode_attention, fused_multiquery_decode_attention,
    latent_decode_attention)
from flexflow_tpu.ops.moe import _grouped_product, _tiled_dot  # noqa: E402

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
BERT = (8, 512, 1024)   # batch, seq, hidden of the bench config
HEADS = 16
SLOTS, ROWS, HEAD_DIM = 16, 2048, 64


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


def _tiled_experts(x, w, idx, wg, wu, wd):
    from unittest import mock

    # traced as on the chip: compiled, not interpreted
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        return _grouped_product(
            x, w, idx, {"w_gate": wg, "w_up": wu, "w_down": wd}, 0, 256,
            dot=_tiled_dot)[0]


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
    # the two cells whose decode step runs it: lgx_decode_sat's full layers
    # (40 slots x 12,288 rows x 1,024 lanes, 48 heads on 8 KV heads) and
    # fh1_decode_sat (96 x 2,048 x 512 lanes, 20 on 4), bf16
    "decode_c1_laguna_xs2_full": (
        lambda q, k, v, pos: fused_decode_attention(q, k, v, pos,
                                                    scale=128 ** -0.5),
        [((40, 1, 48, 128), BF16), ((40, 12288, 1024), BF16),
         ((40, 12288, 1024), BF16), ((40,), I32)]),
    "decode_c1_falcon_h1": (
        lambda q, k, v, pos: fused_decode_attention(q, k, v, pos,
                                                    scale=128 ** -0.5),
        [((96, 1, 20, 128), BF16), ((96, 2048, 512), BF16),
         ((96, 2048, 512), BF16), ((96,), I32)]),
    "decode_mq_c5": (
        _decode(fused_multiquery_decode_attention),
        [((SLOTS, 5, HEADS, HEAD_DIM), BF16), _CACHE, _CACHE,
         ((SLOTS,), I32)]),
    "decode_mq_c16_f32": (  # a prefill chunk of the f32 serve-bench LM
        _decode(fused_multiquery_decode_attention),
        [((SLOTS, 16, HEADS, HEAD_DIM), F32),
         (_CACHE[0], F32), (_CACHE[0], F32), ((SLOTS,), I32)]),
    # ms4_decode_sat's decode step: 128 slots x 4,096 rows, 32 heads on a
    # 256-wide latent and a 64-wide rotary key in 128 lanes, bf16
    "latent_decode": (
        latent_decode_attention,
        [((128, 32, 256), BF16), ((128, 32, 128), BF16),
         ((128, 4096, 256), BF16), ((128, 4096, 128), BF16),
         ((128,), I32), ((128,), F32)]),
    "latent_decode_f32_one_block": (
        latent_decode_attention,
        [((8, 32, 256), F32), ((8, 32, 128), F32), ((8, 256, 256), F32),
         ((8, 256, 128), F32), ((8,), I32), ((8,), F32)]),
    # lgx_decode_sat's prefill chunk: 512 rows x 8 through 256 held experts
    # of 2048 x 512, bf16 (the three tiled grouped matmuls)
    "tiled_experts_chunk": (
        _tiled_experts,
        [((512, 2048), BF16), ((512, 8), F32), ((512, 8), I32),
         ((256, 2048, 512), BF16), ((256, 2048, 512), BF16),
         ((256, 512, 2048), BF16)]),
    # its decode step since PR 36: 40 rows x 8, 320 sorted rows padded to
    # three tiles over the ~183 experts they hit
    "tiled_experts_decode": (
        _tiled_experts,
        [((40, 2048), BF16), ((40, 8), F32), ((40, 8), I32),
         ((256, 2048, 512), BF16), ((256, 2048, 512), BF16),
         ((256, 512, 2048), BF16)]),
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
    # and the core is the kernel's one pass: no (slots, heads, max_len) f32
    # scores are left in the step
    heads = int(cfg["num_attention_heads"])
    assert text.count("tpu_custom_call") == 1
    assert not re.search(rf"f32\[{slots},{heads},{rows}\]", text)
    assert not re.search(rf"f32\[{slots},1,{heads},{rows}\]", text)
    assert not re.search(rf"f32\[{slots},{heads},1,{rows}\]", text)


def test_decode_all_streams_the_expert_stacks_in_place_on_v5e(v5e):
    """`mistral_small4_ep4`'s serving programs, two layers at the published
    widths (32 held experts of 4,096 x 2,048, 128 slots x 4,096 rows) with
    shapes for parameters (two, because a chunk that is not a prompt's last
    runs nothing past the last layer's attention: the LAST layer's experts
    are not in `prefill_chunk`, the first layer's still see the chunk's
    1,024 rows). The decode iteration's 128 token rows take the
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
    cfg["num_hidden_layers"] = layers = 2
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
    calls = [line for line in text["decode_all"].splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == layers and all(
        "mla:scores" in c for c in calls), calls
    assert text["prefill_chunk"].count("ragged-dot") >= 3


# the programs the benchmark's cells time, each with the kernel choice the
# chip makes for it (compile_for_v5e.py at the real sizes: 36 custom calls
# in 12 layers of multi_step; in serving the latent decode kernel, one a
# layer of mistral_small4_ep4's decode step, since PR 30, and the dense
# decode kernel, one a FULL layer of 128-wide heads on a bf16 cache —
# falcon_h1_34b_1chip's and laguna_xs2_1chip's decode steps —, since PR 34)
CELL_PROGRAMS = [
    ("bert_osdi22", "multi_step", 3),          # flash fwd + dq + dkv a layer
    ("lm_osdi22w", "decode_all", 0),
    ("lm_osdi22w", "prefill_chunk", 0),
    ("lm_osdi22w", "prefill_last_chunk", 0),
    ("mistral_small4_ep4", "decode_all", 1),     # the latent decode core
    ("mistral_small4_ep4", "prefill_chunk", 0),
    ("mistral_small4_ep4", "prefill_last_chunk", 0),
    # the state-space mixer is XLA's, the recurrent state donated beside
    # the K/V; grouped-KV rotary attention: the decode kernel in the decode
    # step, the reference chain in the chunks
    ("falcon_h1_34b_1chip", "decode_all", 1),
    ("falcon_h1_34b_1chip", "prefill_chunk", 0),
    ("falcon_h1_34b_1chip", "prefill_last_chunk", 0),
    # two layers here: the full layer's decode kernel and none in the
    # window layer (its ring returns before the selection); the gate and
    # the sigmoid router are XLA's. Cut to 16 experts (100 MB of them, all
    # hit by 8 slots x 8) the expert layer keeps the few-rows form in every
    # program: `small_experts` sends the cell's own 256 through the tiled
    # grouped matmul (`tiled_experts_decode` / `_chunk` above compile those)
    ("laguna_xs2_1chip", "decode_all", 1),
    ("laguna_xs2_1chip", "prefill_chunk", 0),
    ("laguna_xs2_1chip", "prefill_last_chunk", 0),
    # eight layers, every kind: the decode kernel once for each layer that
    # reads the full layer's cache (itself and the cross layer: 40 maps of
    # 128 lanes over 10 key pairs); Mamba-1 is XLA's; a chunk that is not
    # the last runs nothing past the full layer
    ("phi4_mini_flash_1chip", "decode_all", 2),
    ("phi4_mini_flash_1chip", "prefill_chunk", 0),
    ("phi4_mini_flash_1chip", "prefill_last_chunk", 0),
]
# (argument bytes, temporary bytes) of each, by `memory_analysis()` at the
# sizes `cell_programs` builds: what the parent commit of PR 33 compiled to,
# read on both trees before `window`, `head_gate`, `partial_rotary_factor`
# and `scoring` went in — at their defaults the programs are the same; the
# two `decode_all` that hold the dense decode kernel re-read at PR 34 (a
# full layer's three row counters among the arguments: 1,536 B in their
# tiles), every other line as it was. `mistral_small4_ep4`'s chunks attend
# a ladder of prefixes of their batch-1 holder, but this holder (256 + 63
# rows) is one rung: its prefill lines are the bytes they were
# (`test_latent_chunk_attends_a_ladder_of_prefixes_on_v5e` holds the cell's)
CELL_PROGRAM_BYTES = {
    ("bert_osdi22", "multi_step"): (105073152, 121645056),
    ("lm_osdi22w", "decode_all"): (71371776, 1225728),
    ("lm_osdi22w", "prefill_chunk"): (13116416, 0),
    ("lm_osdi22w", "prefill_last_chunk"): (73986048, 2193408),
    ("mistral_small4_ep4", "decode_all"): (98597888, 0),
    ("mistral_small4_ep4", "prefill_chunk"): (7071232, 0),
    ("mistral_small4_ep4", "prefill_last_chunk"): (98841088, 3193344),
    ("falcon_h1_34b_1chip", "decode_all"): (891958272, 0),
    ("falcon_h1_34b_1chip", "prefill_chunk"): (113285120, 0),
    ("falcon_h1_34b_1chip", "prefill_last_chunk"): (894750208, 1290240),
    ("laguna_xs2_1chip", "decode_all"): (313025024, 6188032),
    ("laguna_xs2_1chip", "prefill_chunk"): (172438528, 0),
    ("laguna_xs2_1chip", "prefill_last_chunk"): (315381760, 4584448),
    ("phi4_mini_flash_1chip", "decode_all"): (1747979264, 14357504),
    ("phi4_mini_flash_1chip", "prefill_chunk"): (1133830144, 9580032),
    ("phi4_mini_flash_1chip", "prefill_last_chunk"): (1753339904, 17732608),
}
# layers a configuration is cut to here: one, unless its first layer is not
# its usual one (laguna's is dense and full: the second is a window layer
# over experts) or one layer holds too few kinds (SambaY's plan follows
# from the depth: eight is the least that holds a GMU and a cross layer)
CELL_LAYERS = {"laguna_xs2_1chip": 2, "phi4_mini_flash_1chip": 8}


@pytest.fixture(scope="module")
def cell_programs(v5e):
    """config name -> {program: (jitted fn, argument shapes on the chip)},
    each configuration at its published widths, one layer, a small
    vocabulary and deployment, with shapes for parameters; built once."""
    from unittest import mock

    from benchmark import harness, traffic
    from benchmark.tests.compile_ms4_for_v5e import programs
    from flexflow_tpu.runtime.executor import Executor

    small = dict(num_slots=8, max_len=256, prefill_chunk_tokens=64)
    built = {}

    def shapes(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=v5e), tree)

    def bert(cfg):
        # 8 rows of 512 tokens a chip: past the crossover, as the cell's 64
        batch, k, seq = 8, 2, int(cfg["sequence_length"])
        cfg["deployment"].update(per_chip_batch=batch, steps_per_execution=k)
        builder = harness.module_of("configs", cfg["builder"])
        real_init = Executor.init_params
        with mock.patch.object(builder, "install_weights",
                               lambda *a: None), \
                mock.patch.object(
                    Executor, "init_params",
                    lambda self, key: jax.eval_shape(
                        lambda k_: real_init(self, k_), key)):
            model = builder.build_program(
                cfg, traffic.load_traffic("train_packed_512"), 1, 0)
        i32 = lambda *s: jax.ShapeDtypeStruct(s, I32, sharding=v5e)
        return {"multi_step": (model._get_multi_step().__wrapped__, (
            shapes(model.params), shapes(model.opt_state),
            shapes(model.state), {model.input_ops[0].name: i32(k, batch, seq)},
            i32(k, batch, seq, 1),
            jax.ShapeDtypeStruct((k, 2), jnp.uint32, sharding=v5e)))}

    def get(name, vocab_size=512):
        if (name, vocab_size) not in built:
            cfg = harness.load_config(name)
            cfg.update(num_hidden_layers=CELL_LAYERS.get(name, 1),
                       vocab_size=vocab_size)
            with mock.patch.object(jax, "default_backend", lambda: "tpu"):
                if cfg["runner"] == "train_fit":
                    built[name, vocab_size] = bert(cfg)
                else:
                    if "n_routed_experts" in cfg:
                        cfg.update(n_routed_experts=4,
                                   moe_intermediate_size=256)
                    if "num_experts" in cfg:     # holds all, chooses 8
                        cfg.update(num_experts=16, n_routed_experts=16)
                    cfg["deployment"] = dict(cfg["deployment"], **small)
                    built[name, vocab_size] = programs(cfg, v5e)
        return built[name, vocab_size]

    return get


@pytest.mark.parametrize("config,program,custom_calls_a_layer",
                         CELL_PROGRAMS)
def test_cell_program_holds_the_cells_kernel_choice(
        cell_programs, config, program, custom_calls_a_layer):
    """With default settings on a TPU every cell's program lowers to the
    registry's one rule: flash's three custom calls a layer in the
    training step; in the serving programs the latent decode kernel in
    `mistral_small4_ep4`'s decode step, the dense decode kernel in a full
    layer of `falcon_h1_34b_1chip`'s and `laguna_xs2_1chip`'s, and no
    Pallas call anywhere else (64-wide heads on a float32 cache, a
    window's ring and every chunk keep the reference chain; prefill keeps
    the expanded path), and to the bytes it is pinned at
    (`CELL_PROGRAM_BYTES`)."""
    from unittest import mock

    fn, args = cell_programs(config)[program]
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        jax.clear_caches()
        compiled = fn.lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == custom_calls_a_layer
    ma = compiled.memory_analysis()
    assert (ma.argument_size_in_bytes, ma.temp_size_in_bytes) == \
        CELL_PROGRAM_BYTES[config, program]


def test_latent_chunk_attends_a_ladder_of_prefixes_on_v5e(v5e, monkeypatch):
    """`mistral_small4_ep4`'s `prefill_chunk` at the cell's batch-1 holder
    (4,096 rows and 511 of slack, chunks of 512), two layers at the
    published widths: the first layer's latent attention (the second's
    output is read by no one in a chunk that is not the last) is ONE
    conditional under `mla:` with a branch per entry of the ladder (512,
    1,024, 2,048, 4,096 and the whole 4,607). The temporaries are the
    whole holder's (the largest branch: 310.6 MB at two layers) and the
    conditional's result, the 4 MiB context it hands `mla:out` (4.6 MB in
    all): not a second set of scores. `CELL_PROGRAM_BYTES`' holder of 256
    + 63 rows is two bases or fewer: one branch, the bytes as they were."""
    import re
    from unittest import mock

    from benchmark import harness
    from benchmark.tests.compile_ms4_for_v5e import programs
    from flexflow_tpu.ops import latent_attention as mla

    cfg = harness.load_config("mistral_small4_ep4")
    cfg.update(num_hidden_layers=2, vocab_size=512, n_routed_experts=4,
               moe_intermediate_size=256)
    cfg["deployment"] = dict(cfg["deployment"], num_slots=8)
    compiled = []
    for floor in (mla.PREFILL_PREFIX_FLOOR, 10**6):
        monkeypatch.setattr(mla, "PREFILL_PREFIX_FLOOR", floor)
        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            fn, args = programs(cfg, v5e)["prefill_chunk"]
            jax.clear_caches()
            compiled.append(fn.lower(*args).compile())
    bounded, whole = compiled
    conds = [line for line in bounded.as_text().splitlines()
             if " conditional(" in line]
    assert len(conds) == 1 and "mla:" in conds[0], conds
    branches = re.search(r"branch_computations=\{([^}]*)\}", conds[0])
    assert len(branches.group(1).split(",")) == 5
    assert " conditional(" not in whole.as_text()
    temp = lambda c: c.memory_analysis().temp_size_in_bytes
    assert 0 <= temp(bounded) - temp(whole) < 8 * 2**20


@pytest.mark.parametrize("config", sorted(
    {c for c, prog, _ in CELL_PROGRAMS if prog == "prefill_last_chunk"}))
def test_prefill_programs_run_the_head_for_the_sampled_row_alone(
        cell_programs, config):
    """The served configurations' two chunked-prefill programs, one layer
    at the published widths, a vocabulary (1,920) that is no other width:
    `prefill_chunk` holds no array whose last dimension is the vocabulary
    (no logits, no distribution, not the head's kernel) and returns the
    batch-1 caches alone; in `prefill_last_chunk` every such array but the
    kernel (whole, or the row blocks the compiler prefetches a small one
    in) has one row (or the 8 a tile pads it to), and the pick and the
    install keep their device names."""
    import re
    from unittest import mock

    from benchmark import harness

    vocab, chunk = 1920, 64
    hidden = int(harness.load_config(config)["hidden_size"])
    progs = cell_programs(config, vocab)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        jax.clear_caches()
        compiled = {name: progs[name][0].lower(*progs[name][1]).compile()
                    for name in ("prefill_chunk", "prefill_last_chunk")}
    wide = {name: {tuple(int(d) for d in m.group(1).split(",") if d)
                   for m in re.finditer(rf"\w+\[((?:\d+,)*){vocab}\]",
                                        c.as_text())}
            for name, c in compiled.items()}
    assert not wide["prefill_chunk"], wide
    fn, args = progs["prefill_chunk"]
    small = args[2]
    out = jax.eval_shape(fn, *args)
    assert jax.tree.structure(out) == jax.tree.structure(small)
    assert [a.shape for a in jax.tree.leaves(out)] == [
        a.shape for a in jax.tree.leaves(small)]
    if not harness.load_config(config).get("tie_word_embeddings"):
        # the head's kernel (a tied head reads the (vocab, hidden) table)
        assert (hidden,) in wide["prefill_last_chunk"]
    rows = {lead for lead in wide["prefill_last_chunk"]
            if not (len(lead) == 1 and lead[0] > chunk
                    and hidden % lead[0] == 0)}
    assert rows and all(np.prod(lead, dtype=int) <= 8 for lead in rows), rows
    text = compiled["prefill_last_chunk"].as_text()
    assert "sample:pick" in text and "kv:scatter_span" in text
