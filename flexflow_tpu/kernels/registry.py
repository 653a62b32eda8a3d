"""The one place that decides Pallas kernel or reference lowering.

`KERNELS.select(family, ...)` is a pure function of what the code can
observe, first match wins:

 1. `param` — the op's own `use_flash=True/False` (the explicit per-op
    lane; how CPU tests force the interpret-mode kernel);
 2. `KERNELS.override(family, impl)` — how the parity tests and
    chip_smoke.py force one side;
 3. platform — Pallas compiles only on a TPU; any other backend gets the
    reference (`jax.default_backend()`, looked up at call time);
 4. shape — `attention`: `flash_crossover`; `latent_decode`: pallas (its
    call site asks for one query a slot alone, and there the kernel reads
    the filled rows where the reference reads the allocated ones: 1.9
    against 8.0 ms a decode iteration of `ms4_decode_sat`, PERF.md
    section 6, PR 30); `attention_decode` (one query a slot on the dense
    cache): `filled_rows_decode` — the same read, where the kernel has a
    timed row; `attention_decode_mq` (chunks, speculative verify):
    reference (its kernel in kernels/pallas/decode.py walks every
    allocated block and is reachable by override alone);
    `grouped_experts`: `small_experts` (two crossings: the compute ridge,
    and a step whose assignments miss a good part of the experts).

The mesh is the call sites' business (GSPMD cannot partition a Mosaic
kernel): ops/attention.py runs flash under shard_map (`_on_mesh`) and
keeps the decode reference chain where `ctx.gspmd_partitioned()`, as
ops/latent_attention.py does.

Every recorded selection bumps `ff_kernel_selected_total{op,impl}`;
`CostModel.kernel_time_factor` asks the same `select` so the search
prices what the lowering emits (docs/kernels.md).
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, Optional, Tuple

import jax

FAMILIES = ("attention", "attention_decode", "attention_decode_mq",
            "latent_decode", "grouped_experts")

# per-chip f32 score-matrix bytes at the v5e-measured crossover: flash wins
# from seq ~512 up; below that the blocks are too small to fill the grid and
# XLA's fused einsum stays ahead (round 4's ablation)
FLASH_SCORE_BYTES_CROSSOVER = 1e8

# measured step-time ratio, flash to the einsum core at the BERT bench config
# (round 4's ablation: 39.1 against 44.0 ms a step); the simulator's factor
FLASH_COST_GAIN = 0.89


def flash_crossover(batch: int, heads: int, q_len: int, k_len: int,
                    dp: int = 1) -> bool:
    score_bytes = (4.0 * batch * heads * q_len * k_len) / max(dp, 1)
    return score_bytes > FLASH_SCORE_BYTES_CROSSOVER


# the widest matrix of one expert that the tiled grouped matmul takes as ONE
# VMEM block (two of them in flight beside the row tiles, under the 16 MiB
# a kernel may scope on a v5e), and the token rows past which sending every
# row through every held expert leaves the memory's floor (a v5e, 256
# experts of 2048 x 512 chosen 8 at a time, few-rows / tiled ms a layer:
# 128 rows 2.18 / 2.24, 256 2.72 / 2.40, 384 3.36 / 2.50, 512 4.39 / 2.62,
# 640 5.44 / 2.74; PERF.md section 6, PR 33)
EXPERT_BLOCK_BYTES = 2 << 20
EXPERT_RIDGE_ROWS = 256
# the bytes of held experts a step's assignments are expected to leave
# unchosen, past which the few-rows form's stream of every HELD expert
# costs more than the tiled form's sort, gather and one grid step a group
# (the same v5e and experts, whole op, few-rows / tiled ms a layer by token
# rows, with the expected hit share and the unchosen MB of the 1,611 held: 8
# rows 0.22 1,254 2.15 / 0.52, 16 0.39 976 2.15 / 0.90, 24 0.53 760 2.15 /
# 1.19, 40 0.71 460 2.15 / 1.60, 64 0.87 217 2.16 / 1.92, 96 0.95 80 2.16 /
# 2.15, 128 0.98 29 2.16 / 2.22: they cross at 96 rows, where the tiled
# form's ~0.1 ms of extra fixed cost is 80 MB of the memory's stream;
# PERF.md section 6, PR 36)
EXPERT_UNREAD_BYTES = 128 << 20


def expected_hit_share(assignments: int, experts_total: int) -> float:
    """The share of the experts that `assignments` choices of a uniform
    router over `experts_total` hit at least once; of the experts held
    anywhere, the same share."""
    return 1.0 - (1.0 - 1.0 / experts_total) ** assignments


def small_experts(rows: int, k: int, held: int, experts_total: int,
                  matrix_bytes: int) -> bool:
    """Whether routed experts over `rows` token rows that each choose `k` of
    `experts_total`, `held` of them here with three matrices of
    `matrix_bytes` each, take the tiled grouped matmul (ops/moe.py
    `_tiled_dot`) in place of the few-rows form. Two crossings: past the
    ridge every row through every expert is compute the chosen experts do
    not need; far under it, where the step's assignments are expected to
    miss a good part of the held experts, every one of them streamed is
    bytes the tiled form does not read. The kernel as it is tiles the
    sorted rows alone, so on either side a matrix is one block."""
    unread = (1.0 - expected_hit_share(rows * k, experts_total)) * (
        held * 3 * matrix_bytes)
    return matrix_bytes <= EXPERT_BLOCK_BYTES and (
        rows > EXPERT_RIDGE_ROWS or unread > EXPERT_UNREAD_BYTES)


# what the dense decode kernel (kernels/pallas/decode.py
# `fused_decode_attention`) is admitted for: heads whose slice of the packed
# cache row is whole 128-lane tiles, a cache of 2-byte values, a length that
# whole blocks divide. Timed on a v5e, the core alone, ms a layer, reference
# chain / kernel at 512-row blocks: 40 slots x 12,288 rows x 1,024 lanes, 48
# heads on 8 KV heads, 2.73 / 0.98; 96 x 2,048 x 512 lanes, 20 on 4, 0.59 /
# 0.25 (PERF.md section 6, PR 34). 64-lane heads and a float32 cache
# (`lm_osdi22w`: products at `highest` under a 1e-4 limit) have no timed
# row and keep the chain.
DECODE_HEAD_LANES = 128
DECODE_VALUE_BYTES = 2


def filled_rows_decode(head_dim: int, value_bytes: int, max_len: int) -> bool:
    """Whether one query a slot over a dense cache of `max_len` rows of
    `value_bytes`-wide values, `head_dim` a head, takes the kernel that
    reads the rows each slot has filled."""
    from .pallas.latent_decode import block_rows

    return (head_dim % DECODE_HEAD_LANES == 0
            and value_bytes == DECODE_VALUE_BYTES
            and block_rows(max_len) is not None)


@dataclasses.dataclass(frozen=True)
class KernelChoice:
    """One selection verdict; truthy iff the pallas impl was chosen."""

    family: str
    impl: str    # "pallas" | "reference"
    reason: str  # param | override | backend | shape

    def __bool__(self) -> bool:
        return self.impl == "pallas"


def _known(family: str) -> None:
    if family not in FAMILIES:
        raise KeyError(f"unknown kernel family {family!r}; of {FAMILIES}")


class KernelRegistry:
    def __init__(self):
        self._overrides: Dict[str, str] = {}

    @contextlib.contextmanager
    def override(self, family: str, impl: str):
        """Force one family's impl for the duration (interpret mode
        engages automatically off-TPU)."""
        _known(family)
        if impl not in ("pallas", "reference"):
            raise ValueError(f"impl must be pallas or reference, got {impl!r}")
        prev = self._overrides.get(family)
        self._overrides[family] = impl
        try:
            yield
        finally:
            if prev is None:
                self._overrides.pop(family, None)
            else:
                self._overrides[family] = prev

    def select(self, family: str, *, param: Optional[bool] = None,
               scores: Optional[Tuple[int, int, int, int, int]] = None,
               experts: Optional[Tuple[int, int, int, int, int]] = None,
               decode: Optional[Tuple[int, int, int]] = None,
               record: bool = True) -> KernelChoice:
        """Pick the impl for one op instance. `param` is the op's own
        explicit setting (attention's use_flash); `scores` the attention
        instance's `flash_crossover` arguments (batch, heads, q_len,
        k_len, dp), `experts` the routed product's `small_experts`
        arguments (token rows, experts a token, experts held, experts in
        all, bytes of one expert's matrix), `decode` the
        dense decode step's `filled_rows_decode` arguments (head width,
        bytes of a cached value, cache rows) — `attention_decode_mq` has
        no shape predicate and stays on the reference, `latent_decode` is
        asked for one query a slot alone and takes the kernel;
        `record=False` skips the
        selection counter (the cost simulator asks thousands of times per
        search)."""
        _known(family)
        if param is not None:
            choice = KernelChoice(
                family, "pallas" if param else "reference", "param")
        elif family in self._overrides:
            choice = KernelChoice(family, self._overrides[family], "override")
        elif jax.default_backend() != "tpu":
            choice = KernelChoice(family, "reference", "backend")
        else:
            wins = family == "latent_decode" or (
                family == "attention" and scores is not None
                and flash_crossover(*scores)) or (
                family == "grouped_experts" and experts is not None
                and small_experts(*experts)) or (
                family == "attention_decode" and decode is not None
                and filled_rows_decode(*decode))
            choice = KernelChoice(
                family, "pallas" if wins else "reference", "shape")
        if record:
            from ..obs.registry import REGISTRY

            REGISTRY.counter(
                "ff_kernel_selected_total",
                "Kernel-tier selections by op family and implementation",
                labels=("op", "impl")).inc(op=family, impl=choice.impl)
        return choice


KERNELS = KernelRegistry()
