"""KernelRegistry: one selection path for the fused-kernel tier.

Each op family has a fused Pallas implementation (kernels/pallas/, plus
kernels/flash_attention.py for the attention family) and a reference
einsum/jnp lowering — the op's original code path, which doubles as the
parity oracle. Every consumer — the attention lowering's flash choice,
the norm/softmax ops, the decode hot loop, loss/metrics reductions, and
the cost simulator — asks the SAME `KERNELS.select(family)`, so there
is exactly one policy and one config knob (`--kernel-impl`) instead of
the ad-hoc per-op heuristics that grew up around `use_flash`. (The
registry stores selection POLICY only; each call site imports its fused
kernel directly — there is no runtime dispatch table to keep in sync.)

Selection order (first match wins):

 1. per-op param (`use_flash=True/False` on the attention op) — the
    explicit per-op lane; the old CPU-test "force True" special case is
    now this, spelled as a registry decision;
 2. a test/context override installed with `KERNELS.override(family,
    impl)` — how the interpret-mode parity suite forces Pallas on CPU;
 3. the config knob `--kernel-impl` (`pallas`/`reference` for every
    family, or `family=impl,...` per family). Call sites that have a
    config in hand (op lowerings via ctx.config, the cost model) pass
    it to `select(config=...)` so two models with different knobs in
    one process never cross-pollute; config-less consumers (the loss/
    metrics reductions) use the last `configure()`d default;
 4. auto: backend capability first — Pallas compiles only on TPU, so
    any other backend gets the reference impl (the kernels still RUN
    anywhere under interpret mode, but interpreted Pallas loses to
    XLA's fused CPU code, so nothing auto-selects it off-TPU) — then
    the per-op-family residuals recorded by `obs.calibrate()`/`refit`
    into the FittedProfile (`config.fitted_profile_file`): a family
    whose measured cost runs >= RESIDUAL_CANDIDATE_THRESHOLD over the
    roofline prediction is exactly the op the fused kernel was built
    for. `attention_decode` inherits the `attention` family's residual
    (the decode step never appears as a calibratable graph op, but its
    core IS the attention math). `attention` keeps its measured
    score-bytes crossover heuristic — as the no-evidence default AND as
    a size gate under residual evidence (a residual fitted at seq 2048
    must not force flash onto a seq-128 model below the crossover).
    Everything else defaults to reference until evidence or the knob
    says otherwise; in particular `reduction` — never a graph op, so no
    residual can ever nominate it — is knob-opt-in only, because a Mosaic
    kernel has no GSPMD partitioning rule: inside a step jitted over a
    mesh the chip's compiler refuses it. (The attention op runs its flash
    kernels under shard_map for that reason, and the norm families stay
    on their reference lowering on a mesh — ops/attention.py `_on_mesh`,
    core/op.py `LoweringContext.gspmd_partitioned`.)

Every recorded selection bumps `ff_kernel_selected_total{op,impl}`
(op = family), and `CostModel` prices pallas-selected families with
`PALLAS_COST_GAIN` so the Unity search sees the kernel tier when it
ranks strategies (docs/kernels.md).
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Callable, Dict, Optional

from ..ffconst import OpType

FAMILIES = ("attention", "attention_decode", "attention_decode_mq",
            "layernorm", "rmsnorm", "softmax", "reduction")

# graph-op families the cost simulator can price (serving decode and the
# loss reduction never appear as PCG ops)
OPTYPE_FAMILY = {
    OpType.MULTIHEAD_ATTENTION: "attention",
    OpType.LAYERNORM: "layernorm",
    OpType.RMSNORM: "rmsnorm",
    OpType.SOFTMAX: "softmax",
}

# families whose residual evidence comes from ANOTHER family's
# calibration rows (the decode steps are the attention core over the KV
# cache; they never appear as their own graph ops)
RESIDUAL_ALIAS = {"attention_decode": "attention",
                  "attention_decode_mq": "attention"}

# flash-attention auto policy, shared by ops/attention.py _use_flash and
# CostModel.kernel_time_factor so search pricing can never de-sync from
# what the lowering emits: the per-chip f32 score-matrix bytes at the
# v5e-measured crossover (flash wins from seq ~512 up; below that the
# blocks are too small to fill the grid and XLA's fused einsum stays
# ahead — r4 ablation, kernels/flash_attention.py)
FLASH_SCORE_BYTES_CROSSOVER = 1e8


def flash_crossover(batch: int, heads: int, q_len: int, k_len: int,
                    dp: int = 1) -> bool:
    score_bytes = (4.0 * batch * heads * q_len * k_len) / max(dp, 1)
    return score_bytes > FLASH_SCORE_BYTES_CROSSOVER

# modeled step-time factor of the fused impl relative to the unfused
# lowering, applied by CostModel ONLY when the registry selects pallas
# AND the lowering would actually emit the kernel (the trailing-axis
# gates live in CostModel.kernel_time_factor). attention = the r4 flash
# ablation (39.1 vs 44.0 ms/step at the BERT bench config); the
# norm/softmax/reduction factors model the saved HBM round-trips of the
# unfused mean/var/normalize (resp. exp/sum) passes — refit's
# step_scale absorbs whatever these get wrong, uniformly.
PALLAS_COST_GAIN = {
    "attention": 0.89,
    "attention_decode": 0.80,
    # the multi-query variant amortizes the cache stream over C queries
    # on top of the single-query kernel's saved logits round-trip
    "attention_decode_mq": 0.75,
    "layernorm": 0.70,
    "rmsnorm": 0.70,
    "softmax": 0.75,
    "reduction": 0.85,
}

# a family whose calibration residual (measured/predicted, median over
# its ops) reaches this is a fusion candidate: the backend is leaving
# that much of the roofline on the table. This is only the NO-PROFILE
# default: a FittedProfile carrying `kernel_residual_thresholds`
# (obs/refit.fit_kernel_thresholds — derived from real before/after
# kernel measurements: a family's threshold is the residual the FUSED
# impl itself achieves, so reference-vs-roofline evidence past it means
# switching pays) wins per family, then the
# `--kernel-residual-threshold` config knob
# (FFConfig.kernel_residual_threshold, docs/kernels.md), then this
# constant.
RESIDUAL_CANDIDATE_THRESHOLD = 1.10


@dataclasses.dataclass(frozen=True)
class KernelChoice:
    """One selection verdict; truthy iff the pallas impl was chosen."""

    family: str
    impl: str    # "pallas" | "reference"
    reason: str  # param | override | config | backend | residual |
    #              heuristic | default

    def __bool__(self) -> bool:
        return self.impl == "pallas"


class KernelRegistry:
    def __init__(self):
        self._config_overrides: Dict[str, str] = {}
        self._overrides: Dict[str, str] = {}
        self._residuals: Dict[str, float] = {}
        self._threshold: float = RESIDUAL_CANDIDATE_THRESHOLD
        # per-family FITTED thresholds from the profile (measured
        # before/after evidence); a family present here ignores the knob
        self._fitted_thresholds: Dict[str, float] = {}
        self.residual_source: Optional[str] = None
        # per-call config resolution caches: spec string -> overrides,
        # (profile path, mtime, size) -> (residuals, fitted thresholds)
        self._spec_cache: Dict[str, Dict[str, str]] = {}
        self._residual_cache: Dict[tuple, tuple] = {}

    # -- configuration -----------------------------------------------------
    @staticmethod
    def parse_spec(spec: str) -> Dict[str, str]:
        """`--kernel-impl` value -> per-family override map. Accepts
        `auto` (empty map), a bare `pallas`/`reference` (every family),
        or `family=impl[,family=impl...]` (impl `auto` clears one
        family)."""
        spec = (spec or "auto").strip()
        if spec == "auto":
            return {}
        if spec in ("pallas", "reference"):
            return {f: spec for f in FAMILIES}
        out: Dict[str, str] = {}
        for part in spec.split(","):
            fam, sep, impl = part.partition("=")
            fam, impl = fam.strip(), impl.strip()
            if (not sep or fam not in FAMILIES
                    or impl not in ("pallas", "reference", "auto")):
                raise ValueError(
                    f"bad --kernel-impl term {part!r}: want auto, pallas, "
                    "reference, or family=impl[,...] with families "
                    f"{FAMILIES}")
            if impl != "auto":
                out[fam] = impl
        return out

    def _spec_overrides(self, spec: str) -> Dict[str, str]:
        spec = (spec or "auto").strip()
        hit = self._spec_cache.get(spec)
        if hit is None:
            hit = self._spec_cache[spec] = self.parse_spec(spec)
        return hit

    def _profile_evidence(self, path: Optional[str]) -> tuple:
        """(residuals, fitted thresholds) of the profile at `path` —
        both {} when there is no usable profile."""
        if not path:
            return {}, {}
        import os

        # cache keyed by file identity, not just path: a refit that
        # overwrites fitted_profile.json must not serve stale evidence
        try:
            st = os.stat(path)
            key = (path, st.st_mtime_ns, st.st_size)
        except OSError:
            key = (path, -1, -1)
        hit = self._residual_cache.get(key)
        if hit is not None:
            return hit
        from ..obs.refit import FittedProfile, FittedProfileError

        try:
            prof = FittedProfile.load(path)
            out = (
                {k: float(v)
                 for k, v in (prof.op_family_residuals or {}).items()},
                {k: float(v) for k, v in
                 (prof.kernel_residual_thresholds or {}).items()},
            )
        except FittedProfileError:
            # the machine-model load path raises this loudly; the
            # registry just declines the evidence
            out = ({}, {})
        self._residual_cache[key] = out
        return out

    def configure(self, config) -> None:
        """Adopt a model config as the PROCESS DEFAULT: the
        `--kernel-impl` knob plus the per-op-family residual evidence in
        its fitted profile. Called by FFModel.compile() (idempotent).
        Consumers that carry a config (op lowerings, CostModel) pass it
        to select(config=...) and are unaffected by later configure()
        calls from other models; only config-less consumers (the
        loss/metrics reductions) read this default."""
        self._config_overrides = self._spec_overrides(
            getattr(config, "kernel_impl", "auto"))
        self._threshold = float(
            getattr(config, "kernel_residual_threshold",
                    RESIDUAL_CANDIDATE_THRESHOLD))
        path = getattr(config, "fitted_profile_file", None)
        self._residuals, self._fitted_thresholds = \
            self._profile_evidence(path)
        self.residual_source = path if self._residuals else None

    def residual(self, family: str) -> Optional[float]:
        return self._residuals.get(family)

    @contextlib.contextmanager
    def override(self, family: str, impl: str):
        """Force one family's impl for the duration (parity tests force
        `pallas` on CPU through this; interpret mode engages
        automatically off-TPU)."""
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

    # -- selection ---------------------------------------------------------
    def _counter(self):
        from ..obs.registry import REGISTRY

        return REGISTRY.counter(
            "ff_kernel_selected_total",
            "Kernel-tier selections by op family and implementation",
            labels=("op", "impl"))

    def select(self, family: str, *, param: Optional[bool] = None,
               config=None, backend: Optional[str] = None,
               heuristic: Optional[Callable[[], bool]] = None,
               record: bool = True) -> KernelChoice:
        """Pick the impl for one op instance. `param` is the op's own
        explicit setting (attention's use_flash); `config` the model's
        FFConfig when the caller has one (its knob + fitted profile win
        over the process default set by configure()); `heuristic` a
        zero-arg measured-policy callback consulted only when no
        override and no residual evidence applies; `record=False` skips
        the selection counter (the cost simulator peeks thousands of
        times per search)."""
        if family not in FAMILIES:
            raise KeyError(f"unknown kernel family {family!r}; "
                           f"families: {FAMILIES}")
        config_overrides = (self._spec_overrides(
            getattr(config, "kernel_impl", "auto"))
            if config is not None else self._config_overrides)
        if param is not None:
            choice = KernelChoice(
                family, "pallas" if param else "reference", "param")
        elif family in self._overrides:
            choice = KernelChoice(family, self._overrides[family], "override")
        elif family in config_overrides:
            choice = KernelChoice(
                family, config_overrides[family], "config")
        else:
            be = backend if backend is not None else _default_backend()
            if be != "tpu":
                choice = KernelChoice(family, "reference", "backend")
            else:
                if config is not None:
                    residuals, fitted = self._profile_evidence(
                        getattr(config, "fitted_profile_file", None))
                else:
                    residuals, fitted = (self._residuals,
                                         self._fitted_thresholds)
                # threshold resolution: the profile's FITTED per-family
                # threshold (measured before/after evidence,
                # obs/refit.fit_kernel_thresholds) > the config knob >
                # the hand-set default. The alias maps a derived family
                # (attention_decode*) onto its evidence family for the
                # residual AND the fitted threshold.
                evidence_fam = RESIDUAL_ALIAS.get(family, family)
                threshold = fitted.get(family, fitted.get(evidence_fam))
                if threshold is None:
                    threshold = (float(getattr(
                        config, "kernel_residual_threshold",
                        self._threshold))
                        if config is not None else self._threshold)
                r = residuals.get(evidence_fam)
                # a family with a measured size policy (attention's
                # crossover) keeps it as a GATE even under residual
                # evidence: the residual says the family underperforms
                # at the profiled shape, the heuristic says whether THIS
                # instance is in the regime where the fused kernel wins
                if (r is not None and r >= threshold
                        and (heuristic is None or heuristic())):
                    choice = KernelChoice(family, "pallas", "residual")
                elif heuristic is not None:
                    choice = KernelChoice(
                        family, "pallas" if heuristic() else "reference",
                        "heuristic")
                else:
                    choice = KernelChoice(family, "reference", "default")
        if record:
            self._counter().inc(op=family, impl=choice.impl)
        return choice

    def cost_factor(self, family: Optional[str], *, param=None,
                    config=None, heuristic=None) -> float:
        """Step-time factor the simulator applies to an op of `family`
        under the current selection policy — 1.0 for reference (or
        non-tier ops), PALLAS_COST_GAIN[family] when pallas would be
        selected. Never bumps the selection counter."""
        if family is None:
            return 1.0
        choice = self.select(family, param=param, config=config,
                             heuristic=heuristic, record=False)
        return PALLAS_COST_GAIN[family] if choice else 1.0


def _default_backend() -> str:
    import jax

    return jax.default_backend()


# THE process-wide registry; FFModel.compile()/serving configure it from
# their FFConfig, everything else just selects.
KERNELS = KernelRegistry()
