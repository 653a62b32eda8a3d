"""Runtime configuration and command-line flags.

TPU-native counterpart of the reference's FFConfig (include/flexflow/config.h:92-160)
and FFConfig::parse_args (src/runtime/model.cc:3596-3731). Instead of Legion
`-ll:gpu` worker counts, the device pool is the set of JAX devices (TPU chips),
organized into a `jax.sharding.Mesh` by the strategy layer.
"""
from __future__ import annotations

import dataclasses
import sys
from typing import List, Optional, Sequence

from .ffconst import CompMode

# Hard limits mirroring config.h:40-53 (informational; nothing in the TPU
# runtime statically allocates against them).
MAX_NUM_INPUTS = 2048
MAX_NUM_WEIGHTS = 2048
MAX_NUM_OUTPUTS = 2048
MAX_NUM_WORKERS = 8192
MAX_TENSOR_DIM = 8


@dataclasses.dataclass
class FFIterationConfig:
    """Per-iteration attributes (reference: config.h:162-167)."""

    seq_length: int = -1

    def reset(self) -> None:
        self.seq_length = -1


@dataclasses.dataclass
class FFConfig:
    """Global configuration.

    Flags mirror the reference CLI surface (README.md:45-74): `-b/--batch-size`,
    `-e/--epochs`, `--budget/--search-budget`, `--alpha/--search-alpha`,
    `--only-data-parallel`, `--enable-parameter-parallel`,
    `--enable-attribute-parallel`, `--search-overlap-backward-update`,
    `--base-optimize-threshold`, `--substitution-json`, `--export`/`--import`,
    `--memory-search`, `--profiling`.

    TPU-native addition beyond the reference surface:
    `--steps-per-execution` (K optimizer steps per jitted dispatch).
    """

    batch_size: int = 64
    epochs: int = 1
    iterations: int = 1
    # K optimizer steps per jitted device dispatch (tf.keras
    # steps_per_execution role; FFModel.fit flag of the same name)
    steps_per_execution: int = 1
    # Collective lowering of the searched reduction plan
    # (runtime/collectives.py, docs/machine.md "Lowering"): "gspmd" lets
    # XLA synthesize the gradient-sync schedule (the historical path),
    # "explicit" lowers each reduction_plan entry into real per-tier
    # grouped collectives inside the jitted train step (raising a typed
    # CollectiveLoweringError when the plan cannot be lowered), "auto"
    # lowers explicitly only when supported AND the plan crosses a tier
    # boundary — otherwise it falls back to gspmd.
    collective_lowering: str = "gspmd"
    # Gradient-sync bucket size target in bytes (docs/machine.md
    # "Overlap"): on a multi-tier hierarchical machine, synced gradients
    # are grouped into size-targeted buckets issued in backward
    # production order, so each bucket's per-tier collective can overlap
    # the remaining backward compute — the cost model prices the
    # overlapped/exposed split and the explicit lowering executes the
    # same bucket schedule (FFTA072 checks they agree). 0 disables
    # bucketing (per-tensor issue, the pre-bucketing behavior); the
    # knob is inert on flat machines and under
    # search_overlap_backward_update=False (blocking pricing).
    grad_bucket_bytes: int = 25 * 1024 * 1024
    learning_rate: float = 0.01
    weight_decay: float = 0.0001
    # Device pool. num_devices=None -> all visible JAX devices.
    num_devices: Optional[int] = None
    # Explicit device subset (indices into jax.devices()): the mesh is
    # built from exactly these devices. Set by the elastic coordinator to
    # compile onto the SURVIVORS of a chip loss; wins over num_devices.
    device_ids: Optional[List[int]] = None
    # Elastic runtime hook (elastic/detector.py FailureDetector.wrap): the
    # Executor wraps its jitted train-step dispatch with this, so fault
    # injection, failure classification, and retry ride every dispatch.
    elastic_step_wrapper: Optional[object] = None
    num_nodes: int = 1
    # Search knobs
    search_budget: int = 0
    search_alpha: float = 1.2
    base_optimize_threshold: int = 10
    # mesh factorizations that get the expensive cross-segment best-first
    # refinement (the rest keep their segment-DP strategies); raise for
    # exhaustiveness, lower for compile latency on big graphs
    refine_top_k: int = 4
    # Incremental search (search/plan_cache.py, docs/search.md): a
    # content-addressed cache of SearchResults keyed by (pre-rewrite
    # graph, overlaid machine, batch, devices, search knobs). An exact
    # hit skips enumeration entirely (still re-validated through the
    # analysis gate); a near-miss (same graph + knobs, moved machine /
    # batch) seeds warm-started refinement. --no-plan-cache disables;
    # --plan-cache-dir adds disk persistence across processes.
    plan_cache: bool = True
    plan_cache_dir: Optional[str] = None
    plan_cache_capacity: int = 32
    # Warm-started re-planning off a cached near-miss plan
    # (--no-search-warm-start disables; cold enumeration always wins
    # when no seed exists). The refined plan falls back to a cold
    # search when its cost exceeds warm_fallback_tolerance x the warm
    # sweep's cost floor.
    search_warm_start: bool = True
    warm_fallback_tolerance: float = 1.05
    # Reshard-aware re-planning: weight on the plan-distance term — the
    # predicted cost (resharding/cost.py) of redistributing the LIVE
    # weights onto each warm candidate — added to the candidate ranking
    # when a live plan is present (elastic recovery / drift re-plans).
    # 0 disables the term.
    replan_distance_weight: float = 1.0
    # The LIVE plan (resharding.plan_of of the running model) a re-plan
    # is moving away from — set by the elastic coordinator on the
    # configs it hands the rebuild, never from the CLI. Excluded from
    # the plan-cache key; a warm result the distance term biased beyond
    # the cost tolerance is NOT cached (SearchResult.cache_store), so a
    # live-less lookup can never adopt a reshard-biased plan as a hit.
    replan_live_plan: Optional[object] = None
    # Joint substitution x parallelization search: graph rewrites are
    # best-first search actions costed by their optimal parallelization
    # (reference: base_optimize over candidate graphs, substitution.cc:2229).
    # False = rewrites applied greedily before the strategy search.
    joint_search: bool = True
    # strategy-search algorithm: "unity" (the joint search above) or "mcmc"
    # (the MLSys'19 Metropolis annealing, reference model.cc:3286-3358)
    strategy_search: str = "unity"
    # MCMC iteration budget (None = reuse search_budget); setting it > 0
    # with --strategy-search mcmc enables the search even when
    # search_budget is 0
    mcmc_budget: Optional[int] = None
    # propagate accepted configs to same-typed neighbors (reference:
    # FF_USE_PROPAGATE, model.cc:3181)
    mcmc_propagate: bool = False
    only_data_parallel: bool = False
    enable_parameter_parallel: bool = False
    enable_attribute_parallel: bool = False
    # sequence/context parallelism as a SEARCH axis (NEW vs the reference):
    # the Unity search may shard the position dim over a 'seq' mesh axis
    # (ring attention) when enabled
    enable_sequence_parallel: bool = False
    # pipeline parallelism as a SEARCH axis (NEW vs the reference, whose
    # OP_PIPELINE enum ffconst.h:159 is unused): the search may map the
    # graph's repeated-block region onto a 'stage' mesh axis via the GPipe
    # kernel, priced by bubble fraction (S-1)/(M+S-1) + activation transfer
    enable_pipeline_parallel: bool = False
    # GPipe microbatch count M for the 'stage' axis (batch must divide)
    pipeline_microbatches: int = 4
    enable_inplace_optimizations: bool = False
    # collectives overlap compute in the simulator's two-stream schedule
    # (XLA's latency-hiding scheduler does this on TPU); False = collectives
    # serialize onto the compute stream
    search_overlap_backward_update: bool = True
    # Plan sanitizer (analysis/): the Unity search prunes mesh
    # factorizations the cheap static passes reject before the cost
    # simulator prices them; False simulates every divisor tuple (the
    # unpruned comparison baseline — same chosen strategy, more work)
    analysis_prune: bool = True
    # Opt-in search prune (--verify-candidates): run the sharding-flow
    # verifier's cheap layout subset over the top-K simulated candidates
    # and drop any that fail before the winner is chosen — a plan the
    # verifier rejects would only bounce off the compile gate later
    # (docs/analysis.md "Verifier")
    verify_candidates: bool = False
    # Pre-flight plan analysis at compile()/re-plan time: "error" rejects
    # plans with error-severity diagnostics (PlanAnalysisError), "warn"
    # only logs, "off" skips the pipeline
    plan_analysis: str = "error"
    memory_search: bool = False
    memory_budget_mb: float = 16 * 1024.0  # per-chip HBM budget for memory-aware search
    # per-param optimizer-state factor for the search's memory model
    # (compile() sets it from the real optimizer: Adam 3, momentum 2, SGD 1)
    optimizer_state_factor: float = 3.0
    substitution_json_path: Optional[str] = None
    # Measured op costs for the search (reference: the simulator profiles
    # real kernels, simulator.cc:489). None = auto: measure when the default
    # backend is a real accelerator, stay analytic on CPU (tests/dryruns).
    measure_op_costs: Optional[bool] = None
    op_cost_cache_file: Optional[str] = None
    # Use the native C++ search core (src/ffcore, built on the spot) where
    # it covers the search; a failed build is then an error. False = the
    # pure-Python search, which is the reference semantics.
    use_native_search: bool = True
    export_strategy_file: Optional[str] = None
    import_strategy_file: Optional[str] = None
    export_strategy_computation_graph_file: Optional[str] = None
    export_strategy_task_graph_file: Optional[str] = None
    # Execution knobs
    computation_mode: CompMode = CompMode.COMP_MODE_TRAINING
    profiling: bool = False
    seed: int = 0
    # Numerics: compute dtype for matmul-heavy ops (MXU-friendly default).
    allow_mixed_precision: bool = True
    machine_model_version: int = 0
    machine_model_file: Optional[str] = None
    # Fitted machine profile (obs/refit.py): measured coefficient overlay
    # (effective flop rate per dtype, link bandwidth, latency terms) loaded
    # by make_machine_model over the hand-set ChipSpec constants, so every
    # search/simulation prices with measured reality. Written by
    # `python -m flexflow_tpu profile --refit`.
    fitted_profile_file: Optional[str] = None
    print_freq: int = 10
    iteration_config: FFIterationConfig = dataclasses.field(
        default_factory=FFIterationConfig
    )

    @classmethod
    def from_command_line(cls, argv: Optional[Sequence[str]] = None) -> "FFConfig":
        """Build a config from CLI flags (reference: FFConfig ctor parses argv).
        Explicitly opt-in — plain FFConfig() never touches sys.argv, so library
        users' own flags are not hijacked."""
        cfg = cls()
        cfg.parse_args(sys.argv[1:] if argv is None else argv)
        return cfg

    # -- flag parsing (reference: model.cc:3596-3731) ---------------------
    def parse_args(self, argv: Sequence[str]) -> List[str]:
        """Consume known flags from argv; returns the unconsumed remainder."""
        rest: List[str] = []
        i = 0
        args = list(argv)

        def take() -> str:
            nonlocal i
            i += 1
            if i >= len(args):
                raise ValueError(f"flag {args[i - 1]!r} requires a value")
            return args[i]

        while i < len(args):
            a = args[i]
            if a in ("-b", "--batch-size"):
                self.batch_size = int(take())
            elif a in ("-e", "--epochs"):
                self.epochs = int(take())
            elif a in ("-i", "--iterations"):
                self.iterations = int(take())
            elif a == "--steps-per-execution":
                self.steps_per_execution = int(take())
            elif a == "--collective-lowering":
                v = take()
                from .runtime.collectives import COLLECTIVE_LOWERINGS

                if v not in COLLECTIVE_LOWERINGS:
                    raise ValueError(
                        "--collective-lowering must be one of "
                        f"{COLLECTIVE_LOWERINGS}, got {v!r}")
                self.collective_lowering = v
            elif a == "--grad-bucket-bytes":
                v = int(take())
                if v < 0:
                    raise ValueError(
                        "--grad-bucket-bytes must be >= 0 (bytes; 0 "
                        f"disables bucketing), got {v}")
                self.grad_bucket_bytes = v
            elif a in ("--lr", "--learning-rate"):
                self.learning_rate = float(take())
            elif a in ("--wd", "--weight-decay"):
                self.weight_decay = float(take())
            elif a in ("--budget", "--search-budget"):
                self.search_budget = int(take())
            elif a in ("--alpha", "--search-alpha"):
                self.search_alpha = float(take())
            elif a == "--base-optimize-threshold":
                self.base_optimize_threshold = int(take())
            elif a == "--refine-top-k":
                self.refine_top_k = int(take())
            elif a == "--plan-cache-dir":
                self.plan_cache_dir = take()
            elif a == "--plan-cache-capacity":
                v = int(take())
                if v < 1:
                    raise ValueError(
                        f"--plan-cache-capacity must be >= 1, got {v}")
                self.plan_cache_capacity = v
            elif a == "--no-plan-cache":
                self.plan_cache = False
            elif a == "--no-search-warm-start":
                self.search_warm_start = False
            elif a == "--warm-fallback-tolerance":
                v = float(take())
                if not v >= 1.0:
                    raise ValueError(
                        "--warm-fallback-tolerance must be >= 1.0 (a"
                        f" refined/floor cost ratio), got {v}")
                self.warm_fallback_tolerance = v
            elif a == "--replan-distance-weight":
                v = float(take())
                if v < 0:
                    raise ValueError(
                        "--replan-distance-weight must be >= 0"
                        f" (0 disables the term), got {v}")
                self.replan_distance_weight = v
            elif a == "--strategy-search":
                v = take()
                if v not in ("unity", "mcmc"):
                    raise ValueError(
                        f"--strategy-search must be unity or mcmc, got {v!r}")
                self.strategy_search = v
            elif a == "--mcmc-budget":
                self.mcmc_budget = int(take())
            elif a == "--mcmc-propagate":
                self.mcmc_propagate = True
            elif a == "--only-data-parallel":
                self.only_data_parallel = True
            elif a == "--enable-parameter-parallel":
                self.enable_parameter_parallel = True
            elif a == "--enable-attribute-parallel":
                self.enable_attribute_parallel = True
            elif a == "--enable-sequence-parallel":
                self.enable_sequence_parallel = True
            elif a == "--enable-pipeline-parallel":
                self.enable_pipeline_parallel = True
            elif a == "--pipeline-microbatches":
                self.pipeline_microbatches = int(take())
            elif a == "--search-overlap-backward-update":
                self.search_overlap_backward_update = True
            elif a == "--no-analysis-prune":
                self.analysis_prune = False
            elif a == "--verify-candidates":
                self.verify_candidates = True
            elif a == "--plan-analysis":
                v = take()
                if v not in ("error", "warn", "off"):
                    raise ValueError(
                        f"--plan-analysis must be error, warn or off, got {v!r}")
                self.plan_analysis = v
            elif a == "--memory-search":
                self.memory_search = True
            elif a == "--measure-op-costs":
                self.measure_op_costs = True
            elif a == "--no-measure-op-costs":
                self.measure_op_costs = False
            elif a == "--op-cost-cache":
                self.op_cost_cache_file = take()
            elif a == "--memory-budget":
                self.memory_budget_mb = float(take())
            elif a == "--substitution-json":
                self.substitution_json_path = take()
            elif a == "--export":
                self.export_strategy_file = take()
            elif a == "--import":
                self.import_strategy_file = take()
            elif a == "--export-strategy-computation-graph-file":
                self.export_strategy_computation_graph_file = take()
            elif a == "--export-strategy-task-graph-file":
                self.export_strategy_task_graph_file = take()
            elif a == "--profiling":
                self.profiling = True
            elif a == "--seed":
                self.seed = int(take())
            elif a == "--nodes":
                self.num_nodes = int(take())
            elif a in ("--chips", "-ll:gpu"):
                # `-ll:gpu N` accepted for reference-script compatibility.
                self.num_devices = int(take())
            elif a == "--machine-model-version":
                self.machine_model_version = int(take())
            elif a in ("--machine-model-file", "--machine-spec"):
                # --machine-spec: the hierarchical-machine-friendly alias
                # (docs/machine.md) — one flag loads either format, the
                # factory dispatches on the spec's "tiers" key
                self.machine_model_file = take()
            elif a == "--fitted-profile":
                self.fitted_profile_file = take()
            elif a == "--print-freq":
                self.print_freq = int(take())
            else:
                rest.append(a)
            i += 1
        return rest

    @property
    def workers_per_node(self) -> int:
        return max(1, self.total_devices // max(1, self.num_nodes))

    @property
    def total_devices(self) -> int:
        if self.device_ids is not None:
            return len(self.device_ids)
        if self.num_devices is not None:
            return self.num_devices
        import jax

        return len(jax.devices())

    def get_current_time(self) -> float:
        import time

        return time.time() * 1e6  # microseconds, like Legion's timestamps
