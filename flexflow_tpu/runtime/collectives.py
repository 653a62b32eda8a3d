"""Explicit lowering of the searched per-tier reduction plan.

Until PR 11, `Executor.reduction_plan` was a *record*: the Unity search
synthesized a per-tensor reduction strategy on hierarchical machines
({flat, rs_ar_ag, hier_ring} — docs/machine.md), the FFTA07x gate proved
it legal, and then GSPMD emitted whatever collective schedule XLA liked.
The predicted multipod win was simulated, not executed. This module
closes that gap (ROADMAP item 1, following arXiv:2110.10548 §5 — Unity
*executes* the plans its search synthesizes): each reduction_plan entry
is lowered into real grouped collectives inside the jitted train step,

 - ``rs_ar_ag``  -> ``lax.psum_scatter`` within each inner-tier group
                    (reduce-scatter in the pod), ``lax.psum`` across the
                    outermost-tier groups (all-reduce over DCN on the
                    1/prod(inner) shard), ``lax.all_gather`` back out;
 - ``hier_ring`` -> one full-bytes grouped ``lax.psum`` per tier,
                    inner-first;
 - ``flat``      -> today's single ``lax.psum`` over the whole axis,

selected per synced tensor. The train step's gradient core runs inside a
``shard_map`` manual over the data axis, so per-shard gradients exist to
reduce — GSPMD tensors are logically global and give the lowering
nothing to grab. The supported surface is a pure data-parallel mesh
(exactly the multipod grad-sync case the tier pricing optimizes):
lowering a 'model'/'expert'/'attr' axis would need the gradient core
partial-manual with GSPMD auto elsewhere, and XLA's spmd partitioner
rejects grouped collectives on auto-sharded operands inside a
partial-manual region on every jax this repo supports.

Knob: ``--collective-lowering {gspmd,explicit,auto}`` (FFConfig
.collective_lowering, default gspmd). ``explicit`` raises a typed
CollectiveLoweringError when the plan cannot be lowered (see
`plan_grad_sync_lowering` for the exact conditions); ``auto`` lowers
explicitly only when supported AND the plan actually crosses a tier
boundary, falling back to gspmd otherwise. Numeric parity explicit-vs-
gspmd is pinned by tests/test_collectives.py and the multipod CI twin.

Observability (docs/observability.md): every lowered tensor increments
``ff_collective_lowered_total{strategy,tier}`` and the step build emits
an ``exec.grad_sync`` span carrying the executed schedule — the artifact
the FFTA072 analysis check compares the *planned* schedule against.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

from ..obs.registry import REGISTRY
from ..obs.tracing import get_tracer

COLLECTIVE_LOWERINGS = ("gspmd", "explicit", "auto")


class CollectiveLoweringError(ValueError):
    """--collective-lowering explicit was requested but the compiled plan
    cannot be lowered explicitly (the error names every reason)."""


def lowered_counter():
    """The process-wide lowering counter (one schema, shared with the
    resharding transfer path)."""
    return REGISTRY.counter(
        "ff_collective_lowered_total",
        "Collectives lowered explicitly, by reduction strategy and tier",
        labels=("strategy", "tier"))


def overlap_bucket_counter():
    """Bucketed grad-sync collectives lowered (docs/machine.md
    "Overlap", docs/observability.md ff_grad_sync_overlap_*): one per
    bucket — each bucket is ONE fused per-tier collective over its
    concatenated tensors."""
    return REGISTRY.counter(
        "ff_grad_sync_overlap_buckets_total",
        "Bucketed grad-sync collectives lowered, by reduction strategy",
        labels=("strategy",))


def tier_axis_groups(n: int, group_sizes: List[int]
                     ) -> List[List[List[int]]]:
    """Per-tier ``axis_index_groups`` along one mesh axis of size `n`.

    `group_sizes` is the tier decomposition inner-first (the ``group``
    counts of a reduction_plan entry's ``tiers`` list); their product
    must equal `n`. Axis coordinates map to devices in row-major mesh
    order, so the innermost tier's members are *consecutive* axis
    coordinates — coordinate c decomposes mixed-radix with the innermost
    digit fastest. Level j's groups hold coordinates that differ only in
    digit j: level 0 of (4, 2) over n=8 is [[0..3], [4..7]], level 1 is
    [[0,4], [1,5], [2,6], [3,7]]."""
    if math.prod(group_sizes) != n:
        raise CollectiveLoweringError(
            f"tier group sizes {group_sizes} do not multiply to the axis"
            f" degree {n}")
    out: List[List[List[int]]] = []
    stride = 1
    for nj in group_sizes:
        block = stride * nj
        level = []
        for base in range(0, n, block):
            for r in range(stride):
                level.append([base + r + stride * m for m in range(nj)])
        out.append(level)
        stride = block
    return out


def lower_allreduce(x, axis_name: str, strategy: str,
                    group_sizes: List[int],
                    groups: List[List[List[int]]]):
    """One synced tensor's explicit all-reduce (SUM) over `axis_name`,
    decomposed per `strategy` over the tier groups. Must run inside a
    shard_map manual over `axis_name`. The caller divides by the degree
    for the gradient MEAN."""
    import jax.lax as lax
    import jax.numpy as jnp

    if strategy == "flat" or len(group_sizes) <= 1:
        return lax.psum(x, axis_name)
    if strategy == "hier_ring":
        # a full-bytes ring per tier, inner-first: partial sums within
        # each pod, then the pod-sums ring across the outer tier
        for level in groups:
            x = lax.psum(x, axis_name, axis_index_groups=level)
        return x
    if strategy == "rs_ar_ag":
        shape, size = x.shape, x.size
        flat = x.reshape(-1)
        inner = math.prod(group_sizes[:-1])
        pad = (-size) % inner
        if pad:
            flat = jnp.concatenate(
                [flat, jnp.zeros((pad,), dtype=flat.dtype)])
        # reduce-scatter up the inner tiers: each phase leaves this chip
        # holding a 1/nj shard of its group's partial sum
        for level in groups[:-1]:
            flat = lax.psum_scatter(flat, axis_name, scatter_dimension=0,
                                    axis_index_groups=level, tiled=True)
        # all-reduce the residual shard across the outermost tier — the
        # only phase whose traffic crosses the slow boundary
        flat = lax.psum(flat, axis_name, axis_index_groups=groups[-1])
        # all-gather back down, mirroring the scatter order
        for level in reversed(groups[:-1]):
            flat = lax.all_gather(flat, axis_name, axis=0,
                                  axis_index_groups=level, tiled=True)
        if pad:
            flat = flat[:size]
        return flat.reshape(shape)
    raise CollectiveLoweringError(
        f"unknown reduction strategy {strategy!r}; choices:"
        " flat, rs_ar_ag, hier_ring")


@dataclasses.dataclass
class GradSyncLowering:
    """The executable form of a reduction plan: per synced tensor, the
    strategy and tier group sizes its gradient all-reduce decomposes
    into along the data axis."""

    axis_name: str
    degree: int
    # op name -> {"strategy", "sizes": [inner..outer], "tiers": [names],
    # "bucket": priced bucket id or None (per-tensor), "bytes"}
    entries: Dict[str, Dict[str, Any]]
    mode: str = "explicit"

    def executed_plan(self) -> Dict[str, str]:
        """{op name: strategy} as lowered — what the FFTA072 analysis
        check compares the priced reduction_plan against."""
        return {name: e["strategy"] for name, e in self.entries.items()}

    def executed_buckets(self) -> Dict[str, Optional[int]]:
        """{op name: bucket id (None = per-tensor)} as lowered — the
        executed BUCKET schedule the extended FFTA072 check compares
        against the priced plan's bucket assignment
        (docs/analysis.md)."""
        return {name: e.get("bucket")
                for name, e in self.entries.items()}

    def bucket_map(self) -> Dict[int, List[str]]:
        """{bucket id: [op names]} over the bucketed entries, in entry
        (topo) order — each bucket lowers as ONE fused collective over
        its members' concatenated gradients."""
        out: Dict[int, List[str]] = {}
        for name, e in self.entries.items():
            bid = e.get("bucket")
            if bid is not None:
                out.setdefault(bid, []).append(name)
        return out

    def strategy_counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for e in self.entries.values():
            out[e["strategy"]] = out.get(e["strategy"], 0) + 1
        return out

    # -- lowering ---------------------------------------------------------
    def _groups_for(self, sizes: Tuple[int, ...]):
        cache = getattr(self, "_groups_cache", None)
        if cache is None:
            cache = self._groups_cache = {}
        if sizes not in cache:
            cache[sizes] = tier_axis_groups(self.degree, list(sizes))
        return cache[sizes]

    def sync_tree(self, grads):
        """Reduce a {op: {weight: grad}} tree to the data-group MEAN with
        each op's planned strategy (ops absent from the plan sync flat —
        the conservative legal default).

        Bucketed entries (docs/machine.md "Overlap") lower as ONE fused
        collective per bucket: the members' gradients are flattened and
        concatenated, reduced with the bucket's per-tier strategy, and
        split back. Buckets are independent of each other and each
        depends only on its OWN members' gradients, so the issue order
        is dependency-ordered: XLA's latency-hiding scheduler can fire
        a bucket as soon as its last gradient is produced and overlap
        it with the remaining backward. Tensors of distinct dtypes
        inside one bucket reduce in per-dtype sub-collectives (no
        casts, so numerics match the per-tensor path)."""
        import jax
        import jax.numpy as jnp

        out: Dict[str, Dict[str, Any]] = {}
        bucket_members: Dict[int, List[Tuple[str, str, Any]]] = {}
        for op_name, sub in grads.items():
            e = self.entries.get(op_name)
            if e is not None and e.get("bucket") is not None:
                out[op_name] = {}
                for w_name, g in sub.items():
                    bucket_members.setdefault(e["bucket"], []).append(
                        (op_name, w_name, g))
                continue
            strategy = e["strategy"] if e else "flat"
            sizes = tuple(e["sizes"]) if e else (self.degree,)
            groups = self._groups_for(sizes)
            out[op_name] = jax.tree.map(
                lambda g: lower_allreduce(
                    g, self.axis_name, strategy, list(sizes), groups)
                / self.degree, sub)
        for bid in sorted(bucket_members):
            members = bucket_members[bid]
            # bucket mates share one sync key, hence one strategy and
            # tier decomposition (simulator.plan_sync_buckets)
            e0 = self.entries[members[0][0]]
            strategy, sizes = e0["strategy"], tuple(e0["sizes"])
            groups = self._groups_for(sizes)
            by_dtype: Dict[Any, List[Tuple[str, str, Any]]] = {}
            for m in members:
                by_dtype.setdefault(jnp.asarray(m[2]).dtype, []).append(m)
            for _dt, ms in by_dtype.items():
                flat = jnp.concatenate([g.reshape(-1) for _, _, g in ms])
                red = lower_allreduce(flat, self.axis_name, strategy,
                                      list(sizes), groups) / self.degree
                off = 0
                for op_name, w_name, g in ms:
                    n = int(g.size)
                    out[op_name][w_name] = red[off:off + n].reshape(
                        g.shape)
                    off += n
        return out

    def record(self) -> None:
        """Count every lowered tensor on
        ff_collective_lowered_total{strategy,tier} (plus each bucket on
        ff_grad_sync_overlap_buckets_total{strategy}) and emit the
        exec.grad_sync span carrying the executed schedule, with one
        exec.grad_sync.bucket child span per fused bucket. Once per
        lowering: the train/multi/accumulation step builders share one
        schedule — the counter reflects the schedule, not the number of
        jitted entry points built over it."""
        if getattr(self, "_recorded", False):
            return
        self._recorded = True
        c = lowered_counter()
        buckets = self.bucket_map()
        tracer = get_tracer()
        with tracer.span(
                "exec.grad_sync", mode=self.mode, axis=self.axis_name,
                degree=self.degree, tensors=len(self.entries),
                buckets=len(buckets),
                strategies=self.strategy_counts()):
            for e in self.entries.values():
                for tier in (e["tiers"] or ["mesh"]):
                    c.inc(strategy=e["strategy"], tier=tier)
            bc = overlap_bucket_counter()
            for bid, names in sorted(buckets.items()):
                e0 = self.entries[names[0]]
                bc.inc(strategy=e0["strategy"])
                with tracer.span("exec.grad_sync.bucket", bucket=bid,
                                 tensors=len(names),
                                 strategy=e0["strategy"],
                                 bytes=sum(self.entries[n].get("bytes")
                                           or 0 for n in names)):
                    pass

    def wrap_gstep(self, executor, gstep):
        """Wrap the executor's unjitted gradient core so it computes
        per-shard gradients inside a shard_map manual over the data axis
        and reduces them with the planned per-tier collectives. Keeps
        gstep's exact signature: (params, state, inputs, label, rng) ->
        (grads, metric values, new op state) — grads and metrics come
        back replicated (the explicit collectives produced the global
        mean), so the optimizer update downstream is unchanged."""
        import jax
        from jax.sharding import PartitionSpec as P

        self.record()
        mesh = executor.mesh
        axis, dp = self.axis_name, self.degree
        lowering = self

        def synced_gstep(params, state, inputs, label, rng):
            batch_arrays = [a for a in jax.tree.leaves(inputs)
                            if hasattr(a, "shape") and a.ndim > 0]
            if not batch_arrays or any(a.shape[0] % dp
                                       for a in batch_arrays):
                # a non-dividing (final partial) batch replicates under
                # GSPMD; the explicit path requires equal shards
                return gstep(params, state, inputs, label, rng)

            def body(params, state, inputs, label, rng):
                r = rng
                if r is not None:
                    # decorrelate per-shard randomness (dropout masks):
                    # GSPMD draws one global mask and shards it; each
                    # manual shard must not reuse the same key
                    r = jax.random.fold_in(r, jax.lax.axis_index(axis))
                # sharding constraints are stripped inside the body
                # (LoweringContext.manual_axes, for this trace only):
                # naming the manual axis is illegal there, and naming an
                # auto axis trips an XLA spmd-partitioner check on
                # partial-manual regions. The auto axes don't need the
                # hints — GSPMD propagates from the params' input
                # shardings, which shard_map passes through untouched.
                prev = executor._manual_axes
                executor._manual_axes = frozenset(mesh.axis_names)
                try:
                    grads, mvals, new_state = gstep(params, state, inputs,
                                                    label, r)
                finally:
                    executor._manual_axes = prev
                grads = lowering.sync_tree(grads)
                # per-shard metric means average to the global mean
                # (equal shards — guarded above)
                mvals = jax.tree.map(lambda v: jax.lax.pmean(v, axis),
                                     mvals)
                return grads, mvals, new_state

            in_specs = (P(), P(),
                        jax.tree.map(lambda _: P(axis), inputs),
                        P(axis), P())
            out_specs = (P(), P(), P())
            return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False)(
                params, state, inputs, label, rng)

        return synced_gstep


def plan_grad_sync_lowering(config, graph, mesh, reduction_plan,
                            pipeline_plan=None
                            ) -> Tuple[Optional[GradSyncLowering],
                                       Tuple[str, ...]]:
    """Decide whether (and how) to lower the reduction plan explicitly.

    Returns (lowering, reasons): lowering is None when the GSPMD path
    should run — either because the knob says so, because ``auto`` found
    nothing cross-tier worth decomposing, or because the plan is
    unsupported (reasons name why; the caller raises for mode
    ``explicit``). Supported means: a 'data' mesh axis (degree > 1)
    carries every sync group, no pipeline region and no 'seq'/'stage'
    axis (their kernels already lower through their own shard_map —
    nesting is illegal), and no ops with running state (batch-norm
    statistics need GSPMD's global batch)."""
    mode = getattr(config, "collective_lowering", "gspmd") or "gspmd"
    if mode not in COLLECTIVE_LOWERINGS:
        raise CollectiveLoweringError(
            f"collective_lowering={mode!r}: choices are"
            f" {COLLECTIVE_LOWERINGS}")
    if mode == "gspmd":
        return None, ()
    reasons: List[str] = []
    axis = "data"
    dp = int(mesh.shape[axis]) if (
        mesh is not None and axis in mesh.axis_names) else 1
    if dp <= 1:
        reasons.append("no 'data' mesh axis with degree > 1 to sync over")
    if pipeline_plan is not None:
        reasons.append("the pipeline region already lowers through its"
                       " own shard_map (nesting is illegal)")
    if mesh is not None:
        other = sorted(a for a in mesh.axis_names
                       if a != axis and mesh.shape[a] > 1)
        if other:
            # 'seq'/'stage' kernels already lower through their own
            # shard_map (nesting is illegal); 'model'/'expert'/'attr'
            # would need the gradient core partial-manual over 'data'
            # with GSPMD auto elsewhere, and XLA's spmd partitioner
            # rejects grouped collectives on auto-sharded operands
            # inside a partial-manual region (IsManualSubgroup check) on
            # every jax this repo supports — a pure-dp mesh is the
            # supported surface (exactly the multipod grad-sync case)
            reasons.append(
                "mesh axes beyond 'data' cannot be lowered explicitly"
                " yet: " + ", ".join(other))
    stateful = sorted(op.name for op in graph.ops.values()
                      if op.state_vars)
    if stateful:
        reasons.append(
            "ops with running state need GSPMD's global batch statistics:"
            " " + ", ".join(stateful[:3]))
    plan = dict(reduction_plan or {})
    if not reasons:
        mismatched = sorted(
            name for name, e in plan.items()
            if int(e.get("degree") or dp) != dp)
        if mismatched:
            reasons.append(
                "sync group != the data axis (dp x ap attribute-parallel"
                " sync) for: " + ", ".join(mismatched[:3]))
    if reasons:
        return None, tuple(reasons)
    entries: Dict[str, Dict[str, Any]] = {}
    for op in graph.topo_order():
        if not op.weights:
            continue
        e = plan.get(op.name)
        strategy, sizes, tiers, bucket = "flat", [dp], [], None
        if e:
            tier_list = e.get("tiers") or []
            cand = [int(t["group"]) for t in tier_list]
            if cand and math.prod(cand) == dp:
                strategy = str(e.get("strategy", "flat"))
                sizes = cand
                tiers = [str(t["tier"]) for t in tier_list]
                # the priced bucket schedule rides along (docs/machine.md
                # "Overlap"): bucket mates fuse into one collective in
                # sync_tree; a non-expressible entry drops its bucket
                # with the rest of the decomposition (the documented
                # flat fallback FFTA072 tolerates)
                bucket = e.get("bucket")
            # a decomposition that does not multiply to the axis degree
            # (conservative tier_path round-up) stays flat — legal, just
            # not decomposed
        entries[op.name] = {"strategy": strategy, "sizes": sizes,
                            "tiers": tiers, "bucket": bucket,
                            "bytes": float((e or {}).get("bytes") or 0.0)}
    if mode == "auto" and not any(len(e["sizes"]) > 1
                                  for e in entries.values()):
        return None, ("auto: no cross-tier reduction to decompose — the"
                      " GSPMD schedule is already tier-optimal",)
    if not entries:
        return None, ("no synced weight tensors",)
    lowering = GradSyncLowering(axis_name=axis, degree=dp,
                                entries=entries, mode=mode)
    _verify_lowered_program(config, graph, lowering)
    return lowering, ()


def _verify_lowered_program(config, graph, lowering) -> None:
    """Mandatory sharding-flow gate before the explicit lowering's
    collectives are ever jitted (docs/analysis.md "Verifier"): the
    executed program — tier groups, bucket fusion, per-participant
    sequences — must discharge every pending gradient (FFTA090), carry
    partition-legal axis_index_groups (FFTA091), and be deadlock-free
    under the blocking-collective semantics (FFTA092). Honors the
    plan_analysis knob: "error" raises PlanAnalysisError, "warn" logs,
    "off" skips. Cheap (pure Python over entries x tier levels), so it
    runs on every lowering, not just under the analysis CLI."""
    gate = getattr(config, "plan_analysis", "error") or "error"
    if gate == "off":
        return
    from ..analysis.diagnostics import (DiagnosticReport,
                                        PlanAnalysisError, record_report)
    from ..analysis.interp import verify_grad_sync_program

    report = DiagnosticReport(passes_run=["collective_program"])
    report.extend(verify_grad_sync_program(lowering, graph=graph))
    if not report.diagnostics:
        return
    record_report(report)
    import logging

    log = logging.getLogger("flexflow_tpu.collectives")
    for d in report.diagnostics:
        log.warning("collective program: %s", d.format())
    if gate == "error" and report.errors():
        raise PlanAnalysisError(report)
