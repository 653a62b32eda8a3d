"""Executor: lowers a PCG to jitted SPMD train/inference steps.

This is the TPU-native replacement for the reference's execution stack —
Legion index-task launches per op (e.g. Linear::forward linear.cc:347 →
FFMapper::slice_task mapper.cc:364 → per-GPU kernels) plus Legion iteration
tracing (flexflow_cffi.py:2097-2104). Here the *entire* training iteration
(forward, loss, backward via jax.grad, metrics, optimizer update with
data-parallel gradient reduction) is one traced jax function compiled once by
XLA: tracing+replay is free, fusion replaces FusedOp, and GSPMD inserts the
collectives the reference got from NCCL/Legion copies.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.graph import Graph
from ..core.op import LoweringContext
from ..ffconst import CompMode, OpType
from ..obs.tracing import traced_dispatch
from ..ops.common import emit_dtype
from .metrics import Metrics


class RowCutError(ValueError):
    """A graph `forward_values` cannot run for one wanted row: the tail
    after the last op that keeps a serving cache holds an op (named) that
    does not act on each token position alone, or reads a value whose
    axis 1 is not the token axis."""


# `forward_values(final_row=NO_ROW)`: no row of the final value is wanted
NO_ROW = object()


def _loss_scope(loss_fn) -> str:
    """`loss:<name>`, the device name of the loss's ops."""
    name = getattr(loss_fn, "__name__", "")
    return "loss:" + (name if name.isidentifier() else "custom")


class Executor:
    def __init__(self, graph: Graph, config, mesh=None,
                 reduction_plan=None):
        self.graph = graph
        self.config = config
        self.mesh = mesh
        self.topo = graph.topo_order()
        self._train_step = None
        self._multi_step = None
        self._eval_step = None
        self._forward_jit = None
        self._row_cut_plan = None
        # per-tier reduction decomposition of each synced tensor on a
        # hierarchical machine ({op name: {strategy, tiers, ...}},
        # docs/machine.md) — compile() threads the SAME plan the search
        # priced and the FFTA07x gate proved, so the lowering surface and
        # the cost model can never disagree about how a cross-pod sync
        # decomposes. On the GSPMD path XLA realizes the gradient psum;
        # this records the decomposition it is expected (and priced) to
        # use, and is what a DCN-aware lowering keys its reduce-scatter /
        # donut all-reduce grouping off.
        self.reduction_plan = reduction_plan or {}
        # elastic runtime: wraps jitted TRAIN-step dispatch with fault
        # injection + failure detection + retry (elastic/detector.py).
        # Train steps only — eval/forward dispatches are side-effect-free
        # and re-runnable by their callers, so they stay unguarded.
        self.step_wrapper = getattr(config, "elastic_step_wrapper", None)
        # pipeline parallelism: a 'stage' mesh axis routes the repeated-block
        # region of the PCG through the GPipe kernel (beyond-reference:
        # upstream's OP_PIPELINE ffconst.h:159 is an unused enum)
        self.pipeline_plan = None
        if mesh is not None and "stage" in mesh.axis_names \
                and mesh.shape["stage"] > 1:
            from ..parallel.pipeline_plan import find_pipeline_plan

            self.pipeline_plan = find_pipeline_plan(graph,
                                                    mesh.shape["stage"])
            self.pipeline_microbatches = max(
                1, getattr(config, "pipeline_microbatches", 4))
        # explicit collective lowering (runtime/collectives.py,
        # docs/machine.md "Lowering"): turn the reduction_plan record
        # into real per-tier grouped collectives inside the jitted train
        # step. None = the GSPMD path; the reasons record why (what
        # --collective-lowering explicit raises with, and what auto's
        # fallback logs).
        from .collectives import plan_grad_sync_lowering

        self._manual_axes: frozenset = frozenset()
        self.grad_sync_lowering, self._grad_sync_reasons = \
            plan_grad_sync_lowering(config, graph, mesh,
                                    self.reduction_plan,
                                    pipeline_plan=self.pipeline_plan)

    # -- pipeline helpers --------------------------------------------------
    def _pp_key(self, j: int, r: int, op) -> str:
        return f"seg{j}_op{r}_{op.name}"

    def pipeline_weight_slot(self, op_name: str):
        """Locate a pipelined op's weights inside the stacked tree:
        returns (pp_key, stage_index) — params["__pipeline__"][pp_key][w]
        holds the (S, ...) stack and stage_index selects this op's slice —
        or None when the op is not in the pipelined region (or holds no
        weights). O(1): the map is built once alongside the stacked init."""
        if self.pipeline_plan is None:
            return None
        if not hasattr(self, "_pp_slot_map"):
            plan = self.pipeline_plan
            self._pp_slot_map = {}
            for j in range(plan.segs_per_stage):
                for r, template in enumerate(plan.segments[j]):
                    if not template.weights:
                        continue  # weightless ops have no stacked entry
                    for s in range(plan.n_stages):
                        op_s = plan.segments[s * plan.segs_per_stage + j][r]
                        self._pp_slot_map[op_s.name] = (
                            self._pp_key(j, r, template), s)
        return self._pp_slot_map.get(op_name)

    def _init_pipeline_params(self, key, params: Dict) -> Any:
        """Stacked region parameters: leaf shape (S, *dims), sharded over
        the 'stage' axis — each device holds exactly its stage's slice."""
        import jax

        from jax.sharding import NamedSharding, PartitionSpec

        plan = self.pipeline_plan
        stacked: Dict[str, Dict[str, Any]] = {}
        for j in range(plan.segs_per_stage):
            for r, template in enumerate(plan.segments[j]):
                if not template.weights:
                    continue
                entry: Dict[str, Any] = {}
                for wi, w in enumerate(template.weights):
                    ws = w._weight_spec
                    slices = []
                    for s in range(plan.n_stages):
                        op_s = plan.segments[s * plan.segs_per_stage + j][r]
                        w_s = op_s.weights[wi]
                        key, sub = jax.random.split(key)
                        if w_s._host_value is not None:
                            slices.append(jnp.asarray(w_s._host_value))
                        else:
                            ws_s = w_s._weight_spec
                            slices.append(ws_s.initializer(
                                sub, ws_s.dims, ws_s.dtype.jnp_dtype))
                    val = jnp.stack(slices)
                    spec = PartitionSpec("stage",
                                         *([None] * (val.ndim - 1)))
                    entry[ws.name] = jax.device_put(
                        val, NamedSharding(self.mesh, spec))
                stacked[self._pp_key(j, r, template)] = entry
        params["__pipeline__"] = stacked
        return key

    def _run_pipeline(self, pp_params, x, ctx, rng):
        """Evaluate the pipelined region: GPipe over the 'stage' axis, one
        stage = segs_per_stage isomorphic segments walked with the stage-0
        template ops and this stage's weight slices."""
        from ..kernels.pipeline import gpipe_apply_mesh
        from ..core.op import LoweringContext

        plan = self.pipeline_plan
        config, mode = self.config, ctx.mode
        seq_len = ctx.iter_seq_length

        def stage_fn(p_slice, x_in, *stage_rng):
            sub = LoweringContext(config, mode, None,
                                  stage_rng[0] if stage_rng else None,
                                  iter_seq_length=seq_len)
            sub.in_shard_map = True
            values = {plan.entries[0].guid: x_in}
            for j in range(plan.segs_per_stage):
                for r, op in enumerate(plan.segments[j]):
                    ins = [values[t.guid] for t in op.inputs]
                    weights = dict(p_slice.get(self._pp_key(j, r, op), {}))
                    with jax.named_scope(f"pp:{op.op_type.value}:{op.name}"):
                        outs = op.lower(sub, ins, weights)
                    for t, v in zip(op.outputs, outs):
                        if hasattr(v, "astype"):
                            v = v.astype(emit_dtype(config, t.dtype))
                        values[t.guid] = v
                # the next template segment reads its entry tensor, which
                # segment j's bottleneck just produced into `values`
            return values[plan.segments[plan.segs_per_stage - 1][-1]
                          .outputs[0].guid]

        data_axis = ("data" if "data" in self.mesh.axis_names
                     and self.mesh.shape["data"] > 1 else None)
        return gpipe_apply_mesh(
            stage_fn, pp_params, x, self.mesh,
            axis_name="stage",
            microbatches=self.pipeline_microbatches,
            data_axis=data_axis,
            rng=rng,
        )

    # -- parameter/state initialization (reference: init_operators + initializer tasks)
    def init_params(self, key) -> Tuple[Dict, Dict]:
        params: Dict[str, Dict[str, Any]] = {}
        state: Dict[str, Dict[str, Any]] = {}
        region = (self.pipeline_plan.region_guids
                  if self.pipeline_plan else ())
        for op in self.topo:
            if op.guid in region:
                continue  # stacked under "__pipeline__" below
            if op.weights:
                params[op.name] = {}
                for w in op.weights:
                    key, sub = jax.random.split(key)
                    ws = w._weight_spec
                    init = ws.initializer
                    if w._host_value is not None:
                        val = jnp.asarray(w._host_value)
                    else:
                        val = init(sub, ws.dims, ws.dtype.jnp_dtype)
                    # place with the strategy's weight sharding (TP) so the
                    # jitted step starts from sharded parameters
                    if self.mesh is not None and w.parallel_shape is not None:
                        val = jax.device_put(
                            val, w.parallel_shape.sharding(self.mesh)
                        )
                    params[op.name][ws.name] = val
            if op.state_vars:
                state[op.name] = {}
                for sv in op.state_vars:
                    key, sub = jax.random.split(key)
                    state[op.name][sv.name] = sv.initializer(
                        sub, sv.dims, sv.dtype.jnp_dtype
                    )
        if self.pipeline_plan is not None:
            key = self._init_pipeline_params(key, params)
        return params, state

    # -- forward walk ------------------------------------------------------
    def row_cut(self) -> Tuple[int, frozenset]:
        """Where a forward that wants ONE row of the final value (or none)
        stops running every row: (index in `self.topo` of the last op that
        keeps a serving cache or per-sequence state, guids of the tensors
        that descend from a graph input and so carry the token axis).
        Every prompt token has to go through the caching ops, which read
        across positions; what follows them is fed one token a sequence by
        every decode step already, which is sound only if it acts on each
        position alone. Here that is checked (`Op.acts_per_position`), and
        a graph whose tail does not is refused, by the op's name."""
        if self._row_cut_plan is not None:
            return self._row_cut_plan
        caching = [i for i, op in enumerate(self.topo)
                   if op.kv_cache_arrays() or op.sequence_state_arrays()]
        if not caching or self.pipeline_plan is not None:
            raise RowCutError(
                "one row of the final value can be asked of a graph with an"
                " op that keeps a serving cache, outside a pipeline")
        fed = set()
        for op in self.topo:
            if op.op_type == OpType.INPUT or any(
                    t.guid in fed for t in op.inputs):
                fed.update(t.guid for t in op.outputs)
        for op in self.topo[caching[-1] + 1:]:
            if op.op_type != OpType.INPUT and not op.acts_per_position() \
                    and any(t.guid in fed for t in op.inputs):
                raise RowCutError(
                    f"{op!r} follows the last op that keeps a serving cache"
                    f" ({self.topo[caching[-1]].name}) and does not act on"
                    " each token position alone: its output for one"
                    " position cannot be computed from that position")
        self._row_cut_plan = (caching[-1], frozenset(fed))
        return self._row_cut_plan

    def forward_values(
        self,
        params: Dict,
        state: Dict,
        input_values: Dict[str, Any],
        rng,
        mode: CompMode,
        seq_length: Optional[int] = None,
        decode_pos=None,
        fill_kv_cache: bool = False,
        valid_len=None,
        final_row=None,
    ) -> Tuple[Dict[int, Any], Dict, Any]:
        """Returns (tensor guid -> value, new state, aux loss sum).
        seq_length: iteration
        truncation (FFIterationConfig) — static per distinct length.
        decode_pos / fill_kv_cache: KV-cache serving paths (a traced scalar
        position for incremental decoding / prefill cache capture).
        valid_len: a traced count of the leading REAL tokens of a padded
        prefill dispatch (None = all): an attention hides what follows
        behind its position mask and ignores it, an op that keeps state
        per sequence (Op.sequence_state_arrays) must not let the padding
        into the state.
        final_row: which positions of the token axis (axis 1) the caller
        reads of the values AFTER the last op that keeps a serving cache
        (`row_cut`). None = all of them, and the walk is what it always
        was. A traced index = that one: every value the later ops read is
        cut to it, they run on (rows, 1, ...), and so the final value is
        (rows, 1, ...). `NO_ROW` = none: the walk ends at the cut and the
        later tensors have no value."""
        ctx = LoweringContext(self.config, mode, self.mesh, rng,
                              iter_seq_length=seq_length)
        ctx.decode_pos = decode_pos
        ctx.fill_kv_cache = fill_kv_cache
        ctx.valid_len = valid_len
        if self._manual_axes:
            # tracing inside the explicit grad-sync shard_map body: the
            # manual axes' constraints must not reach XLA (core/op.py)
            ctx.manual_axes = self._manual_axes
            ctx.in_shard_map = True
        # flatten state into ctx keyed by (op_name, var)
        for op_name, vars_ in state.items():
            for var, val in vars_.items():
                ctx.state[(op_name, var)] = val
        plan = self.pipeline_plan
        cut, fed = (len(self.topo), ()) if final_row is None \
            else self.row_cut()
        rows: Dict[int, Any] = {}   # guid -> the value at `final_row`

        def one_row(op, t):
            """What `op`, past the cut, reads of tensor `t`."""
            if t.guid not in fed:
                return ctx.values[t.guid]   # a constant: no token axis
            if t.guid not in rows:
                v = ctx.values[t.guid]
                # the token axis is axis 1 of what the last caching op
                # hands on
                handed = ctx.values[self.topo[cut].outputs[0].guid]
                if v.ndim < 2 or v.shape[1] != handed.shape[1]:
                    raise RowCutError(
                        f"{op!r} reads a value of shape {v.shape} from"
                        " before the last op that keeps a serving cache:"
                        f" axis 1 is not the {handed.shape[1]} tokens")
                with jax.named_scope("rows:pick"):
                    rows[t.guid] = jax.lax.dynamic_slice_in_dim(
                        v, final_row, 1, axis=1)
            return rows[t.guid]

        for i, op in enumerate(self.topo):
            if i > cut and final_row is NO_ROW:
                break
            if plan is not None and op.guid in plan.region_guids:
                if op.guid == plan.first_op_guid:
                    x = ctx.values[plan.region_input.guid]
                    out = self._run_pipeline(
                        params.get("__pipeline__", {}), x, ctx, rng)
                    out = out.astype(
                        emit_dtype(self.config, plan.region_output.dtype))
                    ctx.values[plan.region_output.guid] = ctx.constrain(
                        out, plan.region_output)
                continue
            if op.op_type == OpType.INPUT:
                val = input_values[op.name]
                ctx.values[op.outputs[0].guid] = ctx.constrain(val, op.outputs[0])
                continue
            ins = [one_row(op, t) if i > cut else ctx.values[t.guid]
                   for t in op.inputs]
            weights = dict(params.get(op.name, {}))
            for w in op.weights:
                ws = w._weight_spec
                if ws.name in weights:
                    weights[ws.name] = ctx.constrain(weights[ws.name], w)
            # named scope tags every HLO op with its PCG op, so device
            # profiles (jax.profiler / xprof) group by framework op — the
            # role of the reference's per-task profiling printfs
            with jax.named_scope(f"{op.op_type.value}:{op.name}"):
                outs = op.lower(ctx, ins, weights)
            for t, v in zip(op.outputs, outs):
                # boundary storage dtype: under mixed precision f32
                # activations are stored bf16 (XLA fuses the convert into
                # the producing op, so no extra pass) — see ops/common.py
                if hasattr(v, "astype"):
                    v = v.astype(emit_dtype(self.config, t.dtype))
                ctx.values[t.guid] = ctx.constrain(v, t)
                if i > cut and t.guid in fed:
                    if v.ndim < 2 or v.shape[1] != 1:
                        raise RowCutError(
                            f"{op!r}, given one token position, returned"
                            f" shape {v.shape}")
                    rows[t.guid] = ctx.values[t.guid]
        new_state = {
            op_name: {
                var: ctx.state_updates.get((op_name, var), val)
                for var, val in vars_.items()
            }
            for op_name, vars_ in state.items()
        }
        aux_loss = sum(ctx.aux_losses) if ctx.aux_losses else 0.0
        return ctx.values, new_state, aux_loss

    # -- step builders -----------------------------------------------------
    def build_grad_metrics_step(self, loss_fn, metrics: Metrics,
                                final_tensor, reg_fn=None):
        """UNJITTED core shared by the fused train step and gradient
        accumulation: (params, state, inputs, label, rng) ->
        (grads, metric values incl. loss, new op state)."""

        def gstep(params, state, inputs, label, rng):
            def loss_and_aux(p):
                values, new_state, aux = self.forward_values(
                    p, state, inputs, rng, CompMode.COMP_MODE_TRAINING
                )
                pred = values[final_tensor.guid]
                # what runs outside the graph gets a device name too
                # (forward_values scopes every graph op)
                with jax.named_scope(_loss_scope(loss_fn)):
                    loss = loss_fn(pred, label) + aux
                if reg_fn is not None:
                    loss = loss + reg_fn(p)
                with jax.named_scope("metrics:compute"):
                    mvals = metrics.compute(pred, label) if metrics else {}
                return loss, (mvals, new_state)

            (loss, (mvals, new_state)), grads = jax.value_and_grad(
                loss_and_aux, has_aux=True
            )(params)
            mvals = dict(mvals)
            mvals["loss"] = loss
            return grads, mvals, new_state

        if self.grad_sync_lowering is not None:
            # explicit collective lowering: per-shard grads inside a
            # data-manual shard_map, reduced with the planned per-tier
            # collectives (runtime/collectives.py)
            return self.grad_sync_lowering.wrap_gstep(self, gstep)
        if getattr(self.config, "collective_lowering", "gspmd") \
                == "explicit":
            from .collectives import CollectiveLoweringError

            raise CollectiveLoweringError(
                "--collective-lowering explicit cannot lower this plan: "
                + "; ".join(self._grad_sync_reasons))
        return gstep

    def build_train_step(self, optimizer, loss_fn, metrics: Metrics,
                         final_tensor, input_names: List[str], reg_fn=None):
        gstep = self.build_grad_metrics_step(loss_fn, metrics, final_tensor,
                                             reg_fn)

        def train_step(params, opt_state, state, inputs, label, rng):
            grads, mvals, new_state = gstep(params, state, inputs, label, rng)
            with jax.named_scope("optimizer:update"):
                new_params, new_opt_state = optimizer.update(
                    params, grads, opt_state)
            return new_params, new_opt_state, new_state, mvals

        # elastic retry re-dispatches the SAME arguments after a transient
        # error that surfaced mid-execution; donation would have deleted
        # them, turning every real-error retry into 'Array has been
        # deleted'. Keeping the buffers is the price of retryability.
        donate = () if self.step_wrapper is not None else (0, 1, 2)
        fn = jax.jit(train_step, donate_argnums=donate)
        if self.step_wrapper is not None:
            fn = self.step_wrapper(fn)
        # span per host-side dispatch (outermost, so retries under the
        # elastic wrapper are inside the span); a no-op while tracing is
        # disabled
        self._train_step = traced_dispatch(fn, "executor.train_step")
        return self._train_step

    def build_multi_step(self, optimizer, loss_fn, metrics: Metrics,
                         final_tensor, input_names: List[str], reg_fn=None):
        """K train steps in ONE dispatch via lax.scan — the
        steps_per_execution role of tf.keras (and the reference's
        iterations-per-launch batching of task graphs): the host pays one
        dispatch per K steps instead of one per step. What that buys is
        not measured on the current set-up (ROADMAP S4).

        The returned fn takes (params, opt_state, state, inputs_k, label_k,
        rng_k) where inputs_k/label_k carry a leading K axis and rng_k is
        jax.random.split(key, K); it returns stacked (K,) metric values."""
        gstep = self.build_grad_metrics_step(loss_fn, metrics, final_tensor,
                                             reg_fn)

        def one(carry, xs):
            params, opt_state, state = carry
            inputs, label, rng = xs
            grads, mvals, new_state = gstep(params, state, inputs, label, rng)
            with jax.named_scope("optimizer:update"):
                new_params, new_opt_state = optimizer.update(
                    params, grads, opt_state)
            return (new_params, new_opt_state, new_state), mvals

        def multi_step(params, opt_state, state, inputs_k, label_k, rng_k):
            (params, opt_state, state), mvals = jax.lax.scan(
                one, (params, opt_state, state), (inputs_k, label_k, rng_k))
            return params, opt_state, state, mvals

        # no donation under the elastic wrapper: retry needs the original
        # buffers alive (see build_train_step)
        donate = () if self.step_wrapper is not None else (0, 1, 2)
        fn = jax.jit(multi_step, donate_argnums=donate)
        if self.step_wrapper is not None:
            fn = self.step_wrapper(fn)
        self._multi_step = traced_dispatch(fn, "executor.multi_step")
        return self._multi_step

    def build_eval_step(self, loss_fn, metrics: Metrics, final_tensor):
        def eval_step(params, state, inputs, label):
            values, _, _ = self.forward_values(
                params, state, inputs, None, CompMode.COMP_MODE_INFERENCE
            )
            pred = values[final_tensor.guid]
            with jax.named_scope("metrics:compute"):
                mvals = metrics.compute(pred, label) if metrics else {}
            with jax.named_scope(_loss_scope(loss_fn)):
                mvals["loss"] = loss_fn(pred, label)
            return mvals, pred

        self._eval_step = traced_dispatch(jax.jit(eval_step),
                                          "executor.eval_step")
        return self._eval_step

    def build_forward(self, final_tensor, mode: CompMode = CompMode.COMP_MODE_INFERENCE,
                      seq_length: Optional[int] = None):
        """mode matters for the manual loop: the reference's forward() during
        training is a training-mode pass (dropout active, BN batch stats), so
        FFModel passes its comp_mode here. seq_length: iteration truncation
        — each distinct length jits its own (cached) executable."""

        def fwd(params, state, inputs, rng):
            values, new_state, _ = self.forward_values(
                params, state, inputs, rng, mode, seq_length=seq_length
            )
            return values[final_tensor.guid], new_state

        self._forward_jit = traced_dispatch(jax.jit(fwd),
                                            "executor.forward")
        return self._forward_jit

    def build_grad_step(self, loss_fn, final_tensor,
                        seq_length: Optional[int] = None):
        """Separate backward pass for the manual forward/backward/update API
        (reference: FFModel::backward model.cc:2438)."""

        def grad_step(params, state, inputs, label, rng):
            def loss_of(p):
                values, _, aux = self.forward_values(
                    p, state, inputs, rng, CompMode.COMP_MODE_TRAINING,
                    seq_length=seq_length
                )
                with jax.named_scope(_loss_scope(loss_fn)):
                    return loss_fn(values[final_tensor.guid], label) + aux

            return jax.grad(loss_of)(params)

        return jax.jit(grad_step)

    def shard_batch(self, arr, batch_axis: int = 0):
        """Place a host batch on the mesh, sharded over the data axis.

        Multi-host (jax.process_count() > 1): every process passes the SAME
        global batch; each host materializes only its addressable shards
        (device_put cannot address remote devices, so the array is assembled
        per-device via make_array_from_callback — the launch contract in
        MULTI-NODE.md)."""
        if self.mesh is None or "data" not in self.mesh.axis_names:
            return jnp.asarray(arr)
        from jax.sharding import NamedSharding, PartitionSpec

        spec = [None] * arr.ndim
        # replicate when the batch doesn't divide the data axis (e.g. a
        # short final eval batch) instead of failing the device_put
        if arr.shape[batch_axis] % self.mesh.shape["data"] == 0:
            spec[batch_axis] = "data"
        sharding = NamedSharding(self.mesh, PartitionSpec(*spec))
        if jax.process_count() > 1:
            arr = np.asarray(arr)
            return jax.make_array_from_callback(
                arr.shape, sharding, lambda idx: arr[idx])
        return jax.device_put(arr, sharding)
