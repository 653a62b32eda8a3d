"""Mixture-of-Experts ops: GroupBy, Aggregate, AggregateSpec, Cache.

Reference: src/ops/group_by.cc (scatter tokens to experts), aggregate.cc
(gather expert outputs + load-balance gradient shaping), aggregate_spec.cc,
cache.cc (cached expert assignments with a score callback).

The reference's group_by produces data-dependent shapes; on TPU/XLA shapes
must be static, so we use the standard capacity-factor formulation: each
expert receives a fixed-capacity buffer (capacity = ceil(alpha * k * B / n)),
overflow tokens are dropped, position-in-expert computed with a cumsum over
the token order (deterministic, recomputable by Aggregate). This is also the
formulation expert-parallel all_to_all dispatch wants.
"""
from __future__ import annotations

import functools
import math
import jax
import jax.numpy as jnp

from ..core.op import Op, register_op
from ..ffconst import DataType, OpType


def moe_capacity(batch: int, k: int, n: int, alpha: float) -> int:
    """Per-expert token capacity: ceil(alpha * k * batch / n), clamped to
    >= k. The clamp floor is k (not 1): a tiny batch x small alpha can
    round the raw value below k, and a capacity under k cannot even hold
    one token's k assignments when the router concentrates — every token
    routed to a popular expert would be dropped SILENTLY. The degenerate
    configuration is surfaced by the FFTA080 analysis warning
    (analysis/passes.py pass_moe) instead of by zeroed outputs."""
    return max(int(k), int(math.ceil(alpha * k * batch / n)))


def moe_capacity_degenerate(batch: int, k: int, n: int,
                            alpha: float) -> bool:
    """True when the UNCLAMPED capacity rounds below k — the configuration
    the FFTA080 warning names (the clamp in moe_capacity is silently
    raising the effective capacity factor above the requested alpha)."""
    return int(math.ceil(alpha * k * batch / n)) < int(k)


def moe_tokens(dims) -> int:
    """Token count of an ExpertsOp input: rank-2 inputs are (tokens, F);
    rank-3 (batch, seq, F) inputs dispatch per token over the flattened
    leading dims (the serving decode path runs the same graph at seq=1)."""
    t = 1
    for d in dims[:-1]:
        t *= int(d)
    return t


def _dispatch_plan(assign, n: int, capacity: int):
    """assign: (B, k) int32 expert ids. Returns (expert_of_token, slot_of_token,
    valid) each of shape (B*k,), flattened in row-major token order."""
    flat = assign.reshape(-1)  # (B*k,)
    onehot = jax.nn.one_hot(flat, n, dtype=jnp.int32)  # (T, n)
    # position of each token within its expert (0-based), in token order
    pos = jnp.cumsum(onehot, axis=0) - onehot  # (T, n)
    slot = jnp.sum(pos * onehot, axis=1)  # (T,)
    valid = slot < capacity
    return flat, slot, valid


def _load_balance_loss(full_gate, assign, n: int, lambda_bal: float):
    """Switch-Transformer-style load-balance loss (functional stand-in for
    the reference's lambda_bal gradient shaping in aggregate.cu's backward
    kernel): lambda_bal * n * sum_e(importance_e * load_e)."""
    full = full_gate.astype(jnp.float32)  # (B, n) gate distribution
    importance = jnp.mean(full, axis=0)
    load = jnp.mean(
        jax.nn.one_hot(assign.reshape(-1), n, dtype=jnp.float32), axis=0
    )
    return lambda_bal * n * jnp.sum(importance * load)


def _dispatch_masks(assign, n: int, capacity: int, dtype):
    """One-hot dispatch factors (GShard-style): sel (T, n) expert selector
    masked by capacity validity, slot_oh (T, cap) slot selector. The full
    (T, n, cap) dispatch mask is their outer product; keeping the factors
    separate lets the dispatch/combine einsums contract without ever
    materializing it (XLA picks the pairing)."""
    expert, slot, valid = _dispatch_plan(assign, n, capacity)
    sel = jax.nn.one_hot(expert, n, dtype=dtype) * valid[:, None].astype(dtype)
    slot_oh = jax.nn.one_hot(
        jnp.minimum(slot, capacity - 1), capacity, dtype=dtype
    )
    return sel, slot_oh


@register_op
class GroupByOp(Op):
    """inputs: (features (B, F), assign (B, k)); outputs: n buffers (cap, F)."""

    op_type = OpType.GROUP_BY

    def output_shapes(self):
        x, assign = self.inputs
        n = self.params["n"]
        alpha = self.params.get("alpha", 1.0)
        cap = moe_capacity(x.dims[0], assign.dims[1], n, alpha)
        return [(cap, x.dims[1])] * n, [x.dtype] * n

    def lower(self, ctx, inputs, weights):
        x, assign = inputs
        n = self.params["n"]
        alpha = self.params.get("alpha", 1.0)
        b, f = x.shape
        k = assign.shape[1]
        cap = moe_capacity(b, k, n, alpha)
        # one-hot-einsum dispatch: one (n*cap, T) x (T, F) MXU contraction
        # instead of n scatter passes over all B*k tokens
        dt = x.dtype if jnp.issubdtype(x.dtype, jnp.floating) else jnp.float32
        sel, slot_oh = _dispatch_masks(assign.astype(jnp.int32), n, cap, dt)
        bufs = jnp.einsum("bkn,bkc,bf->ncf", sel.reshape(b, k, n),
                          slot_oh.reshape(b, k, cap), x.astype(dt))
        return [bufs[e].astype(x.dtype) for e in range(n)]


@register_op
class AggregateOp(Op):
    """inputs: gate_preds (B,k), gate_assign (B,k), true_gate_assign (B,k),
    full_gate_grads (B,n), exp_preds[n] (cap, out_dim) -> output (B, out_dim).

    Mirrors the reference Aggregate input signature (aggregate.cc); the
    load-balance gradient shaping (lambda_bal) arrives via jax.grad of the
    combined weighting, so no custom backward kernel is needed.
    """

    op_type = OpType.AGGREGATE

    def output_shapes(self):
        n = self.params["n"]
        exp0 = self.inputs[4]
        b = self.inputs[0].dims[0]
        return [(b, exp0.dims[1])], [exp0.dtype]

    def lower(self, ctx, inputs, weights):
        gate_preds, gate_assign = inputs[0], inputs[1]
        n = self.params["n"]
        exp_preds = inputs[4 : 4 + n]
        b, k = gate_assign.shape
        cap = exp_preds[0].shape[0]
        lambda_bal = self.params.get("lambda_bal", 0.0)
        if lambda_bal:
            ctx.aux_losses.append(
                _load_balance_loss(inputs[3], gate_assign, n, lambda_bal)
            )
        stacked = jnp.stack(exp_preds)  # (n, cap, out_dim)
        dt = stacked.dtype if jnp.issubdtype(stacked.dtype, jnp.floating) else jnp.float32
        sel, slot_oh = _dispatch_masks(gate_assign.astype(jnp.int32), n, cap, dt)
        # combine: one (T, n*cap) x (n*cap, out_dim) contraction gathers each
        # token-assignment's expert output (invalid rows -> zeros via sel)
        tok_out = jnp.einsum("tn,tc,nch->th", sel, slot_oh, stacked.astype(dt))
        tok_out = tok_out.reshape(b, k, -1)
        return [jnp.sum(tok_out * gate_preds[..., None].astype(tok_out.dtype), axis=1)]


@register_op
class ExpertsOp(Op):
    """Fused MoE expert block: dispatch -> batched per-expert FFN -> combine,
    with device-level expert parallelism.

    inputs: x (B, F), gate_preds (B, k) top-k gate weights, assign (B, k)
    expert ids, and optionally full_gate (B, n) for the load-balance loss.
    weights: kernel (n, F, H) and bias (n, H), stacked with a leading expert
    dim that shards over the 'expert' mesh axis.

    This is the TPU-native form of the reference's device-placed experts
    (src/ops/group_by.cc + aggregate.cc scatter/gather between expert ops the
    search puts on different devices, examples/cpp/mixture_of_experts/moe.cc):
    the experts live as one batched einsum whose expert dim is sharded, and
    GSPMD lowers the dispatch/combine contractions between the data-sharded
    token dim and the expert-sharded buffers to all_to_all-style collectives
    over ICI.
    """

    op_type = OpType.EXPERTS

    def _shape(self):
        x, gate_preds, assign = self.inputs[:3]
        n = self.params["n"]
        alpha = self.params.get("alpha", 1.0)
        cap = moe_capacity(moe_tokens(x.dims), assign.dims[-1], n, alpha)
        return x, n, cap, self.params["out_dim"]

    def output_shapes(self):
        x, n, cap, out_dim = self._shape()
        return [tuple(x.dims[:-1]) + (out_dim,)], [x.dtype]

    def acts_per_position(self):
        # while no assignment overflows an expert's capacity: what
        # overflows is dropped in arrival order, across positions. A decode
        # step (one token a sequence) serves this op under that condition
        # already; one position alone (capacity >= k) never overflows
        return self._off_token_axis([-1])

    def weight_specs(self):
        from ..core.op import WeightSpec
        from ..runtime.initializers import DefaultInitializer, ZeroInitializer

        x, n, cap, out_dim = self._shape()
        f = x.dims[-1]
        init = self.params.get("kernel_initializer") or DefaultInitializer(
            fan_in=f, fan_out=out_dim
        )
        return [
            WeightSpec("kernel", (n, f, out_dim), x.dtype, init),
            WeightSpec("bias", (n, out_dim), x.dtype, ZeroInitializer()),
        ]

    def state_specs(self):
        from ..core.op import WeightSpec
        from ..runtime.initializers import ZeroInitializer

        n = self.params["n"]
        # router health state, read by obs.moe.publish_moe_metrics:
        # `dropped` accumulates capacity-overflow token-assignments (the
        # ff_moe_router_dropped_tokens_total source), `load` holds the last
        # step's per-expert assignment fractions (the load-balance gauge)
        return [
            WeightSpec("dropped", (), DataType.DT_FLOAT, ZeroInitializer()),
            WeightSpec("load", (n,), DataType.DT_FLOAT, ZeroInitializer()),
        ]

    def _constrain_expert(self, ctx, val):
        """Pin the expert dim to the 'expert' mesh axis so the batched FFN
        runs expert-parallel and XLA routes tokens with all_to_all."""
        mesh = getattr(ctx, "mesh", None)
        if mesh is not None and "expert" in getattr(mesh, "axis_names", ()):
            from jax.sharding import NamedSharding, PartitionSpec

            spec = PartitionSpec("expert", *([None] * (val.ndim - 1)))
            return jax.lax.with_sharding_constraint(
                val, NamedSharding(mesh, spec)
            )
        return val

    def lower(self, ctx, inputs, weights):
        from .common import apply_activation, matmul_dtype
        from ..ffconst import ActiMode

        x, gate_preds, assign = inputs[:3]
        n = self.params["n"]
        alpha = self.params.get("alpha", 1.0)
        lambda_bal = self.params.get("lambda_bal", 0.0)
        lead = x.shape[:-1]  # (tokens,) or (batch, seq) — restored at exit
        if x.ndim > 2:
            # token-flattened dispatch: the capacity formulation is
            # per-token, and flattening HERE (not in the builder) keeps the
            # graph shape-polymorphic over the leading dims — the serving
            # decode path re-runs this op at seq=1 against the same lowering
            x = x.reshape((-1, x.shape[-1]))
            gate_preds = gate_preds.reshape((-1, gate_preds.shape[-1]))
            assign = assign.reshape((-1, assign.shape[-1]))
        b, f = x.shape
        k = assign.shape[1]
        cap = moe_capacity(b, k, n, alpha)
        cdt = matmul_dtype(getattr(ctx, "config", None), jnp.float32)

        if lambda_bal:
            if len(inputs) <= 3:
                raise ValueError(
                    f"experts op {self.name}: lambda_bal={lambda_bal} needs "
                    "the full gate distribution (pass full_gate=)"
                )
            full_gate = inputs[3]
            if full_gate.ndim > 2:
                full_gate = full_gate.reshape((-1, full_gate.shape[-1]))
            ctx.aux_losses.append(
                _load_balance_loss(full_gate, assign, n, lambda_bal)
            )

        sel, slot_oh = _dispatch_masks(assign.astype(jnp.int32), n, cap, cdt)
        # router health state (obs/moe.py publishes these as the
        # ff_moe_router_dropped_tokens_total / ff_moe_expert_load families);
        # stop_gradient: bookkeeping must not leak into the backward pass
        assign_i = assign.astype(jnp.int32)
        _, _, valid = _dispatch_plan(assign_i, n, cap)
        prev = ctx.state.get((self.name, "dropped"))
        if prev is not None:
            dropped = jnp.sum(1.0 - valid.astype(jnp.float32))
            ctx.state_updates[(self.name, "dropped")] = (
                prev + jax.lax.stop_gradient(dropped))
            load = jnp.mean(
                jax.nn.one_hot(assign_i.reshape(-1), n, dtype=jnp.float32),
                axis=0)
            ctx.state_updates[(self.name, "load")] = (
                jax.lax.stop_gradient(load))
        # (b, k, ...) mask views contract directly against x — no k-fold
        # jnp.repeat copy of the token features
        disp = jnp.einsum("bkn,bkc,bf->ncf", sel.reshape(b, k, n),
                          slot_oh.reshape(b, k, cap), x.astype(cdt))
        disp = self._constrain_expert(ctx, disp)
        kernel = weights["kernel"].astype(cdt)
        h = jnp.einsum("ncf,nfh->nch", disp, kernel,
                       preferred_element_type=jnp.float32)
        h = h + weights["bias"].astype(jnp.float32)[:, None, :]
        h = apply_activation(
            h, self.params.get("activation", ActiMode.AC_MODE_RELU)
        ).astype(cdt)
        h = self._constrain_expert(ctx, h)
        # combine, gate-weighted, summing the k assignments per sample
        gate_flat = gate_preds.reshape(-1).astype(cdt)  # (T,)
        sel_g = (sel * gate_flat[:, None]).reshape(b, k, n)
        slot_bk = slot_oh.reshape(b, k, cap)
        out = jnp.einsum("bkn,bkc,nch->bh", sel_g, slot_bk, h)
        if len(lead) > 1:
            out = out.reshape(lead + (out.shape[-1],))
        return [out.astype(self.outputs[0].dtype.jnp_dtype)]

    def flops(self) -> float:
        x, n, cap, out_dim = self._shape()
        t = moe_tokens(x.dims) * self.inputs[2].dims[-1]
        f = x.dims[-1]
        dispatch = 2.0 * t * n * cap * f
        ffn = 2.0 * n * cap * f * out_dim
        combine = 2.0 * t * n * cap * out_dim
        return dispatch + ffn + combine


@register_op
class AggregateSpecOp(AggregateOp):
    """Variant used with speculative expert predictions (aggregate_spec.cc);
    same dataflow, kept as a distinct type for graph-substitution parity."""

    op_type = OpType.AGGREGATE_SPEC


@register_op
class CacheOp(Op):
    """Cached tensor with staleness score (reference: src/ops/cache.cc).

    Holds the last seen input in non-trainable state; `score_f` (host
    callback in the reference) becomes an on-device L1 divergence score the
    recompile trigger can read via model.get_cache_score().
    """

    op_type = OpType.CACHE

    def output_shapes(self):
        return [self.inputs[0].dims], [self.inputs[0].dtype]

    def state_specs(self):
        from ..core.op import WeightSpec
        from ..runtime.initializers import ZeroInitializer

        return [
            WeightSpec("cached", self.inputs[0].dims, self.inputs[0].dtype, ZeroInitializer()),
            WeightSpec("score", (), DataType.DT_FLOAT, ZeroInitializer()),
        ]

    def lower(self, ctx, inputs, weights):
        x = inputs[0]
        cached = ctx.state.get((self.name, "cached"))
        use_cached = self.params.get("use_cached", False)
        if cached is None:
            return [x]
        score = jnp.mean(jnp.abs(x.astype(jnp.float32) - cached.astype(jnp.float32)))
        ctx.state_updates[(self.name, "score")] = score
        ctx.state_updates[(self.name, "cached")] = x
        return [cached if use_cached else x]


# ---------------------------------------------------------------------
# Dropless gated experts, told which experts they hold
# ---------------------------------------------------------------------
@register_op
class MoERouterOp(Op):
    """x (..., E) -> (weights (..., k) float32, expert ids (..., k) int32):
    logits over ALL `n` experts accumulated in float32, the k largest, and
    the softmax over those k logits (= softmax over n, top-k, renormalised:
    `norm_topk_prob`), times `scale` (`routed_scaling_factor`). With
    `scoring="sigmoid"` each logit is scored by its own sigmoid (monotone:
    the same k are chosen) and the k scores are divided by their sum before
    `scale`. No bias, no correction term, no group limiting."""

    op_type = OpType.MOE_ROUTER

    def output_shapes(self):
        (x,) = self.inputs
        scoring = self.params.get("scoring", "softmax")
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"moe_router {self.name}: scoring={scoring!r}"
                             " is neither 'softmax' nor 'sigmoid'")
        out = tuple(x.dims[:-1]) + (self.params["k"],)
        return [out, out], [DataType.DT_FLOAT, DataType.DT_INT32]

    def acts_per_position(self):
        return self._off_token_axis([-1])   # the contracted axis

    def weight_specs(self):
        from ..core.op import WeightSpec
        from ..runtime.initializers import DefaultInitializer

        (x,) = self.inputs
        return [WeightSpec(
            "kernel", (x.dims[-1], self.params["n"]), x.dtype,
            self.params.get("kernel_initializer") or DefaultInitializer())]

    def lower(self, ctx, inputs, weights):
        from .common import matmul_dtype

        x = inputs[0]
        cdt = matmul_dtype(getattr(ctx, "config", None), x.dtype)
        with jax.named_scope("moe:route"):
            logits = jnp.dot(x.astype(cdt), weights["kernel"].astype(cdt),
                             preferred_element_type=jnp.float32)
            top, idx = jax.lax.top_k(logits, self.params["k"])
            if self.params.get("scoring", "softmax") == "sigmoid":
                score = jax.nn.sigmoid(top)
                w = score / jnp.sum(score, axis=-1, keepdims=True)
            else:
                w = jax.nn.softmax(top, axis=-1)
            w = w * self.params.get("scale", 1.0)
        return [w, idx.astype(jnp.int32)]

    def flops(self) -> float:
        x = self.inputs[0]
        return 2.0 * moe_tokens(x.dims) * x.dims[-1] * self.params["n"]


def local_assignments(idx, first: int, count: int):
    """idx (T, k) expert ids over all experts -> (local (T*k,) int32: the
    id among the `count` experts held here, `count` for an assignment that
    falls on an absent expert; mine (T*k,) bool)."""
    flat = idx.reshape(-1).astype(jnp.int32) - first
    mine = (flat >= 0) & (flat < count)
    return jnp.where(mine, flat, count), mine


def gated_experts_oracle(x, w, idx, weights, first: int, count: int):
    """The test oracle of `GatedExpertsOp`: every local expert applied to
    every token under a mask, one expert at a time, in float32."""
    xf = x.astype(jnp.float32)
    out = jnp.zeros(xf.shape[:-1] + (weights["w_down"].shape[-1],),
                    jnp.float32)
    for e in range(count):
        gate = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        h = (jax.nn.silu(xf @ weights["w_gate"][e].astype(jnp.float32))
             * (xf @ weights["w_up"][e].astype(jnp.float32)))
        out = out + gate[..., None] * (
            h @ weights["w_down"][e].astype(jnp.float32))
    return out


# The most token rows for which `GatedExpertsOp` sends every row through
# every held expert: where the two forms cross on a v5e at 32 held experts
# of 4096 x 2048 (PERF.md section 6, PR 28). The chip's grouped-GEMM kernel
# multiplies one 512-row tile of sorted rows through EVERY group the tile
# spans, so its time does not fall with the rows (5.1-5.5 ms a layer from
# 128 to 640 tokens, 7.9 at 2,048), while the dense product streams the
# three stacks once at the memory's speed up to the chip's ridge (~240
# rows, 2.2 ms) and then grows with the rows (5.4 ms at 640, 17.3 at 2,048).
FEW_ROWS_MAX = 640


def few_rows(tokens: int) -> bool:
    """Whether a routed product over `tokens` (static) token rows takes the
    few-rows form: a decode iteration's slots, a prefill chunk, a small
    batch; a long prefill or a training batch is grouped."""
    return tokens <= FEW_ROWS_MAX


def _few_rows_product(x, w, idx, weights, first: int, count: int):
    """x (T, E) in the matmul dtype, w / idx (T, k) -> (y (T, E) float32,
    assignments per local expert (count,) int32). Every token row through
    every held expert, the router's weight (0 where the token did not
    choose the expert) applied before the sum over experts, which the down
    product takes together with the hidden dimension: one contraction of
    count x F, no (T, count, E) intermediate. The three stacks are read in
    place, once; no sort, no gather."""
    with jax.named_scope("moe:combine"):
        held = first + jnp.arange(count, dtype=jnp.int32)
        chose = idx.astype(jnp.int32)[:, :, None] == held       # (T, k, count)
        gate = jnp.sum(jnp.where(chose, w.astype(jnp.float32)[:, :, None],
                                 0.0), axis=1)                  # (T, count)
        sizes = jnp.sum(chose, axis=(0, 1), dtype=jnp.int32)
    with jax.named_scope("moe:experts"):
        up = lambda m: jnp.einsum("td,edf->tef", x, m.astype(x.dtype),
                                  preferred_element_type=jnp.float32)
        h = (jax.nn.silu(up(weights["w_gate"])) * up(weights["w_up"])
             * gate[:, :, None]).astype(x.dtype)
        y = jnp.einsum("tef,efd->td", h, weights["w_down"].astype(x.dtype),
                       preferred_element_type=jnp.float32)
    return y, sizes


def _ragged_dot(a, m, sizes):
    """XLA's grouped matmul: the chip's compiler lowers it to a grouped-GEMM
    kernel of 512-row tiles."""
    return jax.lax.ragged_dot(a, m, sizes, preferred_element_type=jnp.float32)


# sorted rows a step of the tiled grouped matmul multiplies: a tile that
# spans g groups is multiplied g times, so many small groups want a small
# tile (a v5e, 512 token rows x 8 through 256 experts of 2048 x 512, ms a
# layer: 2.60 at 64, 2.62 at 128, 2.75 at 256; PERF.md section 6, PR 33).
# A decode step's few sorted rows do not want a smaller one (40 token rows
# x 8, padded to 384: 1.651 at 16, 1.614 at 32, 1.598 at 64, 1.597 at 128;
# 8 rows 0.529 / 0.529 / 0.517 / 0.520; PERF.md section 6, PR 36)
GROUP_TILE_ROWS = 128


def _tiled_dot(a, m, sizes):
    """The same product by the Pallas grouped matmul (jax's megablox) at
    tiles of `GROUP_TILE_ROWS` sorted rows by ONE expert's whole matrix
    (`registry.small_experts`: it is one VMEM block). Each tile is
    multiplied with the groups it spans alone, and an expert nobody chose
    is not read."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    from ..runtime.platform import pallas_interpret

    pad = -a.shape[0] % GROUP_TILE_ROWS      # rows of no group, at the end
    out = gmm(jnp.pad(a, ((0, pad), (0, 0))), m, sizes, jnp.float32,
              (GROUP_TILE_ROWS,) + m.shape[1:], interpret=pallas_interpret())
    return out[:a.shape[0]]


def _grouped_product(x, w, idx, weights, first: int, count: int,
                     dot=_ragged_dot):
    """The same (y, assignments per local expert) for many rows:
    assignments sorted by local expert (absent ones last), the token rows
    gathered in that order, three grouped matmuls over the sorted rows
    (`dot`: `_ragged_dot` or `_tiled_dot`), rows brought back to token
    order and the k weighted parts of a token summed in float32."""
    k = idx.shape[-1]
    with jax.named_scope("moe:sort"):
        local, mine = local_assignments(idx, first, count)
        order = jnp.argsort(local, stable=True)     # absent ones last
        sizes = jnp.bincount(local, length=count + 1)[:count].astype(
            jnp.int32)
        rows = x[order // k]                        # (T*k, E)
        held = jnp.arange(rows.shape[0]) < jnp.sum(sizes)
    with jax.named_scope("moe:experts"):
        grouped = lambda a, m: dot(a, m.astype(x.dtype), sizes)
        h = (jax.nn.silu(grouped(rows, weights["w_gate"]))
             * grouped(rows, weights["w_up"])).astype(x.dtype)
        # rows past the held assignments belong to no group: the grouped
        # product leaves them unwritten
        y = jnp.where(held[:, None], grouped(h, weights["w_down"]), 0.0)
    with jax.named_scope("moe:combine"):
        back = jnp.argsort(order)                   # sorted row of (t, i)
        gate = jnp.where(mine, w.reshape(-1).astype(jnp.float32), 0.0)
        y = jnp.sum((y[back] * gate[:, None]).reshape((-1, k, y.shape[-1])),
                    axis=1)
    return y, sizes


@register_op
class GatedExpertsOp(Op):
    """The routed part of a gated (SiLU) expert layer for the experts THIS
    holder has: inputs x (..., E), router weights (..., k), expert ids
    (..., k) over all `experts_total`; weights w_gate / w_up
    (count, E, F) and w_down (count, F, E), no bias.

        y = sum_i [first <= id_i < first + count] w_i E_{id_i}(x),
        E(x) = (silu(x W_g) * x W_u) W_d

    `local_experts = (first, count)` is the contract of expert
    parallelism: the router ranks all `experts_total`, this op keeps the
    assignments that fall on its own experts and computes every one of
    them — nothing is dropped, no capacity. What the absent experts add is
    the other holders' part of the sum; on one holder nothing stands in
    for it.

    The three products take one of two forms, chosen from the static
    number of token rows (`few_rows`): for few rows every row goes through
    every held expert (`_few_rows_product`), for many the assignments are
    sorted by expert and multiplied group by group (`_grouped_product`,
    by XLA's `ragged_dot`). Few rows of SMALL experts are sorted too and
    multiplied by the Pallas grouped matmul (`_tiled_dot`) on either side
    of the few-rows form's own ground: past the chip's ridge, and where
    the step's assignments are expected to miss a good part of the held
    experts' bytes, which that kernel then does not read (a decode step of
    40 rows x 8 on 256 experts hits 71 % of them);
    `kernels/registry.py small_experts` decides, serving steps on one TPU
    alone.

    Router health, threaded by the continuous batcher from one decode
    iteration to the next (`serving_counters`): `assignments` (local
    assignments so far), `experts_hit` (distinct local experts that got a
    token, summed over steps), `steps`, `load` (the last step's
    assignments per local expert), `few_rows_steps` (the steps that took
    the few-rows form). Dropped tokens: none, by construction.
    """

    op_type = OpType.GATED_EXPERTS
    serving_counters = ("assignments", "experts_hit", "steps", "load",
                        "few_rows_steps")

    def _local(self):
        first, count = self.params["local_experts"]
        total = self.params["experts_total"]
        if not (0 <= first and count > 0 and first + count <= total):
            raise ValueError(
                f"gated_experts {self.name}: local_experts=({first},"
                f" {count}) is not a range of the {total} experts")
        return int(first), int(count)

    def output_shapes(self):
        x = self.inputs[0]
        self._local()
        return [x.dims], [x.dtype]

    def acts_per_position(self):
        # a token's own k assignments through its own experts; the
        # counters sum over whatever rows the step was given
        return self._off_token_axis([-1])

    def weight_specs(self):
        from ..core.op import WeightSpec
        from ..runtime.initializers import DefaultInitializer

        x = self.inputs[0]
        e, f = x.dims[-1], self.params["expert_hidden_size"]
        _, count = self._local()
        user = self.params.get("kernel_initializer")
        up = user or DefaultInitializer(fan_in=e, fan_out=f)
        down = user or DefaultInitializer(fan_in=f, fan_out=e)
        return [WeightSpec("w_gate", (count, e, f), x.dtype, up),
                WeightSpec("w_up", (count, e, f), x.dtype, up),
                WeightSpec("w_down", (count, f, e), x.dtype, down)]

    def state_specs(self):
        from ..core.op import WeightSpec
        from ..runtime.initializers import ZeroInitializer

        _, count = self._local()
        z = ZeroInitializer()
        return [WeightSpec("assignments", (), DataType.DT_INT32, z),
                WeightSpec("experts_hit", (), DataType.DT_INT32, z),
                WeightSpec("steps", (), DataType.DT_INT32, z),
                WeightSpec("load", (count,), DataType.DT_INT32, z),
                WeightSpec("few_rows_steps", (), DataType.DT_INT32, z)]

    @staticmethod
    def _tiled(ctx, rows: int, k: int, experts_total: int, matrix,
               cdt) -> bool:
        """Whether a step of few token rows, `k` of `experts_total` experts
        a row through the held experts' `matrix` stack, sorts them and
        takes the Pallas grouped matmul: the registry's call
        (`small_experts`), and only where nothing differentiates the step
        (the kernel's rows of no group are unwritten, which a gradient
        would read) and GSPMD does not partition it."""
        from ..ffconst import CompMode
        from ..kernels.registry import KERNELS

        return bool(
            ctx.mode != CompMode.COMP_MODE_TRAINING
            and not ctx.gspmd_partitioned()
            and KERNELS.select("grouped_experts", experts=(
                rows, k, matrix.shape[0], experts_total,
                matrix.size // matrix.shape[0] * jnp.dtype(cdt).itemsize)))

    def lower(self, ctx, inputs, weights):
        from .common import emit_dtype, matmul_dtype

        x, w, idx = inputs[:3]
        first, count = self._local()
        lead = x.shape[:-1]
        x = x.reshape((-1, x.shape[-1]))
        k = idx.shape[-1]
        cdt = matmul_dtype(getattr(ctx, "config", None), x.dtype)
        few = few_rows(x.shape[0])
        product = _few_rows_product if few else _grouped_product
        if few and self._tiled(ctx, x.shape[0], k,
                               self.params["experts_total"],
                               weights["w_gate"], cdt):
            few, product = False, functools.partial(_grouped_product,
                                                    dot=_tiled_dot)
        out, sizes = product(x.astype(cdt), w.reshape(-1, k),
                             idx.reshape(-1, k), weights, first, count)

        if (self.name, "assignments") in ctx.state:
            for var, by in (
                    ("assignments", jnp.sum(sizes)),
                    ("experts_hit", jnp.sum((sizes > 0).astype(jnp.int32))),
                    ("steps", 1), ("few_rows_steps", int(few))):
                ctx.state_updates[(self.name, var)] = (
                    ctx.state[(self.name, var)] + by)
            ctx.state_updates[(self.name, "load")] = sizes

        out = out.reshape(lead + (out.shape[-1],))
        return [out.astype(emit_dtype(getattr(ctx, "config", None),
                                      self.outputs[0].dtype))]

    def flops(self) -> float:
        """Operations of the assignments that fall here under a uniform
        router: tokens x k x count / experts_total of them, 2 per weight of
        an expert's three matrices."""
        x = self.inputs[0]
        _, count = self._local()
        share = count / float(self.params["experts_total"])
        t = moe_tokens(x.dims) * self.inputs[2].dims[-1] * share
        return 2.0 * t * 3 * x.dims[-1] * self.params["expert_hidden_size"]
