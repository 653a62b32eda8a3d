"""Pipeline parallelism: GPipe over a 'stage' mesh axis.

New capability vs the reference (OP_PIPELINE exists only as an unused enum,
ffconst.h:159 — no implementation): homogeneous stages hold their slice of a
stacked parameter tree (leading dim = stages, sharded over the 'stage'
axis); microbatches flow through the ring with `lax.ppermute`, one hop per
tick, under a `lax.scan` whose reverse-mode differentiation IS the backward
pipeline schedule — no hand-written backward pass.

Schedule (GPipe): T = M + S - 1 ticks. At tick t, stage s computes
microbatch t - s (when 0 <= t - s < M); stage 0 feeds from the microbatch
queue, later stages from the activation ppermuted in at the previous tick;
the last stage's outputs are collected and broadcast with a masked psum.
Bubble fraction is (S-1)/T, driven down by more microbatches, exactly as in
GPipe. Activations stay on neighbor ICI links.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax


def gpipe_stage_loop(stage_fn: Callable, local_params, x_micro,
                     n_stages: int, axis_name: str = "stage", rng=None,
                     fold_axes=()):
    """Runs INSIDE shard_map. local_params: this stage's parameter slice
    (leading stacked dim of size 1, squeezed here). x_micro: (M, ...) the
    full microbatch queue (replicated — only stage 0 reads it). Returns
    (M, ...) outputs, replicated across stages. rng (optional): folded per
    tick and per mesh coordinate along `fold_axes` (the stage axis plus any
    batch-sharding axes), then passed as stage_fn's third argument —
    dropout inside a stage differs per stage, per microbatch, AND per
    data shard, like a sequential execution over the global batch would."""
    s = lax.axis_index(axis_name)
    params = jax.tree_util.tree_map(lambda p: p[0], local_params)
    if rng is not None:
        for ax in (axis_name, *fold_axes):
            rng = jax.random.fold_in(rng, lax.axis_index(ax))
    m = x_micro.shape[0]
    ticks = m + n_stages - 1  # static: mesh size and M are trace-time consts
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(buf, t):
        # stage 0 pulls from the queue; others use the permuted-in buffer
        mb = x_micro[jnp.clip(t, 0, m - 1)]
        x_in = jnp.where(s == 0, mb, buf)
        if rng is None:
            y = stage_fn(params, x_in)
        else:
            y = stage_fn(params, x_in, jax.random.fold_in(rng, t))
        out = y  # meaningful on the LAST stage for microbatch t - (S-1)
        buf_next = lax.ppermute(y, axis_name, perm)
        return buf_next, out

    # the scan carry becomes stage-varying after one tick: mark the init
    # accordingly (shard_map vma type check; same pattern as ring_attention)
    zero = lax.pcast(jnp.zeros_like(x_micro[0]), (axis_name,), to="varying")
    _, outs = lax.scan(tick, zero, jnp.arange(ticks))
    # microbatch i completes on the last stage at tick i + S - 1
    outs = lax.slice_in_dim(outs, n_stages - 1, n_stages - 1 + m, axis=0)
    # broadcast the last stage's outputs to every stage (masked psum)
    mask = (s == n_stages - 1).astype(outs.dtype)
    return lax.psum(outs * mask, axis_name)


def gpipe_apply(stage_fn: Callable, stacked_params, x, mesh,
                axis_name: str = "stage", microbatches: int = 4):
    """Pipeline-parallel application of `stages` homogeneous stage_fns.

    stacked_params: pytree whose leaves have a leading `stages` dim, sharded
    over `axis_name`. x: (B, ...) global batch (B % microbatches == 0).
    Returns (B, ...) outputs. Differentiable end to end.
    """
    return gpipe_apply_mesh(stage_fn, stacked_params, x, mesh,
                            axis_name=axis_name, microbatches=microbatches)


def gpipe_apply_mesh(stage_fn: Callable, stacked_params, x, mesh,
                     axis_name: str = "stage", microbatches: int = 4,
                     data_axis=None, rng=None):
    """Pipeline application on a mesh that may also carry a data axis.

    The executor's PCG path: `x` is the (B, ...) region input, possibly
    batch-sharded over `data_axis`; each (data-shard, stage) device runs the
    GPipe loop on its batch shard, ppermuting activations over `axis_name`
    only. stage_fn(params_slice, x_micro[, rng]) applies one stage's chunk
    of the region. Differentiable end to end (scan reverse-mode is the
    backward pipeline schedule)."""
    from jax.sharding import PartitionSpec as P

    shard_map = jax.shard_map

    b = x.shape[0]
    n_stages = mesh.shape[axis_name]
    if b % microbatches != 0:
        raise ValueError(
            f"pipeline microbatches ({microbatches}) must divide the batch "
            f"({b}) — set config.pipeline_microbatches accordingly")
    micro_b = b // microbatches
    dp = mesh.shape[data_axis] if data_axis else 1
    if micro_b % dp != 0:
        raise ValueError(
            f"per-microbatch batch ({micro_b}) must divide over the data "
            f"axis ({dp}): batch={b}, microbatches={microbatches}")
    stacked = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    assert stacked == n_stages, (
        f"stacked stage dim {stacked} != mesh '{axis_name}' size {n_stages}")
    x_micro = x.reshape((microbatches, micro_b) + x.shape[1:])

    pspec = jax.tree_util.tree_map(lambda _: P(axis_name), stacked_params)
    xspec = P(None, data_axis) if data_axis else P()
    args = (stacked_params, x_micro) + ((rng,) if rng is not None else ())
    in_specs = (pspec, xspec) + ((P(),) if rng is not None else ())

    fold_axes = (data_axis,) if data_axis else ()

    def body(p, xm, *r):
        return gpipe_stage_loop(stage_fn, p, xm, n_stages, axis_name,
                                rng=r[0] if r else None,
                                fold_axes=fold_axes)

    fn = shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=xspec)
    out = fn(*args)
    return out.reshape((b,) + out.shape[2:])
