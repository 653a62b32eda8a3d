"""Manual-collective lowering for redistribution's same-mesh gather moves.

The resharding executor (resharding/executor.py) lowers most scheduled
rounds through the XLA transfer engine, which synthesizes the wire
pattern itself. For the one case where the schedule's named collective
can run as written — a same-mesh move whose every changed dim is a pure
all-gather (degree d -> 1) — this module executes exactly that
collective with shard_map + ``lax.all_gather``, the portable-collective
lowering of arXiv:2112.01075. Parity with the transfer-engine path is
pinned by tests/test_resharding.py.
"""
from __future__ import annotations

from typing import Sequence


def allgather_dims(x, mesh, old_spec, dims: Sequence[int]):
    """All-gather `x` (sharded per `old_spec`, a resharding.ArraySpec) on
    `mesh` along every data dim in `dims`, keeping all other dims'
    sharding. Returns the gathered array, replicated over the gathered
    axes."""
    import jax
    from jax.sharding import PartitionSpec

    in_spec = old_spec.partition_spec()
    out_entries = [None if d in dims else in_spec[d]
                   for d in range(len(old_spec.degrees))]
    out_spec = PartitionSpec(*out_entries)
    axis_names = [old_spec.axes[d] for d in dims]

    def body(blk):
        for d, name in zip(dims, axis_names):
            blk = jax.lax.all_gather(blk, name, axis=d, tiled=True)
        return blk

    # check_vma=False: the gathered output is replicated over the
    # gathered axes, which the static vma checker cannot infer through
    # all_gather
    return jax.shard_map(body, mesh=mesh, in_specs=in_spec,
                         out_specs=out_spec, check_vma=False)(x)
