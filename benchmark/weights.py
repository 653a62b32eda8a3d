"""Seeded weights, made on the device in one jitted call.

A configuration's builder lists its parameters as a `spec`:
`{op name: {weight name: (shape, kind)}}` with kind one of
  "matrix"  normal, std 0.02           (projections, FFN, embeddings, heads)
  "bias"    normal, std 0.02           (so a dropped bias shows)
  "gain"    1 + normal * 0.02          (layer-norm gamma)
The program is handed the resulting tree in place of its own initial
parameters; the plain reference calls the same function with the same seed,
so it takes nothing the program made.
"""
from __future__ import annotations

from typing import Dict, Tuple

STD = 0.02


def seed_key(seed: int):
    """A PRNG key from any whole number the driver may pass (past 2**31)."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def replicated(mesh):
    """Where every leaf goes on a mesh: all of it on every chip (None on
    one device: the default placement)."""
    if mesh is None:
        return None
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec())


def make_weights(spec: Dict[str, Dict[str, Tuple[tuple, str]]], seed: int,
                 dtype="float32", sharding=None):
    """The whole tree in ONE jitted call (leaf by leaf would pay a dispatch
    and a compile cache lookup per leaf). `sharding`: where every leaf goes
    (a replicated NamedSharding on a mesh); None = the default device."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    flat = [(op, w, tuple(shape), kind)
            for op, ws in sorted(spec.items())
            for w, (shape, kind) in sorted(ws.items())]

    def build(key):
        out: Dict[str, Dict[str, object]] = {}
        for i, (op, w, shape, kind) in enumerate(flat):
            n = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * STD
            if kind == "gain":
                n = 1.0 + n
            elif kind not in ("matrix", "bias"):
                raise ValueError(f"weights: unknown kind {kind!r}")
            out.setdefault(op, {})[w] = n.astype(dt)
        return out

    fn = jax.jit(build, out_shardings=sharding) if sharding is not None \
        else jax.jit(build)
    return fn(seed_key(seed))
