"""Multi-host distributed launch.

reference parity: MULTI-NODE.md + the GASNet/UCX conduits
(config/config.linux:38-44) and `mpirun` launch wrappers
(tests/multinode_helpers/mpi_wrapper{1,2}.sh). TPU-native equivalent: JAX's
coordination service — every host calls `initialize()` (jax.distributed),
after which `jax.devices()` spans the whole pod slice and the same pjit
programs scale across DCN with zero code change. The reference's NCCL
communicator plumbing (model.cc:3129-3168) has no analog here: collectives
are compiled into the XLA program.

Launch patterns (see MULTI-NODE.md):
  - TPU pods: run the same script on every host (`gcloud ... tpu-vm ssh
    --worker=all`); initialize() autodetects coordinator/process ids from
    the TPU metadata.
  - CPU/GPU clusters or explicit setups: pass coordinator_address,
    num_processes, process_id (or set JAX_COORDINATOR_ADDRESS,
    JAX_NUM_PROCESSES, JAX_PROCESS_ID; SLURM/OpenMPI envs are autodetected
    by jax.distributed itself).
"""
from __future__ import annotations

import os
from typing import Dict, Optional, Sequence

_initialized = False


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               local_device_ids: Optional[Sequence[int]] = None) -> None:
    """Join (or start) the JAX coordination service. Idempotent."""
    global _initialized
    if _initialized:
        return
    import jax

    # cross-process collectives on the CPU backend need nothing here: the
    # installed jax routes them through gloo by default
    kwargs = {}
    if coordinator_address or os.environ.get("JAX_COORDINATOR_ADDRESS"):
        kwargs["coordinator_address"] = (
            coordinator_address or os.environ["JAX_COORDINATOR_ADDRESS"])
    if num_processes is not None or os.environ.get("JAX_NUM_PROCESSES"):
        kwargs["num_processes"] = int(
            num_processes if num_processes is not None
            else os.environ["JAX_NUM_PROCESSES"])
    if process_id is not None or os.environ.get("JAX_PROCESS_ID"):
        kwargs["process_id"] = int(
            process_id if process_id is not None
            else os.environ["JAX_PROCESS_ID"])
    if local_device_ids is not None:
        kwargs["local_device_ids"] = list(local_device_ids)
    jax.distributed.initialize(**kwargs)
    _initialized = True


def shutdown() -> None:
    global _initialized
    if not _initialized:
        return
    import jax

    jax.distributed.shutdown()
    _initialized = False


def is_multi_host() -> bool:
    import jax

    return jax.process_count() > 1


def host_info() -> Dict[str, int]:
    import jax

    return {
        "process_id": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": len(jax.local_devices()),
        "global_devices": len(jax.devices()),
    }


def pod_mesh(axis_sizes: Dict[str, int]):
    """Build a global mesh over all pod devices, laying axes out so the
    innermost (last) axis maps to devices within a host — intra-host/ICI
    first, DCN only for the outer axes (the scaling-book layout rule:
    collectives on fast links, cross-host traffic on the slowest axis)."""
    import jax

    from ..core.machine import make_mesh

    return make_mesh(axis_sizes, devices=jax.devices())


def data_parallel_mesh():
    """The only_data_parallel fallback over the whole pod."""
    import jax

    return pod_mesh({"data": len(jax.devices())})
