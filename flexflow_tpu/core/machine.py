"""Device-mesh model: MachineView / MachineResource for TPU.

In the reference, `MachineView` (include/flexflow/machine_view.h:14-35) is a
strided grid of device ids and `FFMapper::slice_task` (src/mapper/mapper.cc:364)
places each point task. On TPU the whole mapper layer collapses into GSPMD: a
MachineView here is an *ordered set of named mesh axes with sizes*; tensors
reference these axes in their ParallelDims and XLA emits the collectives.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class MachineResource:
    """Total resources available (reference: machine_view.h:51-60)."""

    num_nodes: int = 1
    devices_per_node: int = 1
    start_device_id: int = 0

    @property
    def num_devices(self) -> int:
        return self.num_nodes * self.devices_per_node


@dataclasses.dataclass(frozen=True)
class MachineView:
    """A device sub-grid: ordered (axis name, size) pairs + start offset.

    hash()/`device_ids()` mirror the reference's MachineView::hash and
    start_device_id + sum(point*stride) addressing (mapper.cc:440-447) for a
    contiguous row-major grid.
    """

    axes: Tuple[Tuple[str, int], ...] = ()
    start_device_id: int = 0

    @property
    def ndims(self) -> int:
        return len(self.axes)

    @property
    def num_devices(self) -> int:
        n = 1
        for _, s in self.axes:
            n *= s
        return n

    @property
    def axis_sizes(self) -> Dict[str, int]:
        return dict(self.axes)

    def device_ids(self) -> List[int]:
        return list(
            range(self.start_device_id, self.start_device_id + self.num_devices)
        )

    def hash(self) -> int:
        h = 17
        h = h * 31 + self.start_device_id
        for name, size in self.axes:
            h = h * 31 + hash(name) % (2**31)
            h = h * 31 + size
        return h & 0x7FFFFFFFFFFFFFFF

    def with_axis(self, name: str, size: int) -> "MachineView":
        return MachineView(self.axes + ((name, size),), self.start_device_id)

    def __str__(self):
        body = "x".join(f"{n}:{s}" for n, s in self.axes) or "1"
        return f"MV[{body}@{self.start_device_id}]"


def data_parallel_view(num_devices: int) -> MachineView:
    """Default fallback view (reference: config.h:96 DataParallelism_GPU)."""
    return MachineView(axes=(("data", num_devices),))


def make_mesh(axis_sizes: Dict[str, int], devices: Optional[Sequence] = None):
    """Build a jax Mesh with the given named axis sizes.

    The product of axis sizes must equal (or divide) the device count; extra
    devices are left out (reference analog: a MachineView covering a subset of
    the cluster).
    """
    from jax.sharding import Mesh

    if devices is None:
        from ..runtime.platform import backend_devices

        devices = backend_devices()
    names = tuple(axis_sizes.keys())
    sizes = tuple(axis_sizes.values())
    need = int(np.prod(sizes)) if sizes else 1
    if need > len(devices):
        raise ValueError(f"mesh needs {need} devices, have {len(devices)}")
    dev_array = np.array(devices[:need]).reshape(sizes if sizes else (1,))
    if not names:
        names = ("data",)
        dev_array = dev_array.reshape((1,))
    return Mesh(dev_array, names)


def mesh_for_view(view: MachineView, devices: Optional[Sequence] = None):
    return make_mesh(dict(view.axes), devices)
