"""SingleDataLoader (reference: src/dataloader/dataloader.cc:1-842,
flexflow_cffi.py:2451).

The reference loads the full numpy dataset into zero-copy host memory and
index-launches per-shard GPU copy tasks each `next_batch`. Here the dataset
stays in host numpy and — when the native core is available — a C++
producer thread (src/ffcore/dataloader.cc) gathers each (optionally
shuffled) batch into a prefetch ring ahead of the training step, playing
the role of the reference's staged copy tasks; `next_batch` then
device_puts the prepared batch with the batch dim sharded over the mesh's
data axis. A pure-numpy path remains when libffcore can't be built."""
from __future__ import annotations

from typing import Optional

import numpy as np


class SingleDataLoader:
    def __init__(self, ffmodel, input_tensor, full_array: np.ndarray,
                 num_samples: Optional[int] = None, data_type=None,
                 shuffle: bool = False, seed: int = 0,
                 prefetch: bool = True):
        self.model = ffmodel
        self.input_tensor = input_tensor
        self.data = np.ascontiguousarray(full_array)
        self.num_samples = num_samples or full_array.shape[0]
        self.batch_size = ffmodel.config.batch_size
        self.shuffle = shuffle
        self.seed = seed
        self.next_index = 0
        self._stream = None
        if prefetch:
            from .. import native

            # no native core (the loader logs why, once): the numpy path
            # below yields the same batches, without the prefetch ring
            if native.available():
                self._stream = native.BatchStream(
                    self.data[: self.num_samples], self.batch_size,
                    shuffle=shuffle, seed=seed)
        self._order = None
        self._epoch = 0
        ffmodel._attach_dataloader(self)

    @property
    def num_batches(self) -> int:
        return self.num_samples // self.batch_size

    @property
    def backend(self) -> str:
        return "native" if self._stream is not None else "numpy"

    def reset(self) -> None:
        self.next_index = 0
        self._epoch = 0
        self._order = None
        if self._stream is not None:
            self._stream.reset()

    def _numpy_next(self) -> np.ndarray:
        lo = self.next_index
        hi = lo + self.batch_size
        if hi > self.num_samples:
            self.next_index = 0
            self._epoch += 1
            self._order = None
            lo, hi = 0, self.batch_size
        if self.shuffle:
            if self._order is None:
                # per-epoch reshuffle with the native stream's reseeding
                # scheme seed+epoch (orders are NOT bit-identical across
                # backends — numpy vs mt19937_64 std::shuffle)
                rng = np.random.RandomState(
                    (self.seed + self._epoch) % (2**32))
                self._order = rng.permutation(self.num_samples)
            idx = self._order[lo:hi]
            self.next_index = hi
            return self.data[idx]
        self.next_index = hi
        return self.data[lo:hi]

    def next_batch(self, ffmodel=None) -> np.ndarray:
        if self._stream is not None:
            # copy out of the ring slot: SingleDataLoader's contract is a
            # stable array (callers may retain batches across calls); the
            # prefetch win is the background GATHER, which still overlaps
            # compute. Zero-copy consumers can use native.BatchStream
            # directly and honor its valid-until-next-call rule.
            return self._stream.next_batch().copy()
        return self._numpy_next()
