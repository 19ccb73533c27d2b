"""Streaming datasets for larger-than-memory files (reference:
``heat/utils/data/partial_dataset.py``).

``PartialH5Dataset`` streams HDF5 in chunks with a background prefetch
thread — per-shard byte-range reads replace the reference's per-rank
parallel-HDF5 loads.
"""

from __future__ import annotations

import queue
import threading
from typing import Optional

import numpy as np

from ...core import factories

__all__ = ["PartialH5Dataset", "PartialH5DataLoaderIter"]


class PartialH5Dataset:
    """Iterate an HDF5 dataset in batches without loading it whole.

    Parameters mirror the reference: ``file``, ``dataset_names``,
    ``batch_size``, ``initial_load`` (rows resident at once), ``use_gpu``
    kept for parity (placement is the mesh's concern here).
    """

    def __init__(self, file: str, comm=None, dataset_names="data", initial_load: int = 7000,
                 load_length: Optional[int] = None, use_gpu: bool = True, np_buffer: bool = True,
                 np_buffer_dataset_names="data", transforms=None):
        try:
            import h5py
        except ImportError as e:
            raise RuntimeError("PartialH5Dataset requires h5py") from e
        self.file = file
        self.names = [dataset_names] if isinstance(dataset_names, str) else list(dataset_names)
        self.load_size = load_length or initial_load
        self.transforms = transforms
        with h5py.File(file, "r") as f:
            self.length = f[self.names[0]].shape[0]
            self.shapes = {n: f[n].shape for n in self.names}

    def __len__(self) -> int:
        return self.length

    def _reader(self, q: "queue.Queue", chunk: int, stop: "threading.Event"):
        import h5py

        try:
            with h5py.File(self.file, "r") as f:
                for lo in range(0, self.length, chunk):
                    if stop.is_set():
                        return
                    hi = min(lo + chunk, self.length)
                    block = {n: np.asarray(f[n][lo:hi]) for n in self.names}
                    while not stop.is_set():
                        try:
                            q.put(block, timeout=0.1)
                            break
                        except queue.Full:
                            continue
        finally:
            while True:
                try:
                    q.put(None, timeout=0.1)
                    return
                except queue.Full:
                    if stop.is_set():
                        return

    def __iter__(self):
        """Yield dicts of DNDarrays (one chunk at a time, prefetched).

        Early iterator abandonment signals the reader thread to stop, so the
        HDF5 handle is released (no leaked threads across partial epochs).
        """
        q: "queue.Queue" = queue.Queue(maxsize=2)
        stop = threading.Event()
        t = threading.Thread(target=self._reader, args=(q, self.load_size, stop), daemon=True)
        t.start()
        try:
            while True:
                block = q.get()
                if block is None:
                    break
                out = {}
                for n, arr in block.items():
                    if self.transforms is not None:
                        arr = self.transforms(arr)
                    out[n] = factories.array(arr, split=0)
                yield out if len(out) > 1 else next(iter(out.values()))
        finally:
            stop.set()
            while True:  # drain so a blocked put wakes up
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
            t.join(timeout=2.0)


PartialH5DataLoaderIter = PartialH5Dataset  # reference-name alias
