"""Batching data loader.

The DataLoader role of the reference's Dataset/Sampler/DataLoader triad
(sections/task3.tex:27-43): draws an index stream from a Sampler, gathers
rows from the in-memory dataset, and yields fixed-shape numpy batches.
Fixed shapes matter on TPU — a ragged final batch would trigger an XLA
recompile, so ``drop_remainder`` defaults to True (the MindSpore notebook's
``batch(drop_remainder=True)`` made the same choice for graph mode,
reference: codes/task1/mindspore/model.ipynb cell 2).

For multi-replica training the loader can batch for SEVERAL replicas at
once (``global_batch``): on a single host driving an N-device mesh, it
stacks each replica's sampler stream into a leading device axis, ready to be
sharded over the mesh's ``data`` axis.
"""

from __future__ import annotations

from typing import Iterator

import numpy as np

from tpudml.data.datasets import ArrayDataset
from tpudml.data.sampler import Sampler, SequentialSampler
from tpudml.obs.tracer import span


class DataLoader:
    def __init__(
        self,
        dataset: ArrayDataset,
        batch_size: int,
        sampler: Sampler | None = None,
        drop_remainder: bool = True,
    ):
        self.dataset = dataset
        self.batch_size = batch_size
        self.sampler = sampler or SequentialSampler(len(dataset), shuffle=False)
        self.drop_remainder = drop_remainder

    def set_epoch(self, epoch: int) -> None:
        self.sampler.set_epoch(epoch)

    def __len__(self) -> int:
        n = len(self.sampler)
        return n // self.batch_size if self.drop_remainder else -(-n // self.batch_size)

    def __iter__(self) -> Iterator[tuple[np.ndarray, np.ndarray]]:
        idx = np.fromiter(iter(self.sampler), dtype=np.int64)
        end = (
            len(idx) - len(idx) % self.batch_size if self.drop_remainder else len(idx)
        )
        # Fused native gather (+ normalize for u8 storage) when the dataset
        # provides it; plain fancy indexing otherwise.
        gather = getattr(self.dataset, "gather", None)
        for start in range(0, end, self.batch_size):
            batch = idx[start : start + self.batch_size]
            with span("gather", "data", rows=len(batch)):
                if gather is not None:
                    out = gather(batch)
                else:
                    out = self.dataset.images[batch], self.dataset.labels[batch]
            yield out


class ShardedDataLoader:
    """Batches for all replicas of a mesh ``data`` axis at once.

    Yields ``[R, B, ...]`` arrays (R = num_replicas, B = per-replica batch):
    the single-host analogue of R processes each running their own loader,
    with identical per-replica index streams (each replica r's stream comes
    from its own Sampler(rank=r)). Reshape/shard over the mesh data axis to
    feed a shard_map/pjit step.
    """

    def __init__(
        self,
        dataset: ArrayDataset,
        batch_size: int,
        samplers: list[Sampler],
        drop_remainder: bool = True,
    ):
        if not samplers:
            raise ValueError("need at least one sampler")
        self.loaders = [
            DataLoader(dataset, batch_size, s, drop_remainder) for s in samplers
        ]

    def set_epoch(self, epoch: int) -> None:
        for ld in self.loaders:
            ld.set_epoch(epoch)

    def __len__(self) -> int:
        return min(len(ld) for ld in self.loaders)

    def __iter__(self):
        its = [iter(ld) for ld in self.loaders]
        for _ in range(len(self)):
            parts = [next(it) for it in its]
            yield (
                np.stack([p[0] for p in parts]),
                np.stack([p[1] for p in parts]),
            )
