"""Device prefetch: overlap host→device transfer with device compute.

The reference's DataLoader hands batches to `.cuda()` synchronously inside
the hot loop (codes/task1/pytorch/model.py:44-49). On TPU the idiomatic
shape is a small device-side queue (the MindSpore notebook's
``dataset_sink_mode`` is the same idea, SURVEY.md §3.5): while step N
computes, batch N+1's host→device copy is already in flight, so input
transfer disappears from the step's critical path.
"""

from __future__ import annotations

import collections
from typing import Iterable, Iterator

import jax

from tpudml.obs.tracer import span


def prefetch_to_device(
    iterator: Iterable,
    size: int = 2,
    sharding=None,
) -> Iterator:
    """Yield items from ``iterator`` with up to ``size`` batches resident
    on device ahead of the consumer.

    Each item (any pytree of arrays) is ``jax.device_put`` — with
    ``sharding`` when given (e.g. a batch NamedSharding for DP) — as soon
    as a queue slot frees, so the copy overlaps the previous steps'
    compute. ``size=2`` is the classic double buffer; larger sizes only
    help when batch arrival jitters.
    """
    if size < 1:
        # Validate eagerly (this is a plain function returning a generator,
        # so the error fires at call time, not at first iteration).
        raise ValueError(f"prefetch size must be >= 1, got {size}")
    return _prefetch_gen(iterator, size, sharding)


def _prefetch_gen(iterator, size, sharding):
    queue: collections.deque = collections.deque()
    it = iter(iterator)

    def enqueue(n: int) -> None:
        for _ in range(n):
            try:
                item = next(it)
            except StopIteration:
                return
            n_bytes = sum(getattr(leaf, "nbytes", 0) for leaf in jax.tree.leaves(item))
            with span("device_put", "data", n_bytes=n_bytes):
                queue.append(jax.device_put(item, sharding))

    enqueue(size)
    while queue:
        yield queue.popleft()
        enqueue(1)
