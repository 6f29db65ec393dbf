"""Elastic run control: supervise a multi-process job across rank death.

``tpudml.launch`` contains failures (one dead rank tears down the whole
job instead of deadlocking the survivors) and can relaunch the job whole.
This package closes the remaining gap to "multi-host reality": a
controller that treats each relaunch as a *membership event* — fresh
rendezvous (new coordinator port, so no half-dead coordinator or zombie
rank can poison the re-form), an optional shrink policy that drops the
failed rank and re-meshes the survivors, and resume from the newest
CRC-valid sharded checkpoint so the restarted job continues the same
training trajectory bit-exactly.

The sharded checkpoint format is what makes shrink possible at all:
restore reassembles full host arrays from *all* processes' shard files,
so any post-failure topology can restore any pre-failure topology's
checkpoint (``tpudml/checkpoint/sharded.py``).

``drill.py`` is the proof: a scripted failure drill (SIGKILL-grade rank
death mid-training → backoff → re-form → resume) whose final parameters
must be bit-identical to an uninterrupted run. Run it as a library
(:func:`run_drill`) or via ``python -m tpudml.elastic --drill``.
"""

from tpudml.elastic.controller import (
    ElasticController,
    ElasticResult,
    ReformRecord,
)


def __getattr__(name):
    # Lazy: ``python -m tpudml.elastic.drill`` (the per-rank child) must
    # not find the drill module pre-imported by its own package (runpy
    # warns, and the child only needs the controller-free half anyway).
    if name == "run_drill":
        from tpudml.elastic.drill import run_drill

        return run_drill
    raise AttributeError(name)


__all__ = [
    "ElasticController",
    "ElasticResult",
    "ReformRecord",
    "run_drill",
]
