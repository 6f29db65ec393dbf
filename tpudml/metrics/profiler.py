"""Tracing / profiling (SURVEY.md §5.1).

The reference measures performance with inline ``time.time()`` spans and
recommends ``torch.cuda.Event`` timing (codes/task2/model-mp.py:48-79,
sections/task2.tex:69-80); it has no profiler. Here :func:`trace`
captures an XLA/TPU profile via ``jax.profiler`` into the run directory —
open in TensorBoard (or Perfetto) to see per-op device time, fusion
boundaries, and collective overlap; the TPU-accurate answer to "where did
the step time go". The program's own host spans (``tpudml.obs.tracer.span``)
land in the same trace, on its clock, as ``tpudml:<cat>/<name>`` events.
"""

from __future__ import annotations

from contextlib import contextmanager
from pathlib import Path
from typing import Iterator

import jax


@contextmanager
def trace(log_dir: str | Path, enabled: bool = True) -> Iterator[None]:
    """Capture a jax.profiler trace under ``log_dir`` (no-op when
    ``enabled`` is False, so call sites can pass a config flag through)."""
    if not enabled:
        yield
        return
    with jax.profiler.trace(str(log_dir)):
        yield


def annotate(name: str):
    """Label a host-side region so it shows up on the trace timeline
    (thin alias of ``jax.profiler.TraceAnnotation``)."""
    return jax.profiler.TraceAnnotation(name)
