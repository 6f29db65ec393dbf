"""tpudml.obs — the unified observability layer (docs/OBSERVABILITY.md).

- :mod:`tpudml.obs.tracer`    — structured spans → Perfetto ``trace.json``.
- :mod:`tpudml.obs.stepstats` — in-graph :class:`StepStats` telemetry.
- :mod:`tpudml.obs.convert`   — serve event log → trace spans (pure).
- :mod:`tpudml.obs.drift`     — static-vs-measured drift monitor
  (``python -m tpudml.obs --check-drift``). Imported lazily: it pulls in
  the parallel engines, which themselves import this package.
"""

from tpudml.obs.convert import serve_trace_events, write_serve_trace
from tpudml.obs.stepstats import StepStats, make_step_stats
from tpudml.obs.tracer import (
    NULL_SPAN,
    NULL_TRACER,
    TRACE_SCHEMA_VERSION,
    Span,
    Tracer,
    chrome_trace_doc,
    dump_trace,
    get_tracer,
    merge_chrome_traces,
    set_tracer,
    span,
    use_tracer,
    validate_chrome_trace,
)

__all__ = [
    "NULL_SPAN",
    "NULL_TRACER",
    "TRACE_SCHEMA_VERSION",
    "Span",
    "StepStats",
    "Tracer",
    "chrome_trace_doc",
    "dump_trace",
    "get_tracer",
    "make_step_stats",
    "merge_chrome_traces",
    "serve_trace_events",
    "set_tracer",
    "span",
    "use_tracer",
    "validate_chrome_trace",
    "write_serve_trace",
]
