"""tpudml.obs — the unified observability layer (docs/OBSERVABILITY.md).

- :mod:`tpudml.obs.tracer`    — structured spans → Perfetto ``trace.json``.
- :mod:`tpudml.obs.passlog`   — the pass log: what every run of the serving
  and training loops keeps of its passes with no ``Tracer`` installed and no
  profiler session (a fixed-size row a pass, the slow ones whole);
  ``last_pass_log("serve" | "train")`` after the run.
- :mod:`tpudml.obs.stepstats` — in-graph :class:`StepStats` telemetry.
- :mod:`tpudml.obs.convert`   — serve event log → trace spans (pure).
- :mod:`tpudml.obs.drift`     — static-vs-measured drift monitor
  (``python -m tpudml.obs --check-drift``). Imported lazily: it pulls in
  the parallel engines, which themselves import this package.
"""

from tpudml.obs.convert import serve_trace_events, write_serve_trace
from tpudml.obs.passlog import PassLog, last_pass_log, pass_log
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
    "PassLog",
    "TRACE_SCHEMA_VERSION",
    "Span",
    "StepStats",
    "Tracer",
    "chrome_trace_doc",
    "dump_trace",
    "get_tracer",
    "last_pass_log",
    "make_step_stats",
    "merge_chrome_traces",
    "pass_log",
    "serve_trace_events",
    "set_tracer",
    "span",
    "use_tracer",
    "validate_chrome_trace",
    "write_serve_trace",
]
