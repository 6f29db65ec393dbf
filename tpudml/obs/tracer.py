"""Unified flight recorder: nested, thread-safe structured spans with
Chrome-trace-event export (SURVEY.md §5.1's "one timeline" gap).

Every telemetry silo the framework grew — the serving engine's and the
trainer's loop spans, `CommStats` collective timings, sentinel trips,
checkpoint save/restore/verify, launcher restarts — feeds one
:class:`Tracer`, which exports a single ``trace.json`` in the Chrome
trace-event format (one ``pid`` track per ``jax.process_index()``),
openable directly in Perfetto (https://ui.perfetto.dev) or
``chrome://tracing``. See docs/OBSERVABILITY.md for the span model.

One way to open a span: :func:`span` (or ``Tracer.span``, the same
code). It always enters a ``jax.profiler.TraceAnnotation`` named
``tpudml:<cat>/<name>`` whose keyword arguments are the span's counters
— inert without a profiler session, an event on the profiler trace's
host line, on the device operations' clock, when one is running — and
additionally records a :class:`Span` in the tracer when that is enabled.

Determinism contract: the export sorts events by ``(ts, -dur, tid, cat,
name)`` and serializes with sorted keys + canonical separators, so a
fixed event log produces byte-identical ``trace.json`` — the property
the serving-trace golden tests pin (events carry the engine's virtual
clock, not wall time).

A disabled tracer records no :class:`Span`: ``Tracer(enabled=False).span(...)``
returns the bare annotation (the module-level ``SPANS_ALLOCATED`` counter
lets tests assert this), so the off position costs one inert annotation
(about a microsecond) per span. That is not all that is kept with no
``Tracer`` installed: while ``ServingEngine.run`` or ``train_loop`` runs,
the spans of its loop (``serve/...``, ``train/...``) also feed that run's
pass log (:mod:`tpudml.obs.passlog`: a fixed-size row a pass, the longest
passes whole), whichever tracer is ambient.
"""

from __future__ import annotations

import json
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass
from pathlib import Path
from typing import Iterator

import jax

from tpudml.obs import passlog

TRACE_SCHEMA_VERSION = 1

# Program spans in a profiler trace are the events whose name starts with
# this (benchmarks/program_spans.py reads them back by it).
ANNOTATION_PREFIX = "tpudml:"

# Every Span ever constructed bumps this (see tests/test_obs.py's
# tracer-off A/B): the cheapest honest way to prove the disabled path
# allocates zero spans without instrumenting allocators.
SPANS_ALLOCATED = 0


@dataclass
class Span:
    """One structured event: a complete span (``ph='X'``, has ``dur_us``)
    or an instant (``ph='i'``). Timestamps are integer microseconds on
    the owning tracer's clock (wall for live tracing, the serve engine's
    virtual clock for deterministic conversions)."""

    name: str
    cat: str
    ts_us: int
    dur_us: int = 0
    ph: str = "X"
    tid: int = 0
    args: dict | None = None

    def __post_init__(self):
        global SPANS_ALLOCATED
        SPANS_ALLOCATED += 1


class _NullSpan:
    """Reusable no-op context manager — the entire disabled-tracer path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


class _RecordedSpan:
    """The enabled path of :meth:`Tracer.span`: the profiler annotation
    plus a :class:`Span` in the tracer when the region closes."""

    __slots__ = ("_tracer", "_annotation", "_name", "_cat", "_args", "_t0")

    def __init__(self, tracer, annotation, name, cat, args):
        self._tracer, self._annotation = tracer, annotation
        self._name, self._cat, self._args = name, cat, args

    def set_metadata(self, **args) -> None:
        """Counters known only once the region has run (the annotation's
        own method of the same name, so both paths take the call)."""
        self._annotation.set_metadata(**args)
        self._args = {**(self._args or {}), **args}

    def __enter__(self):
        self._annotation.__enter__()
        self._t0 = self._tracer._clock()
        return self

    def __exit__(self, *exc):
        tracer = self._tracer
        ts_us = int((self._t0 - tracer._t0) * 1e6)
        dur_us = int((tracer._clock() - self._t0) * 1e6)
        tracer._record(Span(self._name, self._cat, ts_us, dur_us, "X",
                            tracer._tid(), self._args))
        return self._annotation.__exit__(*exc)


class Tracer:
    """Thread-safe structured-span recorder.

    Usage::

        tracer = Tracer()
        with tracer.span("train_step", cat="step"):
            ts, metrics = step(ts, x, y)
        tracer.instant("sentinel_trip", cat="sentinel", args={"step": 7})
        tracer.export(run_dir / "trace.json")

    Nesting is positional (Chrome complete events nest by containment per
    ``tid``); each OS thread gets its own track, numbered densely in
    first-seen order.
    """

    def __init__(self, enabled: bool = True, clock=time.perf_counter):
        self.enabled = enabled
        self._clock = clock
        self._t0 = clock() if enabled else 0.0
        self._lock = threading.Lock()
        self.events: list[Span] = []
        self._tids: dict[int, int] = {}

    # ----------------------------------------------------------- recording

    def now_us(self) -> int:
        return int((self._clock() - self._t0) * 1e6)

    def _tid(self) -> int:
        ident = threading.get_ident()
        tid = self._tids.get(ident)
        if tid is None:
            with self._lock:
                tid = self._tids.setdefault(ident, len(self._tids))
        return tid

    def _record(self, span: Span) -> None:
        with self._lock:
            self.events.append(span)

    def span(self, name: str, cat: str = "host", args: dict | None = None):
        """Context manager around a host region: a profiler annotation
        ``tpudml:<cat>/<name>`` carrying ``args`` (ints, floats or short
        strings) always, and a complete span in this tracer when it is
        enabled. Disabled: the bare annotation, no :class:`Span`. Either
        way a span of a loop whose pass log is open on this thread feeds
        that log (:mod:`tpudml.obs.passlog`)."""
        region = jax.profiler.TraceAnnotation(
            f"{ANNOTATION_PREFIX}{cat}/{name}", **(args or {}))
        if self.enabled:
            region = _RecordedSpan(self, region, name, cat, args)
        log = passlog.active(cat)
        return region if log is None else passlog.LoggedSpan(log, region, name, args)

    def instant(self, name: str, cat: str = "host", args: dict | None = None,
                ts_us: int | None = None) -> None:
        if not self.enabled:
            return
        ts = self.now_us() if ts_us is None else int(ts_us)
        self._record(Span(name, cat, ts, 0, "i", self._tid(), args))

    def add_complete(self, name: str, cat: str, ts_us: int, dur_us: int,
                     args: dict | None = None, tid: int | None = None) -> None:
        """Record a span with explicit timestamps — the feed path for
        already-timed quantities (``CommStats.add``) and deterministic
        conversions (serve events on the virtual clock)."""
        if not self.enabled:
            return
        self._record(Span(name, cat, int(ts_us), int(dur_us), "X",
                          self._tid() if tid is None else int(tid), args))

    def add_events(self, events: list[dict]) -> None:
        """Bulk-ingest pre-built trace events (dicts with name/cat/ph/ts/
        dur/tid/args keys — the output of ``tpudml.obs.convert``)."""
        if not self.enabled:
            return
        for e in events:
            self._record(Span(
                e["name"], e.get("cat", "host"), int(e.get("ts", 0)),
                int(e.get("dur", 0)), e.get("ph", "X"),
                int(e.get("tid", 0)), e.get("args"),
            ))

    # ------------------------------------------------------------- export

    def trace_events(self) -> list[dict]:
        """Deterministically-sorted Chrome trace events (no pid yet)."""
        with self._lock:
            spans = list(self.events)
        return sorted((_event_dict(s) for s in spans), key=_sort_key)

    def chrome_trace(self, pid: int | None = None) -> dict:
        return chrome_trace_doc(self.trace_events(), pid=pid)

    def export(self, path: str | Path, pid: int | None = None) -> Path:
        """Write ``trace.json`` (Chrome trace-event JSON, schema version
        ``TRACE_SCHEMA_VERSION``); returns the path. Byte-deterministic
        for a fixed event log."""
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(dump_trace(self.chrome_trace(pid=pid)))
        return path

    def summary(self) -> dict:
        """Deterministic per-(cat, name) aggregate: count, total, and
        p50/p99 microseconds (reusing ``CommStats.percentiles`` so every
        percentile in the repo interpolates identically)."""
        from tpudml.comm.timing import CommStats

        groups: dict[tuple[str, str], CommStats] = {}
        with self._lock:
            spans = list(self.events)
        for s in spans:
            groups.setdefault((s.cat, s.name), CommStats()).add(s.dur_us * 1e-6)
        out = {}
        for (cat, name), st in sorted(groups.items()):
            pct = st.percentiles()
            out[f"{cat}/{name}"] = {
                "count": st.calls,
                "total_us": int(st.comm_time_s * 1e6),
                "p50_us": int(pct["p50_s"] * 1e6) if pct else 0,
                "p99_us": int(pct["p99_s"] * 1e6) if pct else 0,
            }
        return {"schema": TRACE_SCHEMA_VERSION, "spans": out}


def _event_dict(s: Span) -> dict:
    e = {"name": s.name, "cat": s.cat, "ph": s.ph, "ts": s.ts_us, "tid": s.tid}
    if s.ph == "X":
        e["dur"] = s.dur_us
    else:
        e["s"] = "t"  # instant scope: thread
    if s.args:
        e["args"] = s.args
    return e


def _sort_key(e: dict):
    # Parents (longer spans) sort before their children at equal ts, which
    # is what trace viewers require for proper nesting.
    return (e["ts"], -e.get("dur", 0), e["tid"], e["cat"], e["name"])


def chrome_trace_doc(events: list[dict], pid: int | None = None) -> dict:
    """Wrap sorted trace events in the Chrome trace-event document:
    metadata naming the process track (one per ``jax.process_index()``),
    then the events stamped with that pid."""
    if pid is None:
        try:
            from tpudml.core.dist import process_index

            pid = process_index()
        except Exception:
            pid = 0
    stamped = [dict(e, pid=pid) for e in events]
    meta = {
        "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
        "args": {"name": f"tpudml process {pid}"},
    }
    return {
        "displayTimeUnit": "ms",
        "metadata": {"tpudml_trace_schema": TRACE_SCHEMA_VERSION},
        "traceEvents": [meta] + stamped,
    }


def dump_trace(doc: dict) -> str:
    """Canonical serialization: sorted keys, no whitespace — the byte
    representation the golden/determinism tests pin."""
    return json.dumps(doc, sort_keys=True, separators=(",", ":")) + "\n"


def merge_chrome_traces(docs: list[dict]) -> dict:
    """Merge per-process trace documents (one per rank, distinct pids)
    into a single multi-track document — the pod-level view of a
    multi-process run. Each input must be a valid single-process export;
    two inputs claiming the same pid is an error (two ranks exported with
    the same ``process_index`` — a wiring bug worth failing loudly on).
    Deterministic: metadata tracks sorted by pid, then events in the same
    order :meth:`Tracer.trace_events` uses, pid as the leading key."""
    metas: dict[int, dict] = {}
    events: list[dict] = []
    for doc in docs:
        validate_chrome_trace(doc)
        for e in doc["traceEvents"]:
            if e["ph"] == "M":
                if e["pid"] in metas:
                    raise ValueError(
                        f"duplicate pid {e['pid']} across trace documents"
                    )
                metas[e["pid"]] = e
            else:
                events.append(e)
    events.sort(key=lambda e: (e["pid"],) + _sort_key(e))
    return {
        "displayTimeUnit": "ms",
        "metadata": {"tpudml_trace_schema": TRACE_SCHEMA_VERSION},
        "traceEvents": [metas[p] for p in sorted(metas)] + events,
    }


def validate_chrome_trace(doc: dict) -> None:
    """Schema check for an exported trace document: raises ValueError on
    the first violation of the Chrome trace-event contract the tests (and
    Perfetto) rely on."""
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        raise ValueError("trace document must be a dict with 'traceEvents'")
    if doc.get("metadata", {}).get("tpudml_trace_schema") != TRACE_SCHEMA_VERSION:
        raise ValueError("missing/unknown tpudml_trace_schema version")
    for i, e in enumerate(doc["traceEvents"]):
        for key in ("name", "ph", "pid", "tid"):
            if key not in e:
                raise ValueError(f"event {i} missing {key!r}: {e}")
        if e["ph"] == "X":
            if not isinstance(e.get("ts"), int) or not isinstance(e.get("dur"), int):
                raise ValueError(f"event {i}: complete events need int ts/dur")
        elif e["ph"] == "i":
            if not isinstance(e.get("ts"), int):
                raise ValueError(f"event {i}: instant events need int ts")
        elif e["ph"] != "M":
            raise ValueError(f"event {i}: unknown phase {e['ph']!r}")


# ------------------------------------------------------- ambient tracer
#
# Cross-cutting layers (checkpoint store, launcher, sentinel hook) emit
# into the ambient tracer rather than threading a tracer argument through
# every signature. Defaults to a disabled tracer, so un-instrumented runs
# pay one truthiness check and allocate nothing.

NULL_TRACER = Tracer(enabled=False)
_ambient: Tracer = NULL_TRACER
_ambient_lock = threading.Lock()


def get_tracer() -> Tracer:
    return _ambient


def set_tracer(tracer: Tracer | None) -> Tracer:
    """Install ``tracer`` as the ambient tracer (None → disabled);
    returns the previous one so callers can restore it."""
    global _ambient
    with _ambient_lock:
        prev = _ambient
        _ambient = tracer if tracer is not None else NULL_TRACER
    return prev


def span(name: str, cat: str = "host", **args):
    """Open a span in the ambient tracer (:meth:`Tracer.span`); keyword
    arguments are its counters. A request's spans all carry ``rid``, a
    loop pass's all carry ``step``."""
    return _ambient.span(name, cat, args=args or None)


@contextmanager
def use_tracer(tracer: Tracer | None) -> Iterator[Tracer]:
    """Scoped :func:`set_tracer` — the task entrypoints' idiom."""
    prev = set_tracer(tracer)
    try:
        yield get_tracer()
    finally:
        set_tracer(prev)
