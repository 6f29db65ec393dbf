"""Pass log: what a loop's passes cost on the host clock, kept in every run.

A profiler trace sees 2-4 s of a run and an enabled :class:`Tracer` grows
with it, so a stall that comes once in tens of runs was never on record.
The pass log is: on in every ``ServingEngine.run`` and ``train_loop``
(both open one with :func:`pass_log`), fixed in memory, and fed by the span
sites that exist — while a loop's log is open on its thread,
``Tracer.span`` hands that loop's spans (``cat`` = the log's ``kind``) to it
on their way out. No second set of timing sites: a pass is one
``<kind>/iter`` span, its phases are the child spans' names.

When a pass closes the log keeps one fixed-size row in a ring of
``CAPACITY`` rows (65 bytes a serving row: 4.3 MB, allocated and written
once when the log is made; a run longer than the ring keeps its last
``CAPACITY`` passes):

- ``start_s`` on the loop's clock (the engine's ``now()``: it lines up with
  ``RequestStats`` times), ``ms`` on the host clock, ``step``, ``cls``;
- host milliseconds by child span name, ``<name>_ms`` (serving: ``arrive``,
  ``admit``, ``dispatch``, ``fetch``, ``commit``, ``idle``; training:
  ``next_batch``, ``step``, ``log_sync``, ``hooks``);
- serving: ``queue`` and ``active`` (counters of ``serve/iter``), ``admits``
  and prefill ``chunks`` launched in the pass (of its ``serve/admit`` spans);
- ``hiccup_ms``: the worst lateness of the log's heartbeat inside the pass.

Classes. A serving pass is ``steady`` when it fetched a step and neither it
nor the pass before it admitted anybody (with one step in flight a pass's
prefill chunks are waited for in the NEXT pass's fetch), ``idle`` when it
only slept, ``admitting`` otherwise. A training pass is ``step`` when it
dispatched one, else ``other`` (a fast-forward, the exhausted loader).

Of each class the ``KEEP`` longest passes are kept whole (:meth:`PassLog.summary`
``["slow"]``), with what tells a frozen host from a live one that waits:

- ``cpu_ms`` of the loop's thread, its voluntary / involuntary context
  switches and major page faults (``getrusage(RUSAGE_THREAD)``, one call a
  pass; deltas run from the close of the pass before);
- ``gc_ms`` the collector ran and compilations that ended (``compiles``,
  ``compile_ms``) since the pass before closed;
- ``hiccup_ms``: a heartbeat thread the log owns wakes every 10 ms and keeps
  its worst overshoot. A 2.8 s ``fetch`` with the heartbeat 2.8 s late is a
  process that did not run (steal, a throttled cgroup, swap) — or, where
  ``cpu_ms`` is near ``ms``, Python that held the interpreter lock; with the
  heartbeat on time the host was alive and waited for the runtime or the
  device, and the next step is a longer device trace, not host code.

When the loop ends, each kept pass of the loop's regular class (``steady``,
``step``) longer than 3 x that class's median AND 50 ms goes out as one
WARNING on logger ``tpudml.obs`` (stderr unless configured), the record as
JSON. The last log of each kind stays reachable: :func:`last_pass_log`.
"""

from __future__ import annotations

import gc
import heapq
import json
import logging
import resource
import threading
import time
from contextlib import contextmanager

import numpy as np

CAPACITY = 65536
KEEP = 8
HEARTBEAT_S = 0.010
SLOW_FACTOR, SLOW_MS = 3.0, 50.0

_getrusage = resource.getrusage
_RUSAGE_WHO = getattr(resource, "RUSAGE_THREAD", resource.RUSAGE_SELF)
_COMPILED = "/jax/core/compile/backend_compile_duration"
logger = logging.getLogger("tpudml.obs")


def _serve_class(log: "PassLog", ms: list) -> int:
    admits = log._admits
    before, log._admitted_before = log._admitted_before, admits > 0
    fetched = ms[log._phase["fetch"]] > 0
    if fetched and not admits and not before:
        return 0
    only_slept = not (fetched or admits or ms[log._phase["dispatch"]] > 0)
    return 2 if only_slept and ms[log._phase["idle"]] > 0 else 1


def _train_class(log: "PassLog", ms: list) -> int:
    return 0 if ms[log._phase["step"]] > 0 else 1


# kind -> (child span names, the counters of ``<kind>/iter`` a row keeps,
# classes (the regular one first), classifier). A kind with an ``admit`` child
# also keeps how many closed in the pass and the sum of their ``chunks``.
_KINDS = {
    "serve": (("arrive", "admit", "dispatch", "fetch", "commit", "idle"),
              ("queue", "active"), ("steady", "admitting", "idle"), _serve_class),
    "train": (("next_batch", "step", "log_sync", "hooks"), (),
              ("step", "other"), _train_class),
}


class _Heartbeat(threading.Thread):
    """Wakes every ``interval`` seconds and keeps how late its worst
    wake-up was since the loop last asked."""

    def __init__(self, interval: float):
        super().__init__(name="tpudml-pass-log-heartbeat", daemon=True)
        self.interval = interval
        self._late = 0.0
        self._due = time.perf_counter() + interval
        self._lock = threading.Lock()
        self._done = threading.Event()

    def run(self) -> None:
        while True:
            self._due = time.perf_counter() + self.interval
            if self._done.wait(self.interval):
                return
            late = time.perf_counter() - self._due
            with self._lock:
                self._late = max(self._late, late)

    def take(self, now: float) -> float:
        """Worst lateness in seconds since the last call. A wake-up that is
        overdue right now counts: after a freeze the loop may close its pass
        before this thread has run again."""
        with self._lock:
            late, self._late = max(self._late, now - self._due, 0.0), 0.0
        return late

    def stop(self) -> None:
        self._done.set()
        self.join(timeout=1.0)


class PassLog:
    """One loop run's passes (module docstring). ``clock`` gives a pass's
    ``start_s``; the loop may set it once its own clock exists."""

    def __init__(self, kind: str, clock=None, capacity: int = CAPACITY,
                 heartbeat_s: float = HEARTBEAT_S):
        self.kind = kind
        self.phases, self._of_iter, self.classes, self._classify = _KINDS[kind]
        self._phase = {name: i for i, name in enumerate(self.phases)}
        self._admitting = "admit" in self._phase
        self.counters = self._of_iter + (("admits", "chunks") if self._admitting else ())
        self.capacity = capacity
        self.run = 0  # which log of its kind in this process (pass_log sets it)
        opened = time.perf_counter()
        self.clock = clock or (lambda: time.perf_counter() - opened)
        self._rows = np.zeros(capacity, np.dtype(
            [("start_s", "f8"), ("ms", "f4"), ("step", "i8"), ("cls", "i1")]
            + [(c, "i4") for c in self.counters] + [("hiccup_ms", "f4")]
            + [(f"{p}_ms", "f4") for p in self.phases]))
        # Written once here: zeroed pages are mapped on first touch, which
        # would otherwise be a page fault every ~60 passes inside the loop.
        self._rows.fill(0)
        self.n = 0  # passes closed
        self._count = [0] * len(self.classes)
        self._kept: list[list] = [[] for _ in self.classes]  # heaps of (ms, n, record)
        self._heart = _Heartbeat(heartbeat_s)
        self._admitted_before = False
        self._gc_t0 = None
        self._gc_s = self._compile_s = 0.0
        self._compiles = 0
        self._ru = None
        self.begin()

    # ------------------------------------------------------ fed by the spans

    def begin(self) -> None:
        """A pass opens (its ``<kind>/iter`` span was entered)."""
        self._start_s = self.clock()
        self._ms = [0.0] * len(self.phases)
        self._admits = self._chunks = 0

    def end(self, name: str, t0: float, t1: float, args: dict | None) -> None:
        """A span of this loop closed, ``t0`` to ``t1`` on ``perf_counter``:
        a child adds to its phase, ``iter`` closes the pass."""
        if name == "iter":
            self._close(t0, t1, args or {})
            return
        i = self._phase.get(name)
        if i is not None:
            self._ms[i] += (t1 - t0) * 1e3
            if name == "admit":
                self._admits += 1
                self._chunks += args.get("chunks", 0)

    def _close(self, t0: float, t1: float, args: dict) -> None:
        ru = _getrusage(_RUSAGE_WHO)
        ms = (t1 - t0) * 1e3
        hiccup_ms = self._heart.take(t1) * 1e3
        cls = self._classify(self, self._ms)
        counters = [args.get(c, 0) for c in self._of_iter]
        if self._admitting:
            counters += (self._admits, self._chunks)
        step = args.get("step", -1)
        self._rows[self.n % self.capacity] = (
            self._start_s, ms, step, cls, *counters, hiccup_ms, *self._ms)
        self.n += 1
        self._count[cls] += 1
        kept = self._kept[cls]
        if len(kept) < KEEP or ms > kept[0][0]:
            was = self._ru
            record = {
                "class": self.classes[cls], "start_s": self._start_s, "ms": ms, "step": step,
                **dict(zip(self.counters, counters)),
                "phases_ms": dict(zip(self.phases, self._ms)),
                "hiccup_ms": hiccup_ms,
                "cpu_ms": 1e3 * (ru.ru_utime + ru.ru_stime - was.ru_utime - was.ru_stime),
                "vol_switches": ru.ru_nvcsw - was.ru_nvcsw,
                "invol_switches": ru.ru_nivcsw - was.ru_nivcsw,
                "major_faults": ru.ru_majflt - was.ru_majflt,
                "gc_ms": 1e3 * self._gc_s, "compiles": self._compiles,
                "compile_ms": 1e3 * self._compile_s,
            }
            (heapq.heappush if len(kept) < KEEP else heapq.heapreplace)(
                kept, (ms, self.n, record))
        self._ru = ru
        self._gc_s = self._compile_s = 0.0
        self._compiles = 0

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self._gc_s += time.perf_counter() - self._gc_t0
            self._gc_t0 = None

    def _on_compile(self, seconds: float) -> None:
        self._compiles += 1
        self._compile_s += seconds

    def start(self) -> None:
        """Start the heartbeat and the listeners (``pass_log`` does)."""
        self._ru = _getrusage(_RUSAGE_WHO)
        gc.callbacks.append(self._on_gc)
        self._heart.start()

    def stop(self) -> None:
        self._heart.stop()
        gc.callbacks.remove(self._on_gc)

    # ---------------------------------------------------------------- read

    def rows(self) -> np.ndarray:
        """The kept rows, oldest first (a structured array: the fields of
        the module docstring; ``cls`` indexes ``self.classes``)."""
        if self.n <= self.capacity:
            return self._rows[:self.n].copy()
        at = self.n % self.capacity
        return np.concatenate([self._rows[at:], self._rows[:at]])

    def slow(self, cls: str) -> list[dict]:
        """The kept longest passes of a class, longest first."""
        kept = self._kept[self.classes.index(cls)]
        return [record for _, _, record in sorted(kept, key=lambda k: -k[0])]

    def summary(self) -> dict:
        """Per class: passes (whole run), p50 / p99 (rows in the ring) and
        max in ms; the kept slow passes."""
        rows = self.rows()
        slow = {name: self.slow(name) for name in self.classes}
        classes = {}
        for i, name in enumerate(self.classes):
            ms = rows["ms"][rows["cls"] == i]
            classes[name] = {
                "passes": self._count[i],
                "p50_ms": float(np.percentile(ms, 50)) if ms.size else None,
                "p99_ms": float(np.percentile(ms, 99)) if ms.size else None,
                "max_ms": slow[name][0]["ms"] if slow[name] else None,
            }
        return {"kind": self.kind, "run": self.run, "passes": self.n,
                "capacity": self.capacity, "classes": classes, "slow": slow}

    def warn_slow(self) -> int:
        """One WARNING a kept pass of the regular class that is longer than
        ``SLOW_FACTOR`` x the class's median and ``SLOW_MS``; returns how many."""
        kept = self.slow(self.classes[0])
        if not kept:
            return 0
        ms = self._rows["ms"][:min(self.n, self.capacity)]
        median = float(np.median(ms[self._rows["cls"][:len(ms)] == 0]))
        slow = [r for r in kept if r["ms"] > SLOW_MS and r["ms"] > SLOW_FACTOR * median]
        for record in slow:
            logger.warning("slow %s pass (run %d, median %.3f ms): %s", self.kind,
                           self.run, median, json.dumps(record))
        return len(slow)


# ------------------------------------------------------------ open and last
#
# The span sites find their loop's log here, by (kind, thread): two engines
# on two threads keep two logs, and a loader thread's spans reach neither.

_open: dict[tuple[str, int], PassLog] = {}
_last: dict[str, PassLog] = {}
_runs: dict[str, int] = {}
_listening = False


def _on_jax_duration(event: str, seconds: float, **_) -> None:
    if event == _COMPILED:
        for log in list(_open.values()):
            log._on_compile(seconds)


def active(kind: str) -> PassLog | None:
    """The log a span of category ``kind`` on this thread feeds, if any."""
    if not _open:
        return None
    return _open.get((kind, threading.get_ident()))


def last_pass_log(kind: str) -> PassLog | None:
    """The log of the last ``kind`` loop ("serve", "train") that ended in
    this process: for a reader that gets no report."""
    return _last.get(kind)


@contextmanager
def pass_log(kind: str, clock=None):
    """Keep a pass log while a loop of ``kind`` runs on this thread."""
    global _listening
    if not _listening:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
        _listening = True
    log = PassLog(kind, clock)
    _runs[kind] = log.run = _runs.get(kind, 0) + 1
    key = (kind, threading.get_ident())
    outer = _open.get(key)
    _open[key] = log
    log.start()
    try:
        yield log
    finally:
        log.stop()
        if outer is None:
            del _open[key]
        else:
            _open[key] = outer
        _last[kind] = log
        log.warn_slow()


class LoggedSpan:
    """A span on its way to a pass log: times the region on the host clock
    around the span it wraps (the annotation, or the tracer's recorded
    span) and hands the log its name, interval and counters at the close."""

    __slots__ = ("_log", "_inner", "_name", "_args", "_t0")

    def __init__(self, log: PassLog, inner, name: str, args: dict | None):
        self._log, self._inner, self._name, self._args = log, inner, name, args

    def set_metadata(self, **args) -> None:
        self._inner.set_metadata(**args)
        self._args = {**(self._args or {}), **args}

    def __enter__(self):
        self._inner.__enter__()
        if self._name == "iter":
            self._log.begin()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._log.end(self._name, self._t0, time.perf_counter(), self._args)
        return self._inner.__exit__(*exc)
