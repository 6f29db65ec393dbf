"""Device trace: capture (jax.profiler) and the reduction from a trace to the
numbers the per-layer metrics read.

Two stages, so that the reduction can be tested on a small recorded trace:

1. ``load_events(trace_dir)`` reads the profiler's ``.xplane.pb`` into plain
   lists: for each device its programs (the "XLA Modules" line) and its
   operations ("XLA Ops"), and the host spans the benchmark itself wrote
   (``jax.profiler.TraceAnnotation`` names starting with ``bench:``). Times are
   seconds on the trace's own clock.
2. ``reduce(events)`` turns those lists into a ``Summary``: busy and window
   seconds, the durations of each program, idle gaps by what surrounded them,
   and the operations that took most time.
"""

from __future__ import annotations

import glob
import os
import re
import shutil
import statistics
from dataclasses import dataclass, field

SPAN_PREFIX = "bench:"
WINDOW_SPAN = SPAN_PREFIX + "trace_window"
_ID_SUFFIX = re.compile(r"\(\d+\)$")
_OP_NUMBER = re.compile(r"[.\d]+$")


def _short(name: str) -> str:
    """`%fusion.123 = bf16[...] fusion(...)` -> `fusion.123`, and
    `jit_step(6401575)` -> `jit_step`: the name without the HLO text and
    without the program's id."""
    return _ID_SUFFIX.sub("", name.split(" = ")[0].lstrip("%"))


def op_family(name: str) -> str:
    """`fusion.123` -> `fusion`: what an operation is, without its number."""
    return _OP_NUMBER.sub("", _short(name)) or name


# ------------------------------------------------------------------ capture


class TraceWindow:
    """Start and stop one profiler trace into a fixed directory. The Python
    tracer is off (it slows the host loop that is being measured); host
    spans come from the benchmark's own TraceAnnotations."""

    def __init__(self, trace_dir: str):
        self.trace_dir = trace_dir
        self.active = False
        self.done = False
        self._span = None

    def start(self) -> None:
        import jax

        if self.active or self.done:
            return
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self.active = True
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()

    def stop(self) -> None:
        import jax

        if not self.active:
            return
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active, self.done = False, True


def span(name: str):
    """A host span of the benchmark's own, on the device trace's clock."""
    import jax

    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


# --------------------------------------------------------------------- load


def load_events(trace_dir: str) -> dict:
    """``{"devices": {plane: {"modules": [[name, start_s, dur_s]...],
    "ops": [...]}}, "host": [[name, start_s, dur_s]...]}``."""
    import jax

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {}
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(line.name)
                if key:
                    lines[key] = [[_short(e.name), e.start_ns / 1e9,
                                   e.duration_ns / 1e9] for e in line.events]
            if lines.get("ops"):
                devices[plane.name] = {"modules": lines.get("modules", []),
                                       "ops": lines["ops"]}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns / 1e9, e.duration_ns / 1e9]
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "host": host}


# ---------------------------------------------------------------- intervals


def union(intervals):
    """Merged, sorted list of (start, end) from any list of (start, end)."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """Parts of the merged list ``a`` that the merged list ``b`` does not cover."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


# ------------------------------------------------------------------- reduce


@dataclass
class Summary:
    window_s: float
    busy_s: float                      # mean over devices
    programs: dict = field(default_factory=dict)   # name -> [durations]
    program_starts: dict = field(default_factory=dict)  # first device only
    device_ops: list = field(default_factory=list)      # [[name, seconds]]
    idle_gaps: list = field(default_factory=list)       # [[what, seconds]]
    n_devices: int = 0

    def median_program_s(self, pattern: str):
        """Median duration of the programs whose name matches ``pattern``
        (a regular expression searched in the name), or None."""
        durations = [d for name, ds in self.programs.items()
                     if re.search(pattern, name) for d in ds]
        return statistics.median(durations) if durations else None

    def count_programs(self, pattern: str) -> int:
        return sum(len(ds) for name, ds in self.programs.items()
                   if re.search(pattern, name))


def _window(events: dict):
    """The traced window: the benchmark's own span around it (host and device
    events share the trace's clock), else the extent of the device's events."""
    for name, s, d in events["host"]:
        if name == WINDOW_SPAN:
            return s, s + d
    ops = [op for dev in events["devices"].values() for op in dev["ops"]]
    if not ops:
        raise ValueError("the trace holds no device operation")
    return min(s for _, s, _ in ops), max(s + d for _, s, d in ops)


def reduce(events: dict, top: int = 10) -> Summary:
    planes = sorted(events["devices"])
    if not planes:
        raise ValueError("the trace holds no device operation")
    lo, hi = _window(events)
    busy = [total(union(clip([(s, s + d) for _, s, d in events["devices"][plane]["ops"]],
                             lo, hi))) for plane in planes]
    first = events["devices"][planes[0]]
    programs, starts = {}, {}
    for name, s, d in first["modules"]:
        if s >= lo and s + d <= hi:
            programs.setdefault(name, []).append(d)
            starts.setdefault(name, []).append(s)
    by_op = {}
    for name, s, d in first["ops"]:
        family = op_family(name)
        by_op[family] = by_op.get(family, 0.0) + max(0.0, min(s + d, hi) - max(s, lo))
    device_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = [g for g in subtract([(lo, hi)], union(
        clip([(s, s + d) for _, s, d in first["ops"]], lo, hi)))
        if g[1] - g[0] > 1e-9]  # under a nanosecond: rounding, not a gap
    return Summary(
        window_s=hi - lo, busy_s=sum(busy) / len(busy),
        programs=programs, program_starts=starts,
        device_ops=[[n, t] for n, t in device_ops],
        idle_gaps=_attribute(gaps, first["modules"], events["host"], top),
        n_devices=len(planes))


def _attribute(gaps, modules, host, top: int):
    """Idle seconds by what the host was doing: the benchmark's own span that
    covers the middle of the gap (the innermost, i.e. shortest, one), else
    the programs before and after it."""
    spans = [(s, s + d, n) for n, s, d in host if n != WINDOW_SPAN]
    mods = sorted((s, s + d, n) for n, s, d in modules)
    starts = [m[0] for m in mods]
    import bisect

    by_what = {}
    for s, e in gaps:
        mid = (s + e) / 2
        covering = [(b - a, n) for a, b, n in spans if a <= mid <= b]
        if covering:
            what = min(covering)[1]
        else:
            i = bisect.bisect_right(starts, mid)
            before = mods[i - 1][2] if i > 0 else "trace start"
            after = mods[i][2] if i < len(mods) else "trace end"
            what = f"{before} -> {after}"
        by_what[what] = by_what.get(what, 0.0) + (e - s)
    return [[n, t] for n, t in sorted(by_what.items(), key=lambda kv: -kv[1])[:top]]
