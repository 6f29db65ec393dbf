"""Program spans: the host spans the program itself opens
(`tpudml/obs/tracer.py:span`), read back from the profiler's trace.

The program's spans go out as `jax.profiler.TraceAnnotation`s named
`tpudml:<cat>/<name>`, so they land in the same `.xplane.pb` as the device
operations, on its clock; their keyword arguments (`step`, `rid`, `active`,
`rows`...) come back as the event's stats. Two stages, like `tracing.py`, so
that the arithmetic can be tested on a small recorded list:

1. ``load(trace_dir)`` reads the `tpudml:` events and the benchmark's own
   `bench:trace_window` span into plain lists (seconds on the trace's clock).
2. Pure functions over those lists: the spans that lie wholly inside the
   window, a span's children, and the per-pass arithmetic the
   `layer_metrics/` readers report.

A program without such spans (an earlier commit) gives an empty list, and
every reader then returns None.

By hand: `python3 benchmarks/program_spans.py <trace_dir> [out.json]`.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path.insert(0, str(ROOT))

from benchmarks.tracing import WINDOW_SPAN  # noqa: E402

PROGRAM_PREFIX = "tpudml:"

# ---------------------------------------------------------------------- load

_loaded: dict = {}  # (path, mtime) -> what load() read: one parse per trace


def load(trace_dir: str) -> dict | None:
    """``{"window": [start_s, end_s] | None, "spans": [[name, start_s, dur_s,
    {stat: value}]...]}`` with names as `serve/iter` (prefix taken off), in
    order of start; None when there is no trace under ``trace_dir``."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return None
    key = (paths[-1], os.path.getmtime(paths[-1]))
    if key not in _loaded:
        import jax

        data = jax.profiler.ProfileData.from_file(paths[-1])
        window, spans = None, []
        for plane in data.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PROGRAM_PREFIX):
                        spans.append([e.name[len(PROGRAM_PREFIX):], e.start_ns / 1e9,
                                      e.duration_ns / 1e9, dict(e.stats)])
                    elif e.name == WINDOW_SPAN:
                        window = [e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9]
        _loaded.clear()
        _loaded[key] = {"window": window, "spans": sorted(spans, key=lambda s: s[1])}
    return _loaded[key]


def of_cell(ctx: dict) -> list | None:
    """The program's spans inside the traced window of this run's cell, from
    the directory `run.py` traces into; None when there is no trace or no
    span."""
    loaded = load(str(ROOT / "benchmarks" / ".trace" / ctx["cell"].name))
    return (loaded and inside(loaded)) or None


# ---------------------------------------------------------------- arithmetic


def inside(loaded: dict) -> list:
    """The spans that lie wholly inside the traced window (all of them when
    the trace has no window span): a span cut by the window's edge, or one
    whose start the profiler missed, would read short."""
    if loaded["window"] is None:
        return list(loaded["spans"])
    lo, hi = loaded["window"]
    return [s for s in loaded["spans"] if s[1] >= lo and s[1] + s[2] <= hi]


def named(spans: list, name: str) -> list:
    return [s for s in spans if s[0] == name]


def children(spans: list, parent: list, name: str) -> list:
    """Spans called ``name`` inside ``parent``'s interval (the program's
    loops run on one thread, so containment in time is nesting)."""
    lo, hi = parent[1], parent[1] + parent[2]
    return [s for s in spans if s[0] == name and s is not parent
            and s[1] >= lo and s[1] + s[2] <= hi]


def stat(spans: list, key: str) -> list:
    """The values of one counter over spans that carry it."""
    return [s[3][key] for s in spans if key in s[3]]


def decode_passes(spans: list) -> list:
    """[(pass, its fetch)] for every `serve/iter` that ran a decode step and
    admitted nobody: the steady pass of the serving loop."""
    out = []
    for it in named(spans, "serve/iter"):
        fetch = children(spans, it, "serve/fetch")
        if fetch and not children(spans, it, "serve/admit"):
            out.append((it, fetch[0]))
    return out


def loop_host_s(spans: list) -> list:
    """Per steady pass: its duration less its `serve/fetch` (where the host
    waits for the device) — the host's own work in a pass."""
    return [it[2] - fetch[2] for it, fetch in decode_passes(spans)]


def prefill_stall_s(spans: list, programs: dict, starts: dict, pattern: str) -> list:
    """Per `serve/iter` that has a successor in the window: the device
    seconds of the programs matching ``pattern`` that start between this
    pass's start and the next one's — what admission adds to one decode gap.
    ``programs``/``starts``: `tracing.Summary.programs`/`.program_starts`."""
    prefills = sorted((s, d) for name, ds in programs.items()
                      if re.search(pattern, name)
                      for s, d in zip(starts[name], ds))
    passes = named(spans, "serve/iter")
    return [sum(d for s, d in prefills if a[1] <= s < b[1])
            for a, b in zip(passes, passes[1:])]


if __name__ == "__main__":
    found = load(sys.argv[1])
    text = json.dumps(found)
    if len(sys.argv) > 2:
        with open(sys.argv[2], "w") as f:
            f.write(text)
    kept = inside(found) if found else []
    names = sorted({s[0] for s in kept})
    print(json.dumps({"window": found and found["window"],
                      "spans": len(found["spans"]) if found else 0, "inside": len(kept),
                      "by_name": {n: len(named(kept, n)) for n in names}}))
