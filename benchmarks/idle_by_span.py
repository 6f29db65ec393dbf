"""The device's idle gaps, named by what the PROGRAM was doing in them.

`tracing.reduce` names an idle gap by the benchmark's own `bench:` span over
it, else by the device programs on either side (`breakdown.idle_gaps`:
`jit_step -> jit_step`). The program's spans (`tpudml:<cat>/<name>`,
`program_spans.load`) lie in the same `.xplane.pb` on the same clock; this
lays them over the gaps. Two stages, like its neighbours:

1. ``load(trace_dir)``: the traced window, the gaps in it (no operation on the
   first device), that device's programs and the program's spans, as plain
   lists; None without a trace.
2. Pure functions: ``by_span`` gives each gap the innermost program span that
   covers its middle, else the neighbouring programs; ``uncovered`` is what is
   left of the gaps outside every span of one name (`serve/idle`: an engine
   that waits for the next arrival is idle by the traffic's wish, the rest of
   the idle time the host has to answer for); ``table`` sums by name.

By hand: `python3 benchmarks/idle_by_span.py <trace_dir>` prints the gaps by
span, longest first, then each gap over a millisecond.
"""

from __future__ import annotations

import bisect
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path.insert(0, str(ROOT))

from benchmarks import program_spans, tracing  # noqa: E402


def load(trace_dir: str) -> dict | None:
    """``{"window": [lo, hi], "gaps": [[start_s, end_s]...], "modules":
    [[name, start_s, dur_s]...], "spans": [[name, start_s, dur_s, stats]...]}``."""
    spans = program_spans.load(trace_dir)
    if spans is None:
        return None
    events = tracing.load_events(trace_dir)
    planes = sorted(events["devices"])
    if not planes:
        return None
    lo, hi = tracing._window(events)
    first = events["devices"][planes[0]]
    busy = tracing.union(tracing.clip([(s, s + d) for _, s, d in first["ops"]], lo, hi))
    gaps = [list(g) for g in tracing.subtract([(lo, hi)], busy) if g[1] - g[0] > 1e-9]
    return {"window": [lo, hi], "gaps": gaps, "modules": first["modules"],
            "spans": spans["spans"]}


def of_cell(ctx: dict) -> dict | None:
    return load(str(ROOT / "benchmarks" / ".trace" / ctx["cell"].name))


# ---------------------------------------------------------------- arithmetic


def by_span(gaps: list, spans: list, modules: list, floor_s: float = 1e-6) -> list:
    """``[[what, start_s, seconds]...]``, one entry a gap, longest first:
    ``what`` is the innermost (shortest) program span that covers the gap's
    middle, else ``<program before> -> <program after>``. A gap that runs
    over several spans takes the one at its middle (an engine's long wait is
    many `serve/idle` sleeps, and may take the pass between two of them):
    ``uncovered`` is the exact form. Gaps under ``floor_s`` are the bubbles
    between one program's operations (hundreds of thousands of a few
    nanoseconds in a 4 s trace): one entry for all of them, unnamed."""
    mods = sorted((s, n) for n, s, _ in modules)
    starts = [m[0] for m in mods]
    out, bubbles = [], 0.0
    for s, e in gaps:
        if e - s < floor_s:
            bubbles += e - s
            continue
        mid = (s + e) / 2
        covering = [(d, n) for n, a, d, *_ in spans if a <= mid <= a + d]
        if covering:
            what = min(covering)[1]
        else:
            i = bisect.bisect_right(starts, mid)
            before = mods[i - 1][1] if i > 0 else "trace start"
            after = mods[i][1] if i < len(mods) else "trace end"
            what = f"{before} -> {after}"
        out.append([what, s, e - s])
    if bubbles:
        out.append([f"gaps under {floor_s:g} s", gaps[0][0], bubbles])
    return sorted(out, key=lambda g: -g[2])


def table(named: list) -> list:
    """``[[what, seconds, gaps]...]`` summed over ``by_span``'s entries,
    the most seconds first."""
    total: dict = {}
    for what, _, seconds in named:
        t = total.setdefault(what, [0.0, 0])
        t[0] += seconds
        t[1] += 1
    return sorted(([w, t, n] for w, (t, n) in total.items()), key=lambda r: -r[1])


def uncovered(gaps: list, spans: list, name: str) -> float:
    """Seconds of ``gaps`` that no span called ``name`` covers."""
    cover = tracing.union([(a, a + d) for n, a, d, *_ in spans if n == name])
    return tracing.total(tracing.subtract(tracing.union(map(tuple, gaps)), cover))


def engaged_idle_share(loaded: dict) -> float:
    """Share, in percent, of the window that is idle on the device and not
    inside a `serve/idle` span. The window is cut to what the program's
    spans span: the profiler keeps no span that is open when the trace stops,
    so past the last recorded span nobody can say what the host did (a trace
    that ends in an engine's wait would read that whole sleep as engaged)."""
    lo, hi = loaded["window"]
    lo = max(lo, min(a for _, a, *_ in loaded["spans"]))
    hi = min(hi, max(a + d for _, a, d, *_ in loaded["spans"]))
    gaps = tracing.clip(map(tuple, loaded["gaps"]), lo, hi)
    return 100.0 * uncovered(gaps, loaded["spans"], "serve/idle") / (hi - lo)


if __name__ == "__main__":
    found = load(sys.argv[1])
    if found is None:
        sys.exit(f"no trace with device operations under {sys.argv[1]}")
    named = by_span(found["gaps"], found["spans"], found["modules"])
    lo, hi = found["window"]
    print(json.dumps({"window_s": hi - lo, "idle_s": sum(g[2] for g in named),
                      "gaps": len(named), "engaged_idle_share": engaged_idle_share(found),
                      "by_span": table(named)}))
    for what, start, seconds in named:
        if seconds >= 1e-3:
            print(json.dumps({"what": what, "at_s": start - lo, "seconds": seconds}))
