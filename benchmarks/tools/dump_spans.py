#!/usr/bin/env python3
"""Cut a fixture for `benchmarks/tests/test_program_spans.py` out of a traced run:

    python3 benchmarks/tools/dump_spans.py <cell> <out.json> [seconds]

after `run.py --workload <cell> --trace 1` in the same checkout. Reads the
program's spans (`program_spans.load`) and the device's programs
(`tracing.reduce`) from the cell's trace, keeps what touches the first
`seconds` (default 1.2) of the traced window, so that spans cut by the new
window's end stay in, and writes them with what each reader of the spans
makes of that cut. Adds to `out.json` when it already holds another cell."""

import json
import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def main(cell_name: str, out: str, seconds: str = "1.2") -> None:
    from benchmarks import cells, program_spans, run, tracing

    cell = cells.load_cell(cell_name)
    trace_dir = str(cells.BENCH / ".trace" / cell.name)
    loaded = program_spans.load(trace_dir)
    summary = tracing.reduce(tracing.load_events(trace_dir))
    lo = loaded["window"][0]
    hi = min(loaded["window"][1], lo + float(seconds))
    cut = {"window": [lo, hi],
           "spans": [s for s in loaded["spans"] if s[1] < hi and s[1] + s[2] > lo]}
    pattern = cell.spec.get("programs", {}).get("prefill")
    programs, starts = {}, {}
    for name, durations in summary.programs.items():
        if pattern and re.search(pattern, name):
            kept = [(s, d) for s, d in zip(summary.program_starts[name], durations)
                    if lo <= s and s + d <= hi]
            programs[name] = [d for _, d in kept]
            starts[name] = [s for s, _ in kept]
    program_spans.load = lambda trace_dir: cut
    ctx = {"cell": cell, "host": {}, "trace": tracing.Summary(
        window_s=hi - lo, busy_s=0.0, programs=programs, program_starts=starts)}
    readers = [m["name"] for m in cell.per_layer
               if "program_spans" in (cells.BENCH / "layer_metrics" / f"{m['name']}.py").read_text()]
    expected = {name: run.read_layer_metric(name, ctx) for name in readers}
    path = Path(out)
    record = json.loads(path.read_text()) if path.exists() else {}
    record[cell.name] = {
        "loaded": cut, "trace": {"programs": programs, "program_starts": starts},
        "spec": {"programs": cell.spec.get("programs", {}),
                 "engine": cell.spec.get("engine", {})},
        "expected": {k: v for k, v in expected.items() if v is not None}}
    path.write_text(json.dumps(record) + "\n")
    print(json.dumps({"cell": cell.name, "spans": len(cut["spans"]),
                      "whole_window": {n: len(program_spans.named(
                          program_spans.inside(loaded), n))
                          for n in sorted({s[0] for s in loaded["spans"]})},
                      "expected_on_the_cut": record[cell.name]["expected"]}))


if __name__ == "__main__":
    main(*sys.argv[1:4])
