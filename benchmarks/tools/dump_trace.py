#!/usr/bin/env python3
"""Look at a trace by hand: `python3 benchmarks/tools/dump_trace.py <trace_dir>
[out.json]` prints every plane and line with its first events, then the
reduction's summary; with a second argument it also writes the loaded events
(what `tracing.reduce` reads) as JSON, e.g. to cut a fixture for the tests."""

import glob
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def main(trace_dir: str, out: str | None = None) -> None:
    import jax

    from benchmarks import tracing

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    print(path, os.path.getsize(path), "bytes")
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for e in events[:6]:
                print(f"      {e.name[:90]!r} start {e.start_ns / 1e9:.6f} "
                      f"dur {e.duration_ns / 1e6:.4f} ms")
    events = tracing.load_events(trace_dir)
    summary = tracing.reduce(events)
    print("window_s", summary.window_s, "busy_s", summary.busy_s,
          "devices", summary.n_devices)
    for name, ds in summary.programs.items():
        print(f"  program {name}: n={len(ds)} median {1e3 * sorted(ds)[len(ds) // 2]:.3f} ms")
    print("device_ops", json.dumps(summary.device_ops))
    print("idle_gaps", json.dumps(summary.idle_gaps))
    print("host spans", len(events["host"]), events["host"][:5])
    if out:
        with open(out, "w") as f:
            json.dump(events, f)


if __name__ == "__main__":
    main(*sys.argv[1:3])
