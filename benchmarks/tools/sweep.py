#!/usr/bin/env python3
"""Find a serving cell's knee once, on the chip:

    python3 benchmarks/tools/sweep.py --workload <cell> --rates 6,8,10,12 \\
        [--seconds 30] [--seed 1]

The engine is built and warmed once; each rate then gets one open-loop run of
the cell's traffic at that rate. The knee (defined in the traffic file) is the
highest rate at which the backlog drains within 5% of the window after the
last arrival (the last request is admitted by then; finishing its own tokens
takes what it takes) and `peak_queue_depth` stays under `slots`. The cell's fixed rate is
0.8 x the knee, written into the traffic file as a number; PERF.md keeps the
sweep. Never run by the benchmark itself."""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    from benchmarks import cells, device
    from benchmarks.drivers import serve as drv
    from benchmarks.stats import percentile

    device.compile_cache()
    cell = cells.load_cell(args.workload)
    device.require_chips(cell.chips)
    engine = drv.build_engine(cell, args.seed)
    drv.warm_up(engine, cell, args.seed)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        traffic = {**cell.traffic, "rate_per_s": rate}
        reqs = drv.make_requests(traffic, cell.config, args.seed + i, args.seconds)
        report = engine.run(reqs)
        stats = [report.requests[r.rid] for r in reqs]
        ttft = [s.ttft_s for s in stats if s.ttft_s is not None]
        tpot = [s.tpot_s for s in stats if s.tpot_s is not None]
        drain = max(s.admit_start for s in stats if s.admit_start is not None) \
            - reqs[-1].arrival_time
        print(json.dumps({
            "rate_per_s": rate, "requests": len(reqs),
            "finished": sum(s.finished is not None for s in stats),
            "wall_s": report.wall_time, "last_admit_after_last_arrival_s": drain,
            "finish_after_last_arrival_s": report.wall_time - reqs[-1].arrival_time,
            "drains_within_5pct": drain <= 0.05 * args.seconds,
            "peak_queue_depth": report.peak_queue_depth,
            "queue_under_slots": report.peak_queue_depth < engine.cfg.slots,
            "occupancy": report.occupancy, "decode_steps": report.decode_steps,
            "ttft_p50_ms": 1e3 * percentile(ttft, 50), "ttft_p95_ms": 1e3 * percentile(ttft, 95),
            "tpot_p50_ms": 1e3 * percentile(tpot, 50), "tpot_p95_ms": 1e3 * percentile(tpot, 95),
            "tokens_per_s": sum(1 for s in stats for t in s.token_times
                                if t <= args.seconds) / args.seconds,
        }), flush=True)


if __name__ == "__main__":
    main()
