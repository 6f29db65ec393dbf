#!/usr/bin/env python3
"""`tools/sweep.py` for any serving cell, by the driver the cell's own file
names (sweep.py binds `drivers/serve.py`'s engine by import, sweep_hybrid.py
`drivers/serve_hybrid.py`'s): find the cell's knee once, on the chip.

    python3 benchmarks/tools/sweep_cell.py --workload <cell> --rates 4,5,6 \\
        [--seconds 30] [--seed 1]

Same definition, same columns: the engine is built and warmed once, each rate
gets one open-loop run of the cell's traffic at that rate, from an empty engine
to the last answer. Never run by the benchmark itself."""

from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
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
    from benchmarks.stats import percentile

    device.compile_cache()
    cell = cells.load_cell(args.workload)
    drv = importlib.import_module(f"benchmarks.drivers.{cell.driver}")
    devices = device.require_chips(cell.chips)
    t = time.perf_counter()
    engine = drv.build_engine(cell, args.seed)
    built = time.perf_counter() - t
    drv.warm_up(engine, cell, args.seed)
    print(json.dumps({"build_s": built, "warm_up_s": time.perf_counter() - t - built,
                      "memory_peak_bytes": device.memory_peak_bytes(devices)}), flush=True)
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
            "memory_peak_bytes": device.memory_peak_bytes(devices),
        }), flush=True)


if __name__ == "__main__":
    main()
