#!/usr/bin/env python3
"""One run of one cell:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of the checkout, in one process (which holds the chip; no
children). Prints what it compared and measured as lines of JSON; the LAST
line is the result the driver reads. Fails, and prints no result, when JAX's
default backend is not a TPU or has fewer chips than the cell asks for.
See benchmarks/README.md.
"""

from __future__ import annotations

import time

STARTED = time.perf_counter()  # set-up counts from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))  # `benchmarks` and the program, `tpudml`


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def read_layer_metric(name: str, ctx: dict):
    """`benchmarks/layer_metrics/<name>.py` has one function, `read(ctx)`;
    a reader that finds nothing to read returns None."""
    path = ROOT / "benchmarks" / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"layer_metric_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(ctx)


def run_cell(cell, seed: int, seconds: float, trace: bool, devices, started: float,
             trace_dir: str, clock=None) -> dict:
    """Everything of a run after the look for a chip: drive the cell, reduce
    the trace, read the metrics. Returns the result line as a dict."""
    from benchmarks import device, tracing

    driver = importlib.import_module(f"benchmarks.drivers.{cell.driver}")
    out = driver.run(cell, seed, seconds, trace, devices, started, trace_dir)
    verdict = out["verdict"]
    for row in verdict.rows:
        emit({"compared": row["name"], "value": row["value"], "limit": row["limit"],
              "ok": row["ok"], "note": row["note"]})
    emit({"info": out["info"], "memory_stats": device.memory_stats(devices),
          "jax_events_whole_run": clock.snapshot() if clock else None})
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    dev = {**device.describe(devices), "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": verdict.correct, "attempted": out["attempted"],
              "failed": out["failed"]}
    if not trace:
        values = {m["name"]: out["end_to_end"][m["name"]] for m in cell.end_to_end}
    else:
        summary = tracing.reduce(tracing.load_events(trace_dir))
        ctx = {"trace": summary, "host": out["host"], "cell": cell,
               "n_devices": len(devices),
               "peaks": device.peaks(devices[0].device_kind)
               if devices[0].platform == "tpu" else {}}
        values = {}
        for m in cell.per_layer:
            value = read_layer_metric(m["name"], ctx)
            if value is not None:
                values[m["name"]] = value
        dev.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
        emit({"end_to_end_while_traced": out["end_to_end"]})
    result["metrics"] = {k: {"value": v, "unit": units[k]} for k, v in values.items()}
    result["device"] = dev
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks import cells, device

    cell = cells.load_cell(args.workload)

    from benchmarks.setup_clock import SetupClock

    clock = SetupClock()
    cache_dir = device.compile_cache()
    try:
        devices = device.require_chips(cell.chips)
        device.peaks(devices[0].device_kind)
    except (device.NoChip, KeyError) as e:
        device.fail(str(e))
    emit({"cell": cell.name, "seed": args.seed, "seconds": args.seconds,
          "trace": args.trace, "device": device.describe(devices),
          "compile_cache_dir": cache_dir})
    trace_dir = str(ROOT / "benchmarks" / ".trace" / cell.name)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
                      STARTED, trace_dir, clock)
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
