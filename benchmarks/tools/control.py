#!/usr/bin/env python3
"""Readings for a cell's limits, on the chip, at the cell's own size:

    python3 benchmarks/tools/control.py --workload <cell> --seeds 1,2,.. \\
        --control-seeds 1,2,3 [--seconds 12]

For every seed it prints the numbers the sound program gives against the
reference; for the control seeds also what the control gives: the cell's
configuration one precision down (training: the reference with its weights
and Adam moments stored in `check.control.reference_store_dtype`; serving:
the program's own engine with each `check.controls` entry's `ServeConfig`
fields switched on, e.g. `weight_quant` or `cache_kind` int8, under the same
traffic and judged as a run is). The benchmark's own
runs never run this; PERF.md records what it printed and the limits set from
it. One process, so the chip is held once."""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import compare  # noqa: E402


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def train(cell, seeds, control_seeds) -> None:
    from benchmarks.drivers import train_loop as drv

    for seed in seeds:
        program = drv.Program(cell, seed)
        first = program.first_steps()
        program.free()
        del program
        gc.collect()
        t = time.perf_counter()
        ref = drv.reference(cell, seed, first["batches"])
        reference_s = time.perf_counter() - t
        rows = {r["name"]: r["value"] for r in drv.judge(cell, first, ref).rows}
        emit({"seed": seed, "arm": "sound", "reference_s": reference_s, **rows})
        if seed in control_seeds:
            t = time.perf_counter()
            low = drv.reference(cell, seed, first["batches"],
                                cell.spec["check"]["control"]["reference_store_dtype"])
            verdict = drv.judge(cell, low, ref)
            emit({"seed": seed, "arm": "control", "correct": verdict.correct,
                  "control_s": time.perf_counter() - t,
                  **{r["name"]: r["value"] for r in verdict.rows},
                  "notes": {r["name"]: r["note"] for r in verdict.rows}})


def _serve_arm(cell, arm: str, overrides: dict, seeds, seconds: float) -> None:
    """One engine (its programs compile once) over ``seeds``: each seed's
    weights replace the engine's, made as the engine's own init makes them
    (quantized and dequantized by the program's functions where the arm
    switches `weight_quant` on); every slot was released by the last run."""
    import jax

    from benchmarks.drivers import lm_adapter, serve as drv
    from benchmarks.reference import gpt2

    cfg, spec = cell.config, cell.spec
    dtype = lm_adapter.param_dtype(spec["model"])
    make = jax.jit(lambda key: gpt2.init_weights(cfg, key, dtype))

    def as_engine_keeps_them(key):
        params = lm_adapter.to_program(gpt2.init_weights(cfg, key, dtype), cfg["n_layer"])
        if overrides.get("weight_quant") == "int8":
            from tpudml.serve.fleet.quant import dequantize_params, quantize_params

            params = dequantize_params(*quantize_params(params))
        return params

    seeded = jax.jit(as_engine_keeps_them)
    t = time.perf_counter()
    engine = drv.build_engine(cell, seeds[0], **overrides)
    engine.quantized_params = engine.quant_scales = None  # storage accounting only
    drv.warm_up(engine, cell, seeds[0])
    emit({"arm": arm, "serve_config": overrides, "build_and_warm_s": time.perf_counter() - t})
    for seed in seeds:
        engine.params = None
        gc.collect()
        engine.params = seeded(gpt2.seed_key(seed))
        reqs = drv.make_requests(cell.traffic, cfg, seed, seconds)
        report = engine.run(reqs)
        finished = [(r.rid, r.prompt, list(report.requests[r.rid].tokens))
                    for r in reqs if report.requests[r.rid].finished is not None]
        engine.params = None
        gc.collect()
        t = time.perf_counter()
        weights = make(gpt2.seed_key(seed))
        sample = drv.pick_sample(finished, seed, spec["check"]["sample"])
        rows = drv.served_gaps(cfg, weights, sample, cell.traffic["output_len"]["max"])
        del weights
        verdict = compare.Verdict()
        judged = drv.judge_served(verdict, rows, spec["check"]["limits"])
        emit({"seed": seed, "arm": arm, "requests": len(reqs), "finished": len(finished),
              "correct": verdict.correct, "reference_s": time.perf_counter() - t, **judged,
              **{r["name"]: r["value"] for r in verdict.rows},
              "note": verdict.rows[0]["note"]})
    del engine
    gc.collect()


def serve(cell, seeds, control_seeds, seconds: float) -> None:
    arms = [("sound", {}, seeds)] + [
        (f"control:{name}", overrides, sorted(control_seeds))
        for name, overrides in cell.spec["check"]["controls"].items()]
    for arm, overrides, arm_seeds in arms:
        if not arm_seeds:
            continue
        try:
            _serve_arm(cell, arm, overrides, arm_seeds, seconds)
        except Exception:  # a control that crashes has failed; the next arm still runs
            emit({"arm": arm, "crashed": traceback.format_exc()[-3000:]})
            gc.collect()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=12.0)
    args = ap.parse_args()
    from benchmarks import cells, device

    device.compile_cache()
    cell = cells.load_cell(args.workload)
    device.require_chips(cell.chips)
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    if cell.driver == "train_loop":
        train(cell, seeds, control)
    else:
        serve(cell, seeds, control, args.seconds)


if __name__ == "__main__":
    main()
