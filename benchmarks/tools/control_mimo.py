#!/usr/bin/env python3
"""`tools/control_hybrid.py` for a cell whose driver is `serve_mimo`: readings
for the cell's limits, on the chip, at the cell's own size:

    python3 benchmarks/tools/control_mimo.py --workload <cell> --seeds 1,2,.. \\
        --control-seeds 1 [--seconds 6] [--arms sound,fault_no_sink] [--no-warm-up]

For every seed it prints the numbers the sound program gives against the
reference; for the control seeds also what each `check.controls` entry gives,
under the same traffic and judged as a run is: the program with that entry's
`model` options (`mimo_adapter.build_model`: the sink left out, a window of 127,
RoPE over the whole head, the value scale left out), with its `plant` in place
(`ring_forgets_chunk`: a prefill chunk sees nothing of the ring, so the first
127 queries of every chunk but the first lose the previous chunk's rows), or
with its weights rounded to `weights_stored_as` (float8: the storage type below
bfloat16) while the reference keeps the bfloat16 values. One engine an arm (its
programs compile once); each seed's weights replace the engine's. The
benchmark's own runs never run this; PERF.md records what it printed and the
limits set from it."""

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


def plant(name: str | None):
    """Put the named change into the program's modules; returns what takes it
    out again. Nothing of it is an option of the program."""
    import jax.numpy as jnp

    from tpudml.serve import cache

    if name is None:
        return lambda: None
    if name != "ring_forgets_chunk":
        raise ValueError(f"no plant named {name!r}")
    real = cache.read_ring_slot

    def forgetful(ring, slot, start, dtype):
        k, v = real(ring, slot, start, dtype)
        return jnp.zeros_like(k), jnp.zeros_like(v)

    cache.read_ring_slot = forgetful
    return lambda: setattr(cache, "read_ring_slot", real)


def lower_weights(leaves: list, stored_as: str) -> None:
    """Every bfloat16 leaf of ``leaves`` rounded to ``stored_as`` and back, in
    place, a leaf at a time, each convert a program of its own: the stored type
    has to exist in memory, or the chip's compiler drops the pair of converts
    (`control_hybrid.py`); and two copies of the weights do not fit, so the
    caller keeps no other reference to a leaf."""
    import jax.numpy as jnp

    for i, a in enumerate(leaves):
        if a.dtype == jnp.bfloat16:
            leaves[i] = None
            low = a.astype(stored_as)
            del a
            leaves[i] = low.astype(jnp.bfloat16)


def _arm(cell, arm: str, control: dict, seeds, seconds: float, warm: bool) -> None:
    import jax

    from benchmarks.drivers import serve_mimo as drv

    check = cell.spec["check"]
    t = time.perf_counter()
    undo = plant(control.get("plant"))
    try:
        engine = drv.build_engine(cell, seeds[0], **control.get("model", {}))
        if warm:
            drv.warm_up(engine, cell, seeds[0])
        emit({"arm": arm, "control": control, "build_and_warm_s": time.perf_counter() - t})
        for seed in seeds:
            engine.params = None
            gc.collect()
            leaves, tree = jax.tree.flatten(drv.make_params(cell, seed))
            if control.get("weights_stored_as"):
                lower_weights(leaves, control["weights_stored_as"])
            engine.params = jax.tree.unflatten(tree, leaves)
            del leaves
            reqs = drv.make_requests(cell.traffic, cell.config, seed, seconds)
            t = time.perf_counter()
            finished = drv.finished_requests(reqs, engine.run(reqs))
            served_s = time.perf_counter() - t
            engine.params = None
            gc.collect()
            t = time.perf_counter()
            weights = drv.make_weights(cell, seed)
            rows = drv.served_gaps(cell.config, weights,
                                   drv.sample_of(finished, seed, check["sample"]),
                                   cell.traffic["output_len"]["max"], check["pad_to"])
            del weights
            verdict = compare.Verdict()
            judged = drv.judge(verdict, rows, check["limits"])
            emit({"seed": seed, "arm": arm, "requests": len(reqs), "finished": len(finished),
                  "correct": verdict.correct, "served_s": served_s,
                  "reference_s": time.perf_counter() - t, **judged,
                  **{r["name"]: r["value"] for r in verdict.rows},
                  "failed_limits": [r["name"] for r in verdict.rows if not r["ok"]],
                  "notes": [r["note"] for r in verdict.rows]})
        del engine
        gc.collect()
    finally:
        undo()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--arms", default="", help="only these (sound, or a control's name)")
    ap.add_argument("--no-warm-up", action="store_true",
                    help="compile a program when the traffic first needs it")
    args = ap.parse_args()
    from benchmarks import cells, device

    device.compile_cache()
    cell = cells.load_cell(args.workload)
    device.require_chips(cell.chips)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = sorted(int(s) for s in args.control_seeds.split(",") if s)
    only = {a for a in args.arms.split(",") if a}
    arms = [("sound", {}, seeds)] + [
        (name, entry, control) for name, entry in cell.spec["check"]["controls"].items()]
    for arm, entry, arm_seeds in arms:
        if not arm_seeds or (only and arm not in only):
            continue
        try:
            _arm(cell, arm if arm == "sound" else f"control:{arm}", entry, arm_seeds,
                 args.seconds, not args.no_warm_up)
        except Exception:  # a control that crashes has failed; the next arm still runs
            emit({"arm": arm, "crashed": traceback.format_exc()[-3000:]})
        gc.collect()  # outside the handler: the traceback holds the arm's engine until here


if __name__ == "__main__":
    main()
