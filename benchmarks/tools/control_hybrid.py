#!/usr/bin/env python3
"""`tools/control.py` for a cell whose driver is `serve_hybrid`: readings for
the cell's limits, on the chip, at the cell's own size:

    python3 benchmarks/tools/control_hybrid.py --workload <cell> --seeds 1,2,.. \\
        --control-seeds 1,2,3 [--seconds 12] [--arms sound,weights_fp8]

For every seed it prints the numbers the sound program gives against the
reference; for the control seeds also what each `check.controls` entry gives:
the program's own engine one precision down — with that entry's `model`
options switched on (the recurrent state kept in bfloat16), with its `plant`
in place (`router_bf16`: the router's scores computed in bfloat16), or its
weights rounded to `weights_stored_as` (float8: the storage type below
bfloat16) while the reference keeps the bfloat16 values — under the same
traffic and judged as a run is. The other plants are the two faults the
engine's mechanism for a recurrent state exists to prevent (`no_state_reset`:
admission leaves the last tenant's state; `tail_advances_state`: a padded
chunk tail counts as real). One engine an arm (its programs compile once);
each seed's weights replace the engine's. The benchmark's own runs
never run this; PERF.md records what it printed and the limits set from it."""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
import traceback
from functools import partial
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import compare  # noqa: E402


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def plant(name: str | None):
    """Put the named change into the program's classes; returns what takes
    it out again. Nothing of it is an option of the program."""
    import jax
    import jax.numpy as jnp

    from tpudml.models.hybrid import HybridLM
    from tpudml.nn.mamba import Mamba2
    from tpudml.nn.moe import SigmoidMoE

    if name is None:
        return lambda: None
    if name == "router_bf16":
        owner, attr = SigmoidMoE, "scores"

        def changed(self, params, tokens):
            # `reduce_precision` and not `astype`: the chip's compiler is allowed excess
            # precision and drops a pair of converts, so a router written in bfloat16 types
            # came out computed in float32 (PERF.md, PR 30: its readings equalled the sound
            # program's)
            bf16 = partial(jax.lax.reduce_precision, exponent_bits=8, mantissa_bits=7)
            logits = bf16(jnp.dot(bf16(tokens.astype(jnp.float32)),
                                  bf16(params["router"]["kernel"]),
                                  precision=jax.lax.Precision.HIGHEST))
            return bf16(jax.nn.sigmoid(logits))
    elif name == "no_state_reset":
        owner, attr = HybridLM, "reset_slot"

        def changed(self, caches, slot):
            return caches
    elif name == "tail_advances_state":
        owner, attr = Mamba2, "apply_prefill"
        real_prefill = Mamba2.apply_prefill

        def changed(self, params, cache, u, slot, n_real):
            return real_prefill(self, params, cache, u, slot,
                                jnp.asarray(u.shape[1], jnp.int32))
    else:
        raise ValueError(f"no plant named {name!r}")
    real = getattr(owner, attr)
    setattr(owner, attr, changed)
    return lambda: setattr(owner, attr, real)


def _arm(cell, arm: str, control: dict, seeds, seconds: float) -> None:
    import jax
    import jax.numpy as jnp

    from benchmarks.drivers import serve_hybrid as drv

    spec = cell.spec
    t = time.perf_counter()
    undo = plant(control.get("plant"))
    try:
        engine = drv.build_engine(cell, seeds[0], **control.get("model", {}))
        drv.warm_up(engine, cell, seeds[0])
        emit({"arm": arm, "control": control, "build_and_warm_s": time.perf_counter() - t})
        stored_as = control.get("weights_stored_as")

        def lower(leaves: list) -> None:
            # in place, a leaf at a time, each convert a program of its own: the stored type
            # has to exist in memory, or the compiler drops the pair of converts (see
            # `plant`); and two copies of the weights do not fit
            for i, a in enumerate(leaves):
                if a.dtype == jnp.bfloat16:
                    leaves[i] = None
                    low = a.astype(stored_as)
                    del a
                    leaves[i] = low.astype(jnp.bfloat16)

        for seed in seeds:
            engine.params = None
            gc.collect()
            params = drv.make_params(cell, seed)
            if stored_as:
                leaves, tree = jax.tree.flatten(params)
                del params
                lower(leaves)
                params = jax.tree.unflatten(tree, leaves)
                del leaves
            engine.params = params
            del params
            reqs = drv.make_requests(cell.traffic, cell.config, seed, seconds)
            finished = drv.finished_requests(reqs, engine.run(reqs))
            engine.params = None
            gc.collect()
            t = time.perf_counter()
            weights = drv.make_weights(cell, seed)
            sample = drv.sample_of(finished, seed, spec["check"]["sample"])
            rows = drv.served_gaps(cell.config, weights, sample,
                                   cell.traffic["output_len"]["max"])
            del weights
            verdict = compare.Verdict()
            judged = drv.judge(verdict, rows, spec["check"]["limits"])
            emit({"seed": seed, "arm": arm, "requests": len(reqs), "finished": len(finished),
                  "correct": verdict.correct, "reference_s": time.perf_counter() - t, **judged,
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--arms", default="", help="only these (sound, or a control's name)")
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
                 args.seconds)
        except Exception:  # a control that crashes has failed; the next arm still runs
            emit({"arm": arm, "crashed": traceback.format_exc()[-3000:]})
            gc.collect()


if __name__ == "__main__":
    main()
