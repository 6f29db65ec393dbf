#!/usr/bin/env python3
"""`tools/control_mimo.py` for a cell whose driver is `serve_phi4flash`: readings
for the cell's limits, on the chip, at the cell's own size:

    python3 benchmarks/tools/control_phi4flash.py --workload <cell> --seeds 1,2,.. \\
        --control-seeds 1 [--seconds 6] [--arms sound,fault_no_subln] [--no-warm-up]

For every seed it prints the numbers the sound program gives against the
reference; for the control seeds also what each `check.controls` entry gives,
under the same traffic and judged as a run is: the program with that entry's
`model` options (`phi4flash_adapter.build_model`: a window of 511, the recurrent
state stored bfloat16), with its `plant` in place (`PLANTS`), or with its weights
rounded to `weights_stored_as` (float8: the storage type below bfloat16) while the
reference keeps the bfloat16 values. One engine an arm (its programs compile
once); each seed's weights replace the engine's. The benchmark's own runs never
run this; PERF.md records what it printed and the limits set from it."""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import compare  # noqa: E402
from benchmarks.tools.control_mimo import lower_weights  # noqa: E402


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def _swap(owner, name: str, new):
    """Put ``new`` in ``owner.name``; returns what puts the old one back."""
    old = getattr(owner, name)
    setattr(owner, name, new)
    return lambda: setattr(owner, name, old)


def _lambda_of_layer0():
    """Every differential layer takes the first layer's `lambda_init` (0.2)."""
    from tpudml.models import hybrid

    real = hybrid.lambda_init
    return _swap(hybrid, "lambda_init", lambda depth: real(0))


def _no_subln():
    """A1 - lambda A2 goes on without its 128-wide RMSNorm."""
    from tpudml.nn.attention import DifferentialAttention

    return _swap(DifferentialAttention, "_subln", lambda self, params, x: x)


def _memory_after_gate():
    """The memory units are handed the Mamba layer's scan output AFTER its gate."""
    import jax

    from tpudml.nn.mamba import Mamba1

    real = Mamba1._finish

    def gated(self, params, y, x, z):
        out, m = real(self, params, y, x, z)
        return out, m * jax.nn.silu(z)

    return _swap(Mamba1, "_finish", gated)


def _cross_reads_own_kv():
    """A cross layer behaves as a self-attention layer over the shared cache:
    it projects K and V of its OWN input (with the full layer's projections,
    the only ones there are) and writes them over the token's row, so that every
    later read finds the last cross layer's rows where the full layer's belong."""
    from benchmarks.drivers import phi4flash_adapter as adapter
    from tpudml.nn.attention import DifferentialAttention

    real_decode, real_tree = DifferentialAttention.apply_decode, adapter.to_program

    def own_kv(self, params, cache, x, pos):
        return real_decode(dataclasses.replace(self, cross=False) if self.cross else self,
                           params, cache, x, pos)

    def with_projections(flat, cfg):
        tree = real_tree(flat, cfg)
        kinds = adapter.pattern(cfg)
        full = tree[f"layer{kinds.index('F')}"]["mixer"]
        for i, kind in enumerate(kinds):
            if kind == "X":
                tree[f"layer{i}"]["mixer"].update(k=full["k"], v=full["v"])
        return tree

    undo = [_swap(DifferentialAttention, "apply_decode", own_kv),
            _swap(adapter, "to_program", with_projections)]
    return lambda: [u() for u in undo]


def _no_state_reset():
    """A request takes a slot with the last tenant's recurrent state in it."""
    from tpudml.models import HybridLM

    return _swap(HybridLM, "reset_slot", lambda self, caches, slot: caches)


def _prefill_skips_kv():
    """The second prefill chunk's rows of the full layer's cache are not written."""
    from tpudml.serve import cache

    real = cache.write_chunk
    return _swap(cache, "write_chunk", lambda c, k, v, slot, start: (
        c if start == k.shape[1] else real(c, k, v, slot, start)))


PLANTS = {"lambda_of_layer0": _lambda_of_layer0, "no_subln": _no_subln,
          "memory_after_gate": _memory_after_gate, "cross_reads_own_kv": _cross_reads_own_kv,
          "no_state_reset": _no_state_reset, "prefill_skips_kv": _prefill_skips_kv}


def plant(name: str | None):
    """Put the named change into the program's modules; returns what takes it
    out again. Nothing of it is an option of the program."""
    if name is None:
        return lambda: None
    if name not in PLANTS:
        raise ValueError(f"no plant named {name!r}")
    return PLANTS[name]()


def _arm(cell, arm: str, control: dict, seeds, seconds: float, warm: bool) -> None:
    import jax

    from benchmarks.drivers import serve_phi4flash as drv

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
                                   drv.pick_sample(finished, seed, check["sample"]),
                                   cell.traffic["output_len"]["max"], check["pad_to"])
            del weights
            verdict = compare.Verdict()
            judged = drv.judge_served(verdict, rows, check["limits"])
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
