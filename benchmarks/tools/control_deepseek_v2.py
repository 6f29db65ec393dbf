#!/usr/bin/env python3
"""`tools/control_mimo.py` for a cell whose driver is `serve_deepseek_v2`: readings
for the cell's limits, on the chip, at the cell's own size:

    python3 benchmarks/tools/control_deepseek_v2.py --workload <cell> --seeds 1,2,.. \\
        --control-seeds 1 [--seconds 6] [--sample 16] [--arms sound,fault_no_groups] \\
        [--no-warm-up]

For every seed it prints the numbers the sound program gives against the
reference; for the control seeds also what each `check.controls` entry gives,
under the same traffic and judged as a run is: the program with that entry's
`model` options (`deepseek_v2_adapter.build_model`: plain top-6 without groups,
no `routed_scaling_factor`, renormalised top-k), with its `plant` in place
(`PLANTS`: the softmax scale without m^2, plain RoPE for YaRN, `c_kv` cached before
its norm, `k_r` cached before RoPE), or with its weights rounded to
`weights_stored_as` (float8: the storage type below bfloat16) while the reference
keeps the bfloat16 values. One engine an arm (its programs compile once); each
seed's weights replace the engine's. ``--sample`` judges fewer requests than the
cell's `check.sample` (a control that fails on 16 fails on 64). The benchmark's own
runs never run this; PERF.md records what it printed and the limits set from
it."""

from __future__ import annotations

import argparse
import gc
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import compare  # noqa: E402
from benchmarks.tools.control_mimo import emit, lower_weights  # noqa: E402


def _rows_without(norm: bool, rope: bool):
    """`LatentAttention.latent_rows` with a step left out of what is cached (and
    of what a prefill chunk reads back of itself)."""
    import jax.numpy as jnp

    def latent_rows(self, params, x, positions):
        down = x @ params["kv_down"]["kernel"]
        c_kv, k_r = down[..., :self.kv_rank], down[..., self.kv_rank:]
        if norm:
            c_kv = self._norm(params["kv_norm"], c_kv)
        if rope:
            k_r = self._rope(k_r[..., None, :], positions)[..., 0, :]
        return jnp.concatenate([c_kv, k_r], axis=-1)

    return latent_rows


def _plain_table(dim, base, factor, original, beta_fast, beta_slow):
    import numpy as np

    return (np.float32(base) ** (-2.0 * np.arange(dim // 2, dtype=np.float32) / dim))


# name -> (module attribute path in tpudml.nn.attention, what stands there instead)
PLANTS = {
    "no_mscale": ("LatentAttention._scale",
                  property(lambda self: (self.nope_dim + self.rope_dim) ** -0.5)),
    "plain_rope": ("yarn_inv_freq", _plain_table),
    "latent_before_norm": ("LatentAttention.latent_rows", _rows_without(False, True)),
    "key_before_rope": ("LatentAttention.latent_rows", _rows_without(True, False)),
}


def plant(name: str | None):
    """Put the named change into the program's modules; returns what takes it
    out again. Nothing of it is an option of the program."""
    from tpudml.nn import attention

    if name is None:
        return lambda: None
    if name not in PLANTS:
        raise ValueError(f"no plant named {name!r}")
    path, fake = PLANTS[name]
    owner = attention
    *parents, attr = path.split(".")
    for part in parents:
        owner = getattr(owner, part)
    real = owner.__dict__[attr] if parents else getattr(owner, attr)
    setattr(owner, attr, fake)
    return lambda: setattr(owner, attr, real)


def _arm(cell, arm: str, control: dict, seeds, seconds: float, warm: bool,
         sample: int | None = None) -> None:
    import jax

    from benchmarks.drivers import serve_deepseek_v2 as drv

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
                                   drv.sample_of(finished, seed, sample or check["sample"]),
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
    ap.add_argument("--sample", type=int, default=0, help="judge this many requests")
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
                 args.seconds, not args.no_warm_up, args.sample or None)
        except Exception:  # a control that crashes has failed; the next arm still runs
            emit({"arm": arm, "crashed": traceback.format_exc()[-3000:]})
        gc.collect()  # outside the handler: the traceback holds the arm's engine until here


if __name__ == "__main__":
    main()
