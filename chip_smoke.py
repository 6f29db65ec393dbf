#!/usr/bin/env python3
"""Does the system still start on the chip?  ``python chip_smoke.py``

Drives the main path once on ONE TPU through the entry points users call,
at the full width of the largest model this repo has run (the r05 "large"
row: 12L · d=1024 · 8 heads × 128 · GQA 8/2 · V=32768 · T=2048 · B=8, bf16
compute over f32 master weights, random weights from a seed):

- ``device``    — the first JAX device must be a TPU, or the script prints
                  ``"ok": false`` and exits non-zero before building anything;
- ``train_lm``  — the flagship fused train step (flash attention, fused
                  add+LayerNorm, fused linear-cross-entropy head) built by the
                  public library calls: donated steps, finite falling loss,
                  the Pallas kernels present in the compiled program, and one
                  step's loss and gradient norm against the plain path;
- ``train_cli`` — ``tasks.north_star``: the whole trainer (DataParallel on a
                  one-device mesh, sharded loader, C++ data plane, prefetch,
                  train_loop, evaluate, MetricsWriter);
- ``serve``     — ``tasks.task6_serve`` at the same widths, dense and
                  paged+prefix-sharing, which must generate identical tokens.

``--multichip`` (FOUR chips) runs the device check and then only
``tasks.task5_longcontext`` at the flagship widths under dp, fsdp and tp
against the same arguments and seed on one chip.

Every phase prints one JSON line; the LAST line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Nothing here catches a phase's failure or falls back to the CPU: a phase
that fails raises, and the process exits non-zero without that line. One
process, no children — a chip belongs to one process at a time. The seconds
printed are smoke timings for orientation, NOT a benchmark.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

# The 12-layer, d 1024 grouped-query LM of the round-5 chip runs (2026-07-31).
FLAGSHIP = dict(vocab_size=32768, embed_dim=1024, num_heads=8, num_layers=12,
                num_kv_heads=2)
SEQ_LEN, BATCH = 2048, 8

# Fused-vs-plain parity of ONE step in bf16 compute: both paths round every
# matmul operand to bf16 (8 mantissa bits) but in different op orders
# (flash tiles vs one softmax, fused LN vs two passes, fused head vs
# materialized logits), so agreement is a few bf16 ulps, not f32 parity.
# The gradient tolerance bounds both the norms' difference and the norm of
# the difference (the norms alone can agree while the directions do not).
PARITY_LOSS_RTOL = 1e-2
PARITY_GNORM_RTOL = 5e-2
# Sharded engines vs one chip, loss after every step — the tolerance the
# repo's own multi-step engine parity uses on the CPU mesh
# (tests/test_fsdp.py: fsdp vs dp vs single, rtol=1e-4).
MULTICHIP_LOSS_RTOL = 1e-4
# ResNet-18 on the 10-class synthetic set: chance is 0.10.
CLI_MIN_ACCURACY = 0.30

_compile_seconds = 0.0


def _on_jax_duration(event: str, duration: float, **_) -> None:
    """Sums what JAX itself reports spending in the backend compiler — or,
    on a persistent-cache hit, fetching the executable. Tracing and
    lowering are not counted: their events nest (an outer trace contains
    the inner jits' traces), so their sum can exceed the wall clock."""
    global _compile_seconds
    if event == "/jax/core/compile/backend_compile_duration":
        _compile_seconds += duration


class PhaseClock:
    """Wall seconds of a phase, split into backend compile (``compile_s``)
    and everything else (``warm_s``: trace, lowering, data, execution)."""

    def __enter__(self):
        self._t0, self._c0 = time.perf_counter(), _compile_seconds
        return self

    def __exit__(self, *exc):
        total = time.perf_counter() - self._t0
        self.compile_s = _compile_seconds - self._c0
        self.warm_s = total - self.compile_s

    def fields(self) -> dict:
        return {"compile_s": round(self.compile_s, 2),
                "warm_s": round(self.warm_s, 2)}


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def require(condition, message) -> None:
    """A check of the smoke (not an ``assert``: those vanish under -O)."""
    if not condition:
        raise RuntimeError(f"chip_smoke check failed: {message}")


def kernels_in_program(compiled_text: str) -> bool:
    """The Pallas kernels are in the compiled program, not their
    reference-math fallbacks (``jax.default_backend() != "tpu"`` dispatch)."""
    return "tpu_custom_call" in compiled_text


# ---------------------------------------------------------------- device


def device_phase(want_count: int | None) -> dict:
    """Fail first, before any model exists, unless JAX sees a TPU (and,
    for ``--multichip``, four of them)."""
    dev = jax.devices()
    info = {"platform": dev[0].platform, "kind": dev[0].device_kind,
            "count": len(dev)}
    if info["platform"] != "tpu" or (want_count and len(dev) != want_count):
        print(f"chip_smoke: need {want_count or 1} TPU device(s), JAX found "
              f"{info}", file=sys.stderr)
        emit({"ok": False, "device": info})
        sys.exit(1)

    import importlib.metadata

    import jaxlib

    import tpudml.native
    from tpudml.core.compile_cache import enable_compile_cache

    emit({"phase": "device", **info, "jax": jax.__version__,
          "jaxlib": jaxlib.__version__,
          "libtpu": importlib.metadata.version("libtpu"),
          "native_data_plane": tpudml.native.available(),
          "compile_cache_dir": enable_compile_cache()})
    return info


# -------------------------------------------------------------- train_lm


def _flagship_lm(cfg: dict, seq_len: int, fused: bool):
    from tpudml.models import TransformerLM

    return TransformerLM(
        **cfg, max_len=seq_len, impl="flash" if fused else "full", rope=True,
        compute_dtype=jnp.bfloat16, fused_ln=fused,
    )


def _lm_batch(cfg: dict, batch: int, seq_len: int):
    from tpudml.data.datasets import synthetic_lm

    seqs = jnp.asarray(synthetic_lm(batch, seq_len, cfg["vocab_size"], seed=1))
    return seqs[:, :-1], seqs[:, 1:]


def _one_sgd_step(model, fused: bool, x, y):
    """(loss, parameter update) of one public train step under plain
    SGD at lr=1 — the update IS the gradient, so the public steps can be
    compared on gradients without reaching inside them."""
    from tpudml.core.prng import seed_key
    from tpudml.optim import make_optimizer
    from tpudml.train import (
        TrainState,
        make_lm_fused_train_step,
        make_train_step,
    )

    opt = make_optimizer("sgd", 1.0)
    step = (make_lm_fused_train_step(model, opt, save_scores=True) if fused
            else make_train_step(model, opt))
    ts = TrainState.create(model, opt, seed_key(0))
    before = jax.tree.map(jnp.copy, ts.params)  # the step donates ts
    ts, metrics = step(ts, x, y)
    grads = jax.tree.map(jnp.subtract, before, ts.params)
    return float(metrics["loss"]), grads


def _global_norm(tree) -> float:
    from tpudml.obs.stepstats import grad_normsq

    return float(jnp.sqrt(grad_normsq(tree)))


def train_lm_phase(cfg: dict = FLAGSHIP, seq_len: int = SEQ_LEN,
                   batch: int = BATCH, steps: int = 8,
                   parity_batch: int = 2) -> dict:
    """The flagship model under the fused train step, with a fused-vs-plain
    parity check of one step."""
    from tpudml.core.prng import seed_key
    from tpudml.optim import make_optimizer
    from tpudml.train import TrainState, make_lm_fused_train_step

    with PhaseClock() as clock:
        model = _flagship_lm(cfg, seq_len, fused=True)
        opt = make_optimizer("adamw", 3e-4)
        x, y = _lm_batch(cfg, batch, seq_len)
        step = make_lm_fused_train_step(model, opt, save_scores=True)
        ts = TrainState.create(model, opt, seed_key(0))

        compiled = step.lower(ts, x, y).compile()
        require(kernels_in_program(compiled.as_text()),
                "no tpu_custom_call in the compiled train step: the kernels' "
                "reference fallbacks were compiled instead")
        mem = compiled.memory_analysis()

        losses, step_s = [], []
        for _ in range(steps):
            t0 = time.perf_counter()
            ts, metrics = compiled(ts, x, y)  # donated: rebind
            losses.append(float(metrics["loss"]))  # host fetch = device sync
            step_s.append(time.perf_counter() - t0)
        require(all(np.isfinite(losses)), f"non-finite loss in {losses}")
        require(losses[-1] < losses[0], f"loss did not fall: {losses}")
        # Free the training state before the next program: a compile's
        # memory_analysis() counts one program, not what the process holds.
        del ts, compiled, metrics
        gc.collect()

        xp, yp = x[:parity_batch], y[:parity_batch]
        loss_f, grads_f = _one_sgd_step(model, True, xp, yp)
        loss_p, grads_p = _one_sgd_step(
            _flagship_lm(cfg, seq_len, fused=False), False, xp, yp)
        gn_f, gn_p = _global_norm(grads_f), _global_norm(grads_p)
        diff = _global_norm(jax.tree.map(jnp.subtract, grads_f, grads_p))
        np.testing.assert_allclose(loss_f, loss_p, rtol=PARITY_LOSS_RTOL)
        np.testing.assert_allclose(gn_f, gn_p, rtol=PARITY_GNORM_RTOL)
        require(diff <= PARITY_GNORM_RTOL * gn_p,
                f"|g_fused - g_plain| = {diff} > {PARITY_GNORM_RTOL} * {gn_p}")
        del grads_f, grads_p
        gc.collect()

    record = {
        "phase": "train_lm", "ok": True, **clock.fields(),
        "config": {**cfg, "seq_len": seq_len, "batch": batch},
        "steps": steps, "losses": [round(v, 4) for v in losses],
        "median_step_s_not_a_benchmark": round(float(np.median(step_s[1:])), 4),
        "program_bytes": {
            "arguments": mem.argument_size_in_bytes,
            "temporaries": mem.temp_size_in_bytes,
        } if mem is not None else None,
        "checked": {
            "finite_falling_loss": True,
            "tpu_custom_call_in_compiled_step": True,
            "fused_vs_plain_at_batch": parity_batch,
            "loss_fused": round(loss_f, 5), "loss_plain": round(loss_p, 5),
            "loss_rtol": PARITY_LOSS_RTOL,
            "grad_norm_fused": round(gn_f, 5), "grad_norm_plain": round(gn_p, 5),
            "grad_norm_rtol": PARITY_GNORM_RTOL,
            "grad_diff_over_plain_norm": round(diff / gn_p, 5),
        },
    }
    emit(record)
    return record


# ------------------------------------------------------------- train_cli

# The r05 ResNet row's per-chip batch. Five epochs of the 4096-sample
# synthetic set = 20 optimizer steps: after one epoch (4 steps) BatchNorm's
# running statistics have not converged and eval accuracy is still chance.
CLI_ARGV = ["--model", "resnet18", "--dataset", "synthetic", "--epochs", "5",
            "--batch_size", "1024", "--log_every", "0"]


def train_cli_phase(argv: list[str] = CLI_ARGV) -> dict:
    """The north-star trainer exactly as a user calls it."""
    import tasks.north_star

    with PhaseClock() as clock:
        metrics = tasks.north_star.main(argv)
    loss, acc = float(metrics["loss"]), float(metrics["test_accuracy"])
    require(np.isfinite(loss) and np.isfinite(acc), f"non-finite {metrics}")
    require(acc >= CLI_MIN_ACCURACY,
            f"test accuracy {acc:.3f} < {CLI_MIN_ACCURACY} (chance is 0.10)")
    record = {
        "phase": "train_cli", "ok": True, **clock.fields(), "argv": argv,
        "steps": int(metrics["steps"]), "world": int(metrics["world"]),
        "checked": {"final_loss": round(loss, 4),
                    "test_accuracy": round(acc, 4),
                    "min_accuracy": CLI_MIN_ACCURACY},
    }
    emit(record)
    return record


# ----------------------------------------------------------------- serve

# Prompts of 300–1000 tokens in 256-token chunks: chunked flash prefill
# compiles chunk indices 0..3. prefix_sharing needs page_size to be a
# multiple of prefill_chunk. No --step_time_s: the engine's real clock.
SERVE_ARGV = [
    "--vocab", "32768", "--embed_dim", "1024", "--num_heads", "8",
    "--num_kv_heads", "2", "--num_layers", "12", "--max_len", "2048",
    "--cache_kind", "bf16", "--slots", "8", "--prefill_chunk", "256",
    "--n_requests", "12", "--qps", "inf", "--prompt_len", "300", "1000",
    "--new_tokens", "24", "48", "--seed", "0",
]
SERVE_PAGED = ["--paged", "--page_size", "256", "--prefix_sharing"]


def serve_phase(argv: list[str] = SERVE_ARGV,
                paged: list[str] = SERVE_PAGED) -> dict:
    """The serving engine through its task entry point, dense then paged;
    ``run()`` itself asserts token accounting against the ledger."""
    import tasks.task6_serve

    arms = {}
    with PhaseClock() as clock:
        for name, extra in (("dense", []), ("paged", paged)):
            with PhaseClock() as arm_clock:
                out = tasks.task6_serve.main(argv + extra)
            require(out["generated_tokens"] > 0 and out["decode_steps"] > 0
                    and np.isfinite(out["tokens_per_sec"])
                    and out["e2e_p99_s"] > 0,
                    f"{name} arm served nothing: {out}")
            arms[name] = (out, arm_clock.fields())
            gc.collect()
    dense, paged_out = arms["dense"][0], arms["paged"][0]
    require(dense["tokens"] == paged_out["tokens"],
            "dense and paged engines generated different tokens for one seed")
    record = {
        "phase": "serve", "ok": True, **clock.fields(),
        "argv": argv, "paged_argv": paged,
        "arms": {
            name: {**fields, "generated_tokens": out["generated_tokens"],
                   "decode_steps": out["decode_steps"],
                   "wall_s_engine_clock": round(
                       out["generated_tokens"] / out["tokens_per_sec"], 3)}
            for name, (out, fields) in arms.items()
        },
        "checked": {"token_accounting": True, "dense_equals_paged_tokens": True,
                    "requests": len(dense["tokens"]),
                    "pool_stats": paged_out["pool_stats"]},
    }
    emit(record)
    return record


# ------------------------------------------------------------- multichip

# task5 computes in f32 (it has no compute-dtype flag): about twice the
# bf16 activations, so the global batch is 4, not 8 — what the single-chip
# arm fits — and every arm uses the same batch.
MULTICHIP_ARGV = [
    "--vocab", "32768", "--embed_dim", "1024", "--num_heads", "8",
    "--num_kv_heads", "2", "--num_layers", "12", "--seq_len", "2048",
    "--batch_size", "4", "--attn", "flash", "--fused_ln", "--fused_xent",
    "--rope", "--steps", "5", "--log_every", "1", "--lr", "3e-4",
    "--seed", "0",
]


def device_bytes_in_use() -> list[int]:
    return [d.memory_stats()["bytes_in_use"] for d in jax.devices()]


def _param_spread(train_state) -> int:
    """Most devices any one parameter leaf is placed on."""
    return max(len(leaf.sharding.device_set)
               for leaf in jax.tree.leaves(train_state.params))


def multichip_phase(argv: list[str] = MULTICHIP_ARGV,
                    n_devices: int = 4) -> dict:
    """dp / fsdp / tp over ``n_devices`` chips against one chip: same
    arguments, same seed, the loss after every step."""
    import tasks.task5_longcontext

    arms = {}
    for parallel in ("single", "dp", "fsdp", "tp"):
        with PhaseClock() as clock:
            out = tasks.task5_longcontext.main(
                argv + ["--parallel", parallel, "--n_devices", str(n_devices)])
        losses = [loss for _, loss in out["loss_history"]]
        require(losses and all(np.isfinite(losses)),
                f"--parallel {parallel}: losses {losses}")
        in_use = device_bytes_in_use()  # while out["train_state"] is alive
        arm = {**clock.fields(), "losses": losses,
               "param_leaf_max_devices": _param_spread(out["train_state"]),
               "bytes_in_use": in_use}
        if parallel != "single":
            np.testing.assert_allclose(
                losses, arms["single"]["losses"], rtol=MULTICHIP_LOSS_RTOL,
                err_msg=f"--parallel {parallel} vs single")
            require(all(b > 0 for b in in_use[:n_devices]),
                    f"--parallel {parallel}: a device holds nothing: {in_use}")
        if parallel in ("fsdp", "tp"):
            require(arm["param_leaf_max_devices"] == n_devices,
                    f"--parallel {parallel}: no parameter leaf spans "
                    f"{n_devices} devices")
        arms[parallel] = arm
        emit({"phase": f"multichip:{parallel}", "ok": True, **arm})
        del out
        gc.collect()
    record = {
        "phase": "multichip", "ok": True, "argv": argv, "n_devices": n_devices,
        "global_batch": int(argv[argv.index("--batch_size") + 1]),
        "checked": {"loss_rtol_vs_single": MULTICHIP_LOSS_RTOL,
                    "arms": ["dp", "fsdp", "tp"],
                    "fsdp_tp_params_on_all_devices": True,
                    "all_devices_hold_memory": True},
    }
    emit(record)
    return record


# ------------------------------------------------------------------ main


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument(
        "--multichip", action="store_true",
        help="four chips: task5 under dp/fsdp/tp against one chip, and "
        "no other phase")
    args = parser.parse_args(argv)

    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
    info = device_phase(4 if args.multichip else None)
    if args.multichip:
        multichip_phase()
    else:
        train_lm_phase()
        train_cli_phase()
        serve_phase()
    emit({"ok": True, "device": info})


if __name__ == "__main__":
    main()
