"""Training driver: the program's `train_loop` fed by the program's loader
and prefetch, at a configuration's sizes, around the fused single-chip step
(`make_lm_fused_train_step`). Another engine is another driver file.

One object (the jitted step with its state) is built in set-up, driven
through its first steps by `train_loop` (those steps are compared with the
reference after the window), and handed to the window's `train_loop` call.
"""

from __future__ import annotations

import gc
import importlib
import statistics
import time

import numpy as np

from benchmarks import compare, counts, tracing
from benchmarks.drivers import lm_adapter
from benchmarks.reference import gpt2


# A leaf whose first gradient (reference) is under this share of the median
# leaf's takes no part in the comparison of the parameters' change.
DEAD_GRADIENT = 1e-3


class Feed:
    """What `train_loop` iterates: the program's DataLoader behind the
    program's `prefetch_to_device`. The benchmark's part is to end the
    iterator (after `max_steps`, or when the window's seconds are over), to
    time each `next()`, to keep the first host batches for the reference,
    and to hold the host no more than `in_flight` steps ahead of the device
    (so that the window closes within a step or two of its length)."""

    def __init__(self, loader, prefetch: int, in_flight: int, keep: int):
        self.loader, self.prefetch = loader, prefetch
        self.in_flight, self.keep = in_flight, keep
        self.kept: list = []      # first host batches, for the reference
        self.waits: list = []     # seconds inside next(), one per step
        self.served_at: list = []  # host clock when each batch was handed over
        self.losses: list = []    # device scalars, appended by the hook
        self._max_steps = self._deadline = None
        self._served = 0

    def arm(self, max_steps: int | None = None, seconds: float | None = None):
        self._max_steps, self._served = max_steps, 0
        self._deadline = None if seconds is None else time.perf_counter() + seconds
        self.waits, self.served_at = [], []

    def set_epoch(self, epoch: int) -> None:
        self.loader.set_epoch(epoch)

    def _over(self) -> bool:
        if self._max_steps is not None and self._served >= self._max_steps:
            return True
        return self._deadline is not None and time.perf_counter() >= self._deadline

    def _tap(self):
        for batch in self.loader:
            if len(self.kept) < self.keep:
                self.kept.append(batch)
            yield batch

    def __iter__(self):
        from tpudml.data.prefetch import prefetch_to_device

        if self._over():
            return
        it = prefetch_to_device(self._tap(), size=self.prefetch)
        while not self._over():
            if len(self.losses) > self.in_flight:
                with tracing.span("wait_for_device"):
                    self.losses[-self.in_flight - 1].block_until_ready()
            t0 = time.perf_counter()
            with tracing.span("loader.next"):
                try:
                    batch = next(it)
                except StopIteration:
                    return
            self.served_at.append(time.perf_counter())
            self.waits.append(self.served_at[-1] - t0)
            self._served += 1
            yield batch


def _build(cell, seed: int):
    """(model, optimizer, step_fn, seeded state)."""
    import jax
    import jax.numpy as jnp
    from tpudml.optim.optimizers import AdamW
    from tpudml.train import TrainState, make_lm_fused_train_step

    cfg, spec = cell.config, cell.spec
    model = lm_adapter.build_model(cfg, spec["model"])
    hp = spec["optimizer"]
    opt = AdamW(lr=hp["lr"], b1=hp["b1"], b2=hp["b2"], eps=hp["eps"],
                weight_decay=hp["weight_decay"])
    dtype = lm_adapter.param_dtype(spec["model"])
    n_layer = cfg["n_layer"]

    def seeded_state(key):
        params = lm_adapter.to_program(gpt2.init_weights(cfg, key, dtype), n_layer)
        return TrainState(params=params, model_state={},
                          opt_state=opt.init(params), step=jnp.zeros((), jnp.int32))

    step = make_lm_fused_train_step(model, opt, save_scores=spec["engine"].get("save_scores"))
    return model, opt, step, jax.jit(seeded_state)(gpt2.seed_key(seed))


def _leaf_norms(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree.map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), tree)


class Program:
    """The one object of a run: the jitted step with its state, the feed, and
    what the comparison reads of the first steps. Set-up builds it and drives
    it through `first_steps`; the window gets the same object."""

    def __init__(self, cell, seed: int):
        import jax
        import jax.numpy as jnp
        from tpudml.data.datasets import ArrayDataset
        from tpudml.data.loader import DataLoader
        from tpudml.data.sampler import RandomPartitionSampler

        self.cell, self.seed = cell, seed
        cfg, spec, traffic = cell.config, cell.spec, cell.traffic
        self.check, self.loop = spec["check"], spec["loop"]
        data = importlib.import_module(
            f"benchmarks.traffic.{traffic['generator']}").make(traffic, cfg, seed)
        dataset = ArrayDataset(images=data["inputs"], labels=data["targets"], name="lm")
        sampler = RandomPartitionSampler(
            len(dataset), shuffle=traffic.get("shuffle", True), seed=seed % (2 ** 31))
        loader = DataLoader(dataset, traffic["batch"], sampler)
        self.model, self.opt, self.step_fn, self.state = _build(cell, seed)
        self.feed = Feed(loader, self.loop["prefetch"], self.loop["in_flight"],
                         keep=self.check["steps"])
        n_layer, dtype = cfg["n_layer"], lm_adapter.param_dtype(spec["model"])
        # Read between steps, from the state the step returns: the optimizer's
        # first moment after one step is (1 - b1) x the first gradient as the
        # optimizer got it; the parameters' change is against the seeded start.
        self._first_grad = jax.jit(lambda ts: _leaf_norms(ts.opt_state["m"]))
        self._change = jax.jit(lambda ts, key: _leaf_norms(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), ts.params,
            lm_adapter.to_program(gpt2.init_weights(cfg, key, dtype), n_layer))))
        self._seen: dict = {}
        self.window_trace = None
        self._window_from = None

    def _hook(self, *, epoch, step, train_state, metrics):
        import jax

        self.feed.losses.append(metrics["loss"])
        if step == 1:
            self._seen["first_grad"] = self._first_grad(train_state)
        if step == self.check["steps"]:
            self._seen["change"] = self._change(train_state, gpt2.seed_key(self.seed))
        if self.window_trace is not None and self._window_from is not None:
            at = self.cell.spec["trace"]
            done = len(self.feed.losses) - self._window_from
            if done == at["start_step"]:
                self.window_trace.start()
            elif done == at["start_step"] + at["steps"]:
                jax.block_until_ready(metrics["loss"])
                self.window_trace.stop()

    def _loop(self):
        import jax
        from tpudml.train import train_loop

        self.state, last = train_loop(
            self.model, self.opt, self.feed, num_epochs=self.loop["epochs"],
            key=jax.random.key(0), log_every=self.loop["log_every"],
            step_fn=self.step_fn, state=self.state, hooks=[self._hook])
        return last

    def first_steps(self) -> dict:
        """Drive the first steps through `train_loop` and the feed; returns
        what the comparison needs of the program, as host floats."""
        import jax

        n, b1 = self.check["steps"], self.cell.spec["optimizer"]["b1"]
        n_layer = self.cell.config["n_layer"]
        self.feed.arm(max_steps=n + self.loop["warm_steps"])
        self._loop()
        flat = lambda tree: lm_adapter.from_program(jax.device_get(tree), n_layer)  # noqa: E731
        return {
            "losses": [float(x) for x in self.feed.losses[:n]],
            "first_grad_norms": {k: float(v) / (1.0 - b1)
                                 for k, v in flat(self._seen["first_grad"]).items()},
            "change_norms": {k: float(v) for k, v in flat(self._seen["change"]).items()},
            "batches": self.feed.kept[:n],
        }

    def window(self, seconds: float, trace_window=None) -> dict:
        import jax

        self._window_from = len(self.feed.losses)
        self.window_trace = trace_window
        with tracing.span("train_loop"):
            self.feed.arm(seconds=seconds)
            t0 = time.perf_counter()
            last = self._loop()
            elapsed = time.perf_counter() - t0
        if trace_window is not None:
            trace_window.stop()
        losses = np.asarray(jax.device_get(self.feed.losses[self._window_from:]),
                            np.float64)
        return {"t0": t0, "elapsed_s": elapsed, "losses": losses,
                "waits_s": list(self.feed.waits),
                "step_intervals_s": np.diff(self.feed.served_at),
                "train_time_s": last.get("train_time_s")}

    def free(self) -> None:
        self.state = self.step_fn = self._first_grad = self._change = None
        self._seen.clear()
        self.feed.losses.clear()
        gc.collect()


def reference(cell, seed: int, batches, store_dtype: str = "float32") -> dict:
    """The plain reference's numbers for the same first steps. A
    ``store_dtype`` below float32 is the control
    (`check.control.reference_store_dtype`)."""
    return gpt2.train_reference(cell.config, cell.spec["optimizer"], seed, batches,
                                rows_per_block=cell.spec["check"]["rows_per_block"],
                                store_dtype=store_dtype)


def judge(cell, program: dict, ref: dict, window_losses=None) -> compare.Verdict:
    limits = cell.spec["check"]["limits"]
    verdict = compare.Verdict()
    for i, (lp, lr) in enumerate(zip(program["losses"], ref["losses"]), start=1):
        verdict.add(f"loss_gap.step{i}", compare.relative_gap(lp, lr),
                    limits["loss_gap"], f"program {lp:.6f} reference {lr:.6f}")
    gap, leaf = compare.worst_leaf_gap(program["first_grad_norms"], ref["first_grad_norms"])
    verdict.add("first_grad_gap", gap, limits["first_grad_gap"],
                f"worst leaf {leaf}: program {program['first_grad_norms'][leaf]:.4g} "
                f"reference {ref['first_grad_norms'][leaf]:.4g}")
    # Adam turns the rounding noise of a gradient that is zero by the
    # mathematics (a key bias: softmax does not see it) into full-size steps,
    # so such a leaf's change is noise in the program and in the reference.
    grads = ref["first_grad_norms"]
    floor = DEAD_GRADIENT * statistics.median(grads.values())
    live = [k for k, g in grads.items() if g >= floor]
    gap, leaf = compare.worst_leaf_gap({k: program["change_norms"][k] for k in live},
                                       {k: ref["change_norms"][k] for k in live})
    verdict.add("param_change_gap", gap, limits["param_change_gap"],
                f"worst leaf {leaf}; {len(grads) - len(live)} of {len(grads)} leaves "
                f"left out (first gradient under {DEAD_GRADIENT:g} of the median leaf's)")
    if window_losses is not None:
        bad = int(np.sum(~np.isfinite(window_losses)))
        verdict.add("nonfinite_steps", float(bad), 0.0,
                    f"of {len(window_losses)} window steps")
    return verdict


def run(cell, seed: int, seconds: float, trace: bool, devices, started: float,
        trace_dir: str) -> dict:
    from benchmarks import device as dev

    cfg, traffic = cell.config, cell.traffic
    batch, seq_len = traffic["batch"], traffic["seq_len"]
    program = Program(cell, seed)
    first = program.first_steps()
    gc.collect()
    out = program.window(seconds, tracing.TraceWindow(trace_dir) if trace else None)
    setup_s = out["t0"] - started
    memory_peak = dev.memory_peak_bytes(devices)
    program.free()

    t_ref = time.perf_counter()
    ref = reference(cell, seed, first["batches"])
    reference_s = time.perf_counter() - t_ref
    verdict = judge(cell, first, ref, out["losses"])

    steps, elapsed, waits = len(out["losses"]), out["elapsed_s"], out["waits_s"]
    failed = int(np.sum(~np.isfinite(out["losses"])))
    step_flops = counts.train_step_flops(cfg, batch, seq_len)
    on_tpu = devices[0].platform == "tpu"
    return {
        "verdict": verdict, "attempted": steps, "failed": failed,
        "end_to_end": {"train.tokens_per_s": steps * batch * seq_len / elapsed,
                       "setup_s": setup_s},
        "memory_peak_bytes": memory_peak,
        "host": {"loader_waits_s": waits, "step_flops": step_flops},
        "info": {
            "steps": steps, "elapsed_s": elapsed,
            "train_loop_time_s": out["train_time_s"],
            "step_ms_mean": 1e3 * elapsed / max(steps, 1),
            "loss_first": first["losses"],
            "loss_last": float(out["losses"][-1]) if steps else None,
            "loader_wait_ms_median": 1e3 * float(np.median(waits)) if waits else None,
            # a stall of the host shows as one long interval between two batches
            "step_interval_ms_max": 1e3 * float(np.max(out["step_intervals_s"]))
            if steps > 1 else None,
            "step_intervals_over_1p5x_median": int(np.sum(
                out["step_intervals_s"] > 1.5 * np.median(out["step_intervals_s"])))
            if steps > 1 else None,
            "mfu_end_to_end_pct": 100 * step_flops * steps / elapsed / (
                len(devices) * dev.peaks(devices[0].device_kind)["bf16_flops_per_s"])
            if on_tpu else None,
            "reference_s": reference_s,
            "reference_step_s": ref["step_seconds"]},
    }
