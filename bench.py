"""Benchmark entrypoint (driver contract: prints ONE JSON line).

Headline = the north-star metric (BASELINE.json): steady-state CIFAR-10
ResNet-18 training throughput in images/sec/chip, bfloat16 compute on the
MXU. A transformer-LM tokens/sec/chip secondary metric (task5's flagship
model, flash attention on TPU) tracks the sequence workload too.

Three timing protocols (VERDICT round 2, item 1 — the honest clock):

- ``fori`` (HEADLINE): K train steps inside ONE XLA dispatch via
  ``lax.fori_loop``; the device cannot elide or overlap them, and the
  measurement syncs by fetching the final loss to the host (a
  device->host copy cannot complete before the value exists). Per-step
  time is differenced between two trip counts, which cancels dispatch +
  transfer overhead. This is the artifact-proof number: its MFU must be
  <= 1.0 on working hardware.
- ``synced``: one dispatch per step, host-fetching the loss every step.
  Includes per-step dispatch/transfer latency — the lower bound a naive
  eager-style loop would see.
- ``pipelined`` (legacy, rounds 1-2 protocol): chained donated-state
  dispatches, sync once at the end — kept only for continuity with prior
  recordings; ``mfu_pipelined_artifact`` flags it independently when it
  exceeds peak.

Every timing ends in a device sync: the host fetches a value the timed
work produced.

``mfu`` = flops_per_step (XLA compiled cost analysis of the single-chip
step) / sec_per_step(fori) / chip bf16 peak.
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp

# bf16 peak FLOP/s per chip by device kind (public spec sheets).
_PEAK_BF16 = {
    "v4": 275e12,
    "v5 lite": 197e12,  # v5e
    "v5e": 197e12,
    "v5p": 459e12,
    "v6": 918e12,  # Trillium
}


def _peak_flops(device) -> float | None:
    kind = getattr(device, "device_kind", "").lower()
    for key, peak in _PEAK_BF16.items():
        if key in kind:
            return peak
    return None


def _compiled_flops(fn, *args) -> float | None:
    """FLOPs of one call from XLA's cost analysis (None if unavailable).
    ``fn`` may already be jitted (lowered directly — nothing executes, so
    donated arguments are safe to pass)."""
    try:
        if not hasattr(fn, "lower"):
            fn = jax.jit(fn)
        cost = fn.lower(*args).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        flops = float(cost.get("flops", 0.0))
        return flops or None
    except Exception:
        return None


def _fetch(x) -> float:
    """Host materialization as the sync barrier: a device->host copy of
    the value cannot complete before the device has produced it."""
    return float(jax.device_get(x))


def _make_step_body(model, optimizer):
    """(ts, images, labels) -> (new_ts, loss): the real training step body
    (shared with make_train_step, so the bench times what training runs)."""
    from tpudml.train import make_train_step_body

    step = make_train_step_body(model, optimizer)

    def body(ts, images, labels):
        new_ts, metrics = step(ts, images, labels)
        return new_ts, metrics["loss"]

    return body


def _time_fori(body, ts, batch, k_lo, k_hi, reps=3):
    """Artifact-proof seconds/step: run K steps inside ONE dispatch, sync by
    fetching the final loss, difference two trip counts to cancel the
    constant dispatch + transfer overhead. ``k`` is a dynamic argument so
    both trip counts share one compiled program.

    Returns ``(median, runs)``: the whole differencing is repeated
    ``reps`` times and the MEDIAN is the headline, so a single noisy rep
    can neither inflate nor deflate the recorded number (VERDICT r3
    item 7 — r3 shipped a below-pin artifact from a one-shot run while
    BASELINE.md carried a better best-of-round); ``runs`` lets the
    artifact record the spread."""
    import statistics

    @jax.jit
    def run(ts, images, labels, k):
        def one(_, carry):
            ts, _ = carry
            return body(ts, images, labels)

        return jax.lax.fori_loop(0, k, one, (ts, jnp.zeros((), jnp.float32)))

    images, labels = batch

    def timed(k) -> float:
        t0 = time.perf_counter()
        _, loss = run(ts, images, labels, k)
        _fetch(loss)
        return time.perf_counter() - t0

    timed(2)  # compile + warm
    runs = []
    for _ in range(reps):
        # Symmetric sampling (min of 2 each) so a one-off host hiccup on
        # either trip count cannot bias or sign-flip the difference.
        t_lo = min(timed(k_lo) for _ in range(2))
        t_hi = min(timed(k_hi) for _ in range(2))
        if t_hi <= t_lo:
            # Degenerate measurement (jitter swamped the spread): fall
            # back to the k_hi run including overhead — an upper bound on
            # sec/step, never a garbage near-zero headline.
            runs.append(t_hi / k_hi)
        else:
            runs.append((t_hi - t_lo) / (k_hi - k_lo))
    return statistics.median(runs), runs


def _time_synced(step, ts, batch, iters):
    """One dispatch per step, host sync (loss fetch) every step. ``step``
    is a (ts, *batch) -> (ts, loss) body (jitted or not)."""
    for _ in range(3):
        ts, loss = step(ts, *batch)
        _fetch(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        ts, loss = step(ts, *batch)
        _fetch(loss)
    return (time.perf_counter() - t0) / iters


def _time_pipelined(step, ts, batch, iters):
    """Rounds 1-2 protocol: chained donated-state dispatches, one sync at
    the end (see module docstring) — NOT the headline."""
    for _ in range(3):
        ts, m = step(ts, *batch)
    jax.block_until_ready(m["loss"])
    t0 = time.perf_counter()
    for _ in range(iters):
        ts, m = step(ts, *batch)
    jax.block_until_ready(m["loss"])
    return (time.perf_counter() - t0) / iters


def _mfu_fields(flops_per_step, sec_fori, sec_synced, sec_pipelined, peak,
                fori_runs=None):
    fields = {
        "sec_per_step": round(sec_fori, 6),
        "sec_per_step_synced": round(sec_synced, 6),
        "sec_per_step_pipelined": round(sec_pipelined, 6),
        "protocol": "fori",
    }
    if fori_runs:
        # Median-of-N protocol (VERDICT r3 item 7): publish the spread so
        # the artifact itself shows whether a delta is signal or jitter.
        fields["sec_per_step_runs"] = [round(s, 6) for s in sorted(fori_runs)]
        fields["fori_spread"] = round(
            (max(fori_runs) - min(fori_runs)) / sec_fori, 4
        )
    if flops_per_step and peak:
        mfu = flops_per_step / sec_fori / peak
        mfu_pipe = flops_per_step / sec_pipelined / peak
        fields.update(
            flops_per_step=round(flops_per_step),
            mfu=round(mfu, 4),
            # The fori protocol cannot exceed peak on working hardware; a
            # True here means the measurement itself is broken.
            mfu_artifact=bool(mfu > 1.0),
            mfu_pipelined=round(mfu_pipe, 4),
            # Flagged independently of the headline.
            mfu_pipelined_artifact=bool(mfu_pipe > 1.0),
        )
    return fields


def bench_resnet(on_tpu: bool, n_devices: int) -> dict:
    from tpudml.core.config import MeshConfig
    from tpudml.core.dist import make_mesh
    from tpudml.core.prng import seed_key
    from tpudml.data.datasets import synthetic_classification
    from tpudml.models import ResNet18
    from tpudml.optim import make_optimizer
    from tpudml.parallel.dp import DataParallel
    from tpudml.train import TrainState

    # 1024/chip keeps the MXU fed and amortizes dispatch; fits v5e HBM
    # comfortably for CIFAR-sized inputs. CPU dev mode stays tiny: XLA CPU
    # executes conv bodies inside while-loops ~25x slower than the plain
    # step (observed 30.8 vs 1.25 s/step at batch 16), so the fori smoke
    # must be minimal there.
    per_chip_batch = 1024 if on_tpu else 8
    batch = per_chip_batch * n_devices
    images, labels = synthetic_classification(batch, (32, 32, 3), 10, seed=0)
    images, labels = jnp.asarray(images), jnp.asarray(labels)

    model = ResNet18(compute_dtype=jnp.bfloat16 if on_tpu else jnp.float32)
    opt = make_optimizer("sgd", 0.1, momentum=0.9)

    # Headline clock: single-chip step body under fori (what imgs/sec/CHIP
    # and MFU measure; the DP collective is timed by the pipelined path).
    chip_batch = (images[:per_chip_batch], labels[:per_chip_batch])
    body = _make_step_body(model, opt)
    ts0 = TrainState.create(model, opt, seed_key(0))
    sec_fori, fori_runs = _time_fori(
        body, ts0, chip_batch,
        *((8, 40) if on_tpu else (1, 3)), reps=3 if on_tpu else 1,
    )

    step1 = jax.jit(body)
    sec_synced = _time_synced(step1, ts0, chip_batch, 10 if on_tpu else 2)

    mesh = make_mesh(MeshConfig(axes={"data": n_devices}), jax.devices())
    dp = DataParallel(model, opt, mesh, stacked_batches=False)
    sec_pipe = _time_pipelined(
        dp.make_train_step(), dp.create_state(seed_key(0)),
        (images, labels), 30 if on_tpu else 3,
    )

    # FLOPs from the single-chip step on the per-chip batch (what each
    # chip executes; collectives excluded, matching the per-chip metric).
    # ts0 is safe to pass: step1 does not donate and lowering executes
    # nothing.
    flops = _compiled_flops(step1, ts0, *chip_batch)
    return {
        # "_fori" names the protocol (ADVICE r3): the pre-r3 metric
        # "cifar10_resnet18_train_imgs_per_sec_per_chip" measured the
        # multi-device pipelined step and its history is NOT comparable
        # to this single-chip fori number.
        "metric": "cifar10_resnet18_train_imgs_per_sec_per_chip_fori",
        "value": round(per_chip_batch / sec_fori, 1),
        "unit": "imgs/sec/chip",
        "value_synced": round(per_chip_batch / sec_synced, 1),
        "value_pipelined": round(batch / sec_pipe / max(n_devices, 1), 1),
        **_mfu_fields(flops, sec_fori, sec_synced, sec_pipe,
                      _peak_flops(jax.devices()[0]), fori_runs),
    }


def _analytic_lm_flops(cfg, batch: int, seq_len: int) -> float:
    """Matmul-math FLOPs per train step of the decoder LM, counted
    analytically: XLA's cost analysis cannot see inside Pallas custom
    calls (flash attention, fused add+LN, fused linear-cross-entropy),
    so as more of the model moves into kernels the cost-analysis MFU
    silently DEFLATES (the fused-xent step dropped it to 0.26 while
    getting FASTER). Convention (PaLM-style strict matmul accounting):
    2 FLOP/MAC, backward = 2× forward (dX + dW), causal attention counts
    the ~half of the score/value matmuls actually computed, elementwise/
    norm/embedding-gather work excluded. GQA (``num_kv_heads``) shrinks
    the k/v projections to 2·d·(kv_heads·dh)."""
    d, L, V = cfg["embed_dim"], cfg["num_layers"], cfg["vocab_size"]
    # num_heads only matters under GQA (kv_heads < heads shrinks the k/v
    # projections); MHA callers (tools/ablate_lm.py) may omit both. A cfg
    # with kv_heads but no heads would silently inflate the k/v term under
    # the heads=1 fallback (dh would be d), so reject it loudly.
    heads = cfg.get("num_heads") or 1
    if cfg.get("num_kv_heads") and not cfg.get("num_heads"):
        raise ValueError("cfg sets num_kv_heads but not num_heads")
    kv_heads = cfg.get("num_kv_heads") or heads
    dh = d // heads
    tokens = batch * seq_len
    # Per layer: q d² + out-proj d² + k/v 2·d·(kv·dh) + fc1/fc2 2·4d²;
    # head d·V.
    per_layer = 2 * d * d + 2 * d * (kv_heads * dh) + 8 * d * d
    matmul_params = L * per_layer + d * V
    matmul = 6 * tokens * matmul_params
    # Full attention fwd 4·B·T²·d + bwd 8·B·T²·d = 12·B·T²·d; causal ≈ ½.
    # (GQA shares k/v across query heads — the score/value matmul FLOPs
    # are unchanged: every query head still contracts against T keys.)
    attn = 6 * L * batch * seq_len * seq_len * d
    return float(matmul + attn)


def bench_transformer(on_tpu: bool, large: bool = False) -> dict:
    """task5 flagship: decoder LM, flash attention on TPU, bf16, fused
    add+LN junctions, fused linear-cross-entropy head (save-scores speed
    mode) — the fastest exported train-step path.

    ``large=True`` is the chip-filling config (VERDICT r4 item 3): d=1024
    (8 heads × dh 128), L=12, GQA 4:1, T=2048 — ~218M params, 16k tokens
    per step, sized so the MXU sees big contractions and the 50%-MFU
    claim is tested at a scale that exercises HBM, not just caches."""
    from tpudml.core.prng import seed_key
    from tpudml.data.datasets import synthetic_lm
    from tpudml.models import TransformerLM
    from tpudml.optim import make_optimizer
    from tpudml.train import (
        TrainState,
        make_lm_fused_train_step,
        make_lm_fused_train_step_body,
    )

    if on_tpu and large:
        cfg = dict(vocab_size=32768, embed_dim=1024, num_heads=8,
                   num_layers=12, num_kv_heads=2)
        seq_len, batch = 2048, 8
    elif on_tpu:
        # head_dim 128 (4 heads at d=512), matching the MXU/VPU 128-lane
        # geometry: dh=64 half-fills the contraction dim of every
        # attention matmul and the lane dim of every Q/O tile (measured
        # 36.8 -> 25.4 ms/step on v5e, same parameter count and FLOPs).
        cfg = dict(vocab_size=32768, embed_dim=512, num_heads=4, num_layers=6)
        seq_len, batch = 1024, 8
    elif large:  # CPU smoke of the large path: GQA plumbing only
        cfg = dict(vocab_size=256, embed_dim=64, num_heads=4, num_layers=2,
                   num_kv_heads=2)
        seq_len, batch = 128, 4
    else:  # dev smoke on CPU: keep it seconds, not minutes
        cfg = dict(vocab_size=256, embed_dim=64, num_heads=4, num_layers=2)
        seq_len, batch = 128, 4
    model = TransformerLM(
        **cfg,
        max_len=seq_len,
        impl="flash" if on_tpu else "full",
        rope=True,
        # Master-weight mixed precision: f32 params (the optimizer state),
        # bf16 MXU compute, f32 norms/softmax/logits.
        compute_dtype=jnp.bfloat16 if on_tpu else None,
        # Fused residual-add+LN junction kernels: measured 20.88 →
        # 18.68 ms/step on v5e at this config (BASELINE.md round 4).
        fused_ln=on_tpu,
    )
    opt = make_optimizer("adamw", 3e-4)
    # synthetic_lm returns [n, seq_len+1] ALREADY (slice x/y from it) —
    # passing seq_len+1 here would train at T = seq_len+1, a block-
    # misaligned length that every flash kernel pads up per layer per
    # direction (the r1-r3 recordings did exactly that: T=1025).
    seqs = jnp.asarray(synthetic_lm(batch, seq_len, cfg["vocab_size"], seed=1))
    x, y = seqs[:, :-1], seqs[:, 1:]

    # The fused linear-cross-entropy head in save-scores speed mode:
    # measured 21.6 → 18.0 ms/step vs the materialized-logits step at
    # this config (BASELINE.md round 4). V=32k at B·T=8k fits the f32
    # score residual comfortably on-chip.
    fused_body = make_lm_fused_train_step_body(model, opt, save_scores=on_tpu)

    def body(ts, tokens_in, labels):
        new_ts, metrics = fused_body(ts, tokens_in, labels)
        return new_ts, metrics["loss"]

    ts0 = TrainState.create(model, opt, seed_key(0))
    sec_fori, fori_runs = _time_fori(
        body, ts0, (x, y),
        *((8, 40) if on_tpu else (1, 3)), reps=3 if on_tpu else 1,
    )

    step1 = jax.jit(body)
    sec_synced = _time_synced(step1, ts0, (x, y), 10 if on_tpu else 2)
    step = make_lm_fused_train_step(model, opt, save_scores=on_tpu)
    sec_pipe = _time_pipelined(
        step, TrainState.create(model, opt, seed_key(0)), (x, y),
        20 if on_tpu else 3,
    )
    # Analytic matmul FLOPs (docstring of _analytic_lm_flops: the Pallas
    # kernels hide their FLOPs from XLA's cost analysis); the XLA number
    # rides along for the record.
    flops = _analytic_lm_flops(cfg, batch, seq_len)
    flops_xla = _compiled_flops(step1, ts0, x, y)
    tokens = batch * seq_len
    return {
        # "_fori" versions the protocol (ADVICE r3), as for the headline.
        "metric": "transformer_lm_large_train_tokens_per_sec_per_chip_fori"
        if large else "transformer_lm_train_tokens_per_sec_per_chip_fori",
        "config": {**cfg, "seq_len": seq_len, "batch": batch},
        "value": round(tokens / sec_fori, 1),
        "unit": "tokens/sec/chip",
        "value_synced": round(tokens / sec_synced, 1),
        "value_pipelined": round(tokens / sec_pipe, 1),
        "flops_source": "analytic_model_math",
        "flops_per_step_xla": round(flops_xla) if flops_xla else None,
        **_mfu_fields(flops, sec_fori, sec_synced, sec_pipe,
                      _peak_flops(jax.devices()[0]), fori_runs),
        **_residual_fields(cfg, batch, seq_len, on_tpu),
    }


def _residual_fields(cfg, batch, seq_len, on_tpu) -> dict:
    """Round-20 per-residual breakdown for the flagship row: fori-timed
    ms/step of the two non-MXU residual sites this round fused — the
    decode head tail (``ops.fused_decode_head`` at the flagship head
    shape) and one step's worth of block junctions
    (``ops.fused_attn_junction`` chained ``num_layers`` deep) — so
    BENCH_r06+ tracks the residuals shrinking next to ``mfu``.
    ``exposed_comm_ms`` is structurally 0.0 on the single-chip flagship
    row; the multi-chip rows (``--zero1``) carry the measured
    exposed-vs-hidden attribution from ``overlap_report``, and the
    planner's per-candidate split lives in plan.json."""
    import numpy as np

    from tpudml.ops.decode_head import fused_decode_head
    from tpudml.ops.junction_kernel import fused_attn_junction

    d, heads, L = cfg["embed_dim"], cfg["num_heads"], cfg["num_layers"]
    v, dh = cfg["vocab_size"], d // heads
    rng = np.random.default_rng(0)
    f32 = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32))
    ks = ((8, 40) if on_tpu else (1, 3))
    reps = 3 if on_tpu else 1

    # Head tail at the decode shape: [batch, d] features into the [d, V]
    # head. The 1e-20·carry term threads a loop-carried dependency so
    # fori iterations cannot collapse; it never changes the measured math.
    h, w = f32(batch, d), f32(d, v) * 0.1

    def head_body(ts, h, w):
        _, _, lse = fused_decode_head(h + ts * 1e-20, w)
        out = jnp.sum(lse)
        return out, out

    head_s, head_runs = _time_fori(head_body, jnp.zeros(()), (h, w), *ks,
                                   reps=reps)

    # One step's junctions: L fused attention junctions chained through
    # the residual stream (each layer's s feeds the next), the train
    # trunk's per-step junction count.
    q, k, vv = f32(batch, seq_len, heads, dh), f32(batch, seq_len, heads, dh), \
        f32(batch, seq_len, heads, dh)
    wo, bo = f32(d, d) * 0.1, f32(d)
    g, b2 = f32(d), f32(d)

    def junction_body(ts, q, r):
        r = r + ts * 1e-20
        y = r
        for _ in range(L):
            r, y = fused_attn_junction(q, k, vv, r, wo, bo, g, b2)
        out = jnp.sum(y)
        return out, out

    junc_s, junc_runs = _time_fori(
        junction_body, jnp.zeros(()), (q, f32(batch, seq_len, d)), *ks,
        reps=reps)

    return {
        "head_ms": round(head_s * 1e3, 4),
        "junction_ms": round(junc_s * 1e3, 4),
        "exposed_comm_ms": 0.0,  # single-chip row: no wire to expose
        "residual_runs_ms": {
            "head": [round(s * 1e3, 4) for s in sorted(head_runs)],
            "junction": [round(s * 1e3, 4) for s in sorted(junc_runs)],
        },
    }


def _bytes_on_device0(tree) -> int:
    """Bytes of ``tree``'s leaves resident on device 0 — the per-chip
    memory footprint, read from the arrays' addressable shards (a
    replicated leaf counts its FULL size; a sharded leaf only its local
    slice), so the replicated-vs-ZeRO-1 HBM delta is measured, not
    inferred."""
    dev0 = jax.devices()[0]
    total = 0
    for leaf in jax.tree.leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if shards is None:
            total += getattr(leaf, "nbytes", 0)
            continue
        total += sum(s.data.nbytes for s in shards if s.device == dev0)
    return total


def bench_zero1(on_tpu: bool, n_devices: int) -> dict:
    """``--zero1`` mode: the ZeRO-1 comparison protocol (BASELINE.md).

    Four engines train the SAME flagship LM on the SAME ``{"data": N}``
    mesh with the SAME global batch, so every delta is the weight-update
    strategy and nothing else:

    - ``dp_replicated``  — allreduce grads, every chip runs the full update
    - ``dp_zero1``       — reduce-scatter grads, 1/N update, all_gather params
    - ``dp_zero1_overlap`` — double-buffered variant (gather at step START,
      accum_steps=2 so compute exists to hide it under)
    - ``fsdp``           — 1-D param sharding, the other point on the
      memory/comm trade-off curve

    Per engine: pipelined sec/step (the engines' donated-state protocol —
    fine for RELATIVE comparison on one box; the fori headline stays the
    absolute clock) plus per-chip param and optimizer-state bytes from
    the arrays' addressable shards. The ZeRO-1 rows also carry the
    exposed-vs-hidden comm attribution from ``overlap_report``.
    """
    from tpudml.core.config import MeshConfig
    from tpudml.core.dist import make_mesh
    from tpudml.core.prng import seed_key
    from tpudml.data.datasets import synthetic_lm
    from tpudml.models import TransformerLM
    from tpudml.optim import make_optimizer
    from tpudml.parallel.dp import DataParallel
    from tpudml.parallel.fsdp import FSDP

    if on_tpu:
        cfg = dict(vocab_size=32768, embed_dim=512, num_heads=4, num_layers=6)
        seq_len, per_chip_batch, iters = 1024, 8, 20
    else:  # CPU dryrun: tiny LM, enough steps to median away jitter
        cfg = dict(vocab_size=256, embed_dim=64, num_heads=4, num_layers=2)
        seq_len, per_chip_batch, iters = 128, 4, 6
    batch = per_chip_batch * n_devices
    model = TransformerLM(
        **cfg,
        max_len=seq_len,
        impl="flash" if on_tpu else "full",
        rope=True,
        compute_dtype=jnp.bfloat16 if on_tpu else None,
        fused_ln=on_tpu,
    )
    opt = make_optimizer("adamw", 3e-4)
    seqs = jnp.asarray(synthetic_lm(batch, seq_len, cfg["vocab_size"], seed=1))
    x, y = seqs[:, :-1], seqs[:, 1:]

    mesh = make_mesh(MeshConfig(axes={"data": n_devices}), jax.devices())
    fused = True  # the flagship head; composes with zero1 and accum
    engines = {
        "dp_replicated": lambda: DataParallel(
            model, opt, mesh, fused_xent=fused),
        "dp_zero1": lambda: DataParallel(
            model, opt, mesh, fused_xent=fused, zero1=True),
        "dp_zero1_overlap": lambda: DataParallel(
            model, opt, mesh, fused_xent=fused, zero1=True,
            zero1_overlap=True, accum_steps=2),
        "fsdp": lambda: FSDP(model, opt, mesh, fused_xent=fused),
    }

    rows: dict[str, dict] = {}
    reports: dict[str, dict] = {}
    for name, build in engines.items():
        eng = build()
        ts = eng.create_state(seed_key(0))
        row = {
            "params_bytes_per_chip": _bytes_on_device0(ts.params),
            "opt_state_bytes_per_chip": _bytes_on_device0(ts.opt_state),
        }
        step = eng.make_train_step()
        # Bytes were read above; the timing loop is free to donate ts.
        row["sec_per_step"] = round(_time_pipelined(step, ts, (x, y), iters), 6)
        rows[name] = row
        if name in ("dp_zero1", "dp_zero1_overlap"):
            # Fresh (undonated) state for the attribution spans.
            reports[name] = {
                k: (round(v, 6) if isinstance(v, float) else v)
                for k, v in eng.overlap_report(
                    eng.create_state(seed_key(0)), x, y,
                    iters=10 if on_tpu else 4, warmup=2 if on_tpu else 1,
                ).items()
            }

    rep, zro = rows["dp_replicated"], rows["dp_zero1"]
    return {
        "metric": "zero1_weight_update_sharding_comparison",
        "config": {**cfg, "seq_len": seq_len, "global_batch": batch,
                   "n_devices": n_devices, "fused_xent": fused,
                   "optimizer": "adamw"},
        "protocol": "pipelined_relative",
        "on_tpu": on_tpu,
        "rows": rows,
        "opt_state_bytes_ratio_zero1_vs_replicated": round(
            zro["opt_state_bytes_per_chip"] / rep["opt_state_bytes_per_chip"],
            4),
        "sec_per_step_ratio_zero1_vs_replicated": round(
            zro["sec_per_step"] / rep["sec_per_step"], 4),
        "overlap": reports,
    }


def bench_moe(on_tpu) -> dict:
    """``--moe`` report: one LM step time for the three MoE FFN paths —
    gather+capacity, dropless ragged with lax.ragged_dot's stock dW
    transpose, and dropless ragged with the grouped-dW backward
    (ops/moe_kernel.py) — at E ∈ {4, 8}, top-1, on the same trunk/
    protocol as the transformer row (fori differencing, median of 3).
    Single-shard by construction: dispatch='ragged' rejects EP."""
    from tpudml.core.prng import seed_key
    from tpudml.data.datasets import synthetic_lm
    from tpudml.models import TransformerLM
    from tpudml.optim import make_optimizer
    from tpudml.train import TrainState

    if on_tpu:
        cfg = dict(vocab_size=32768, embed_dim=512, num_heads=4, num_layers=6)
        seq_len, batch, k_lo, k_hi = 1024, 8, 4, 12
    else:  # CPU dryrun: wiring + ratio sanity, not chip numbers
        cfg = dict(vocab_size=256, embed_dim=64, num_heads=4, num_layers=2)
        seq_len, batch, k_lo, k_hi = 128, 4, 2, 6
    seqs = jnp.asarray(synthetic_lm(batch, seq_len, cfg["vocab_size"], seed=3))
    x, y = seqs[:, :-1], seqs[:, 1:]

    variants = {
        "gather": dict(moe_dispatch="gather"),
        "ragged_stock": dict(moe_dispatch="ragged", moe_ragged_dw="stock"),
        "ragged_grouped": dict(moe_dispatch="ragged", moe_ragged_dw="grouped"),
    }
    rows: dict[str, dict] = {}
    for e in (4, 8):
        for name, kv in variants.items():
            model = TransformerLM(
                **cfg,
                max_len=seq_len,
                impl="flash" if on_tpu else "full",
                rope=True,
                compute_dtype=jnp.bfloat16 if on_tpu else None,
                fused_ln=on_tpu,
                moe_experts=e,
                moe_capacity_factor=1.25,
                moe_top_k=1,
                **kv,
            )
            opt = make_optimizer("adamw", 3e-4)
            ts = TrainState.create(model, opt, seed_key(0))
            body = _make_step_body(model, opt)
            sec, runs = _time_fori(body, ts, (x, y), k_lo, k_hi)
            rows[f"E{e}_{name}"] = {
                "sec_per_step": round(sec, 6),
                "runs": [round(r, 6) for r in runs],
            }
    ratios = {
        f"E{e}_{name}_vs_gather": round(
            rows[f"E{e}_{name}"]["sec_per_step"]
            / rows[f"E{e}_gather"]["sec_per_step"], 4)
        for e in (4, 8)
        for name in ("ragged_stock", "ragged_grouped")
    }
    return {
        "metric": "moe_dispatch_backward_comparison",
        "config": {**cfg, "seq_len": seq_len, "batch": batch,
                   "capacity_factor": 1.25, "top_k": 1,
                   "optimizer": "adamw"},
        "protocol": "fori_median",
        "on_tpu": on_tpu,
        # Off-TPU the grouped path runs its reference segment-einsum, not
        # the Pallas kernel — a CPU row checks wiring, not the kernel.
        "grouped_dw_backend": "pallas" if on_tpu else "reference_einsum",
        "rows": rows,
        "ratios": ratios,
    }


def main_moe() -> None:
    """Driver for ``python bench.py --moe``: prints ONE JSON line, same
    contract as ``main()``, for the MoE dispatch/backward comparison."""
    on_tpu = jax.devices()[0].platform != "cpu"
    print(json.dumps(bench_moe(on_tpu)))


def bench_plan(world: int) -> dict:
    """``--plan`` mode: planner rank order vs measured step times.

    Measures the dryrun weight-update regimes (the ``--zero1`` engine
    set: DP-replicated, ZeRO-1, ZeRO-1+overlap, FSDP — all fused-xent on
    the same ``{"data": world}`` mesh, same flagship LM, same global
    batch) by building each one THROUGH the planner's own
    ``build_candidate``, so the program timed is exactly the program the
    emitted plan describes. The planner then scores the same four
    candidates; the report carries both orderings and the acceptance
    ratio: measured time of the planner's top-1 over the measured best.
    The planner validation test pins ``within_tolerance`` (<= 1.10).
    """
    from tpudml.plan.emit import build_candidate
    from tpudml.plan.score import score_candidate
    from tpudml.plan.space import Candidate, flagship_lm

    spec = flagship_lm()
    mesh = (("data", world),)

    def cand(engine, zero1=False, overlap=False, accum=1):
        return Candidate(
            engine=engine, mesh=mesh, zero1=zero1, zero1_overlap=overlap,
            accum_steps=accum, fused_xent=True, sentinel=False, obs=False,
        )

    named = {
        "dp_replicated": cand("dp"),
        "dp_zero1": cand("zero1", zero1=True),
        "dp_zero1_overlap": cand("zero1", zero1=True, overlap=True, accum=2),
        "fsdp": cand("fsdp"),
    }
    rows: dict[str, dict] = {}
    for name, c in named.items():
        score = score_candidate(spec, c)
        _, ts, step, (x, y) = build_candidate(spec, c)
        sec = _time_pipelined(step, ts, (x, y), iters=6)
        rows[name] = {
            "candidate": c.key(),
            "sec_per_step": round(sec, 6),
            "planner_per_token_s": score.per_token_s,
        }
    planner_order = sorted(
        named, key=lambda n: (rows[n]["planner_per_token_s"], n))
    measured_order = sorted(named, key=lambda n: rows[n]["sec_per_step"])
    for i, n in enumerate(planner_order, 1):
        rows[n]["planner_rank"] = i
    for i, n in enumerate(measured_order, 1):
        rows[n]["measured_rank"] = i
    top1, best = planner_order[0], measured_order[0]
    ratio = rows[top1]["sec_per_step"] / rows[best]["sec_per_step"]
    return {
        "metric": "planner_rank_validation",
        "config": {**spec.to_dict(), "world": world, "fused_xent": True,
                   "optimizer": "adamw"},
        "protocol": "pipelined_relative",
        "rows": rows,
        "planner_order": planner_order,
        "measured_order": measured_order,
        "planner_top1": top1,
        "measured_best": best,
        "top1_vs_best_ratio": round(ratio, 4),
        "tolerance": 1.10,
        "within_tolerance": ratio <= 1.10,
    }


def main_plan() -> None:
    """Driver for ``python bench.py --plan [--world N]``: prints ONE JSON
    line, same contract as ``main()``, for the planner rank validation.
    Self-provisions an 8-device CPU mesh when no accelerator is visible
    (same dance as ``--zero1``)."""
    import os
    import sys

    if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""
    ) and not os.environ.get("TPU_NAME"):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip()
    argv = sys.argv[1:]
    world = jax.device_count()
    if "--world" in argv:
        world = min(int(argv[argv.index("--world") + 1]), jax.device_count())
    print(json.dumps(bench_plan(world)))


def bench_serve(on_tpu, smoke=False) -> dict:
    """``--serve`` report for the multi-tenant serving tier.

    ``smoke=True`` (tier-1 canary, seconds on CPU): one seeded workload
    through dense / paged / paged+spec engines, asserting token parity —
    the wiring check that the three compiled decode paths agree.

    The full report (minutes on CPU; ``@slow`` in tests), six sections:

    (a) the A/B the KV cache exists for — per-token decode step time,
        cached vs cacheless (full forward over the whole history) at
        T ∈ {512, 1024}, per-call median with a host token fetch as the
        sync barrier;
    (b) dense engine throughput/latency at fixed QPS points;
    (c) equal-HBM paged vs dense on a mixed short/long workload where
        dense strands >50% of its reserved rows — occupancy, HBM-row
        occupancy, and tokens per decode step at byte-identical KV HBM;
    (d) prefix sharing — admit→first-token wall time for requests
        repeating a 96-token head, shared vs unshared pages;
    (e) speculative decoding — accepted_len and target-step collapse
        with a 1-layer trunk draft on damped-residual params (a
        converged-model stand-in: blocks contribute small corrections,
        the regime where a trunk draft agrees; random-init blocks
        disagree at chance level and would measure nothing);
    (f) a slots × page_size × cache_kind × spec_k Pareto sweep (16 paged
        rows): virtual tokens/sec and p50/p99 TTFT/TPOT on the
        deterministic step clock.

    All scheduler-level rows run the virtual step clock
    (``step_time_s``), so their numbers are a pure function of
    (seed, config) on any host; wall seconds ride along for scale.
    CPU-dryrun numbers are wiring + ratio sanity, not chip numbers —
    BASELINE.md protocol requires a named-chip rerun before recording.
    """
    import math
    import statistics

    import numpy as np

    from tpudml.models import TransformerLM
    from tpudml.serve import (
        Request, ServeConfig, ServingEngine, make_cacheless_decode_step,
        make_decode_step, poisson_workload,
    )

    STEP_S = 0.01  # virtual decode-step clock for all scheduler rows

    def pct(xs, q):
        xs = [x for x in xs if x is not None]
        if not xs:
            return None
        return round(float(np.percentile(np.asarray(xs), q)), 5)

    def hbm_occupancy(rep, hbm_rows):
        """Fraction of KV HBM rows holding LIVE request state, averaged
        over decode steps — replayed from the admit/evict event log."""
        start, end = {}, {}
        for e in rep.events:
            kind, rid, _slot, step = e[:4]
            if kind == "admit":
                start[rid] = step
            elif kind in ("evict", "expire"):
                end[rid] = step
        row_steps = 0
        for rid, s0 in start.items():
            st = rep.requests[rid]
            used = st.prompt_len + len(st.tokens)
            row_steps += (end.get(rid, rep.decode_steps) - s0) * used
        denom = rep.decode_steps * hbm_rows
        return round(row_steps / denom, 4) if denom else 0.0

    if smoke:
        # Tier-1 canary: parity across the three decode paths, tiny
        # model, virtual clock — deterministic and CPU-cheap.
        model = TransformerLM(vocab_size=64, embed_dim=32, num_heads=4,
                              num_kv_heads=2, num_layers=2, max_len=32,
                              rope=True, impl="full")
        params, _ = model.init(jax.random.key(0))

        def run_mode(**kw):
            scfg = ServeConfig(slots=2, max_len=32, prefill_chunk=4,
                               step_time_s=STEP_S, **kw)
            reqs, _ = poisson_workload(6, math.inf, 11, vocab_size=64,
                                       prompt_len=(2, 8), new_tokens=(3, 6))
            return ServingEngine(model, params, scfg, draft_layers=1).run(reqs)

        dense = run_mode()
        paged = run_mode(cache_layout="paged", page_size=4)
        spec = run_mode(cache_layout="paged", page_size=4, spec_k=2)

        def toks(rep):
            return {r: rep.requests[r].tokens for r in rep.requests}

        rows = {
            name: {
                "decode_steps": rep.decode_steps,
                "tokens_per_step": round(
                    rep.generated_tokens / max(rep.decode_steps, 1), 3),
                "occupancy": round(rep.occupancy, 4),
            }
            for name, rep in (("dense", dense), ("paged", paged),
                              ("paged_spec", spec))
        }
        rows["paged_spec"]["mean_accepted_len"] = round(
            spec.mean_accepted_len, 3)
        return {
            "metric": "serving_multitenant_parity_smoke",
            "on_tpu": on_tpu,
            "smoke": True,
            "parity_dense_paged_spec": toks(dense) == toks(paged) == toks(spec),
            "rows": rows,
        }

    if on_tpu:
        cfg = dict(vocab_size=32768, embed_dim=512, num_heads=8,
                   num_kv_heads=2, num_layers=6)
        slots, reps = 8, 20
    else:  # CPU dryrun: ratio + wiring sanity, not chip numbers
        cfg = dict(vocab_size=256, embed_dim=64, num_heads=4,
                   num_kv_heads=2, num_layers=2)
        slots, reps = 2, 7

    def timed_median(fn, *args, n=reps):
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            out = fn(*args)
            jax.device_get(out)  # host copy of the tokens = sync barrier
            ts.append(time.perf_counter() - t0)
        return statistics.median(ts)

    decode_rows: dict[str, dict] = {}
    for t_hist in (512, 1024):
        model = TransformerLM(**cfg, max_len=t_hist, rope=True,
                              impl="flash" if on_tpu else "full")
        params, _ = model.init(jax.random.key(0))
        rng = np.random.default_rng(1)

        # Cached: one token per slot at cache depth t_hist - 1. The step
        # donates its caches, so thread them through the warmup calls and
        # time with a fixed re-bound cache state.
        step = make_decode_step(model)
        caches = model.init_decode_cache(slots, t_hist)
        toks = rng.integers(0, cfg["vocab_size"], slots).astype(np.int32)
        pos = np.full(slots, t_hist - 1, np.int32)
        for _ in range(2):  # compile + warm
            _, _, caches = step(params, caches, toks, pos)

        def cached_once():
            nonlocal caches
            out, _, caches = step(params, caches, toks, pos)
            return out

        cached_sec = timed_median(cached_once)

        # Cacheless: the same emitted token pays a full forward over the
        # entire history (the J110 shape).
        bad_step = make_cacheless_decode_step(model)
        history = rng.integers(
            0, cfg["vocab_size"], (slots, t_hist)).astype(np.int32)
        for _ in range(2):
            bad_step(params, history)
        cacheless_sec = timed_median(bad_step, params, history)

        decode_rows[f"T{t_hist}"] = {
            "cached_sec_per_token_step": round(cached_sec, 6),
            "cacheless_sec_per_token_step": round(cacheless_sec, 6),
            "speedup": round(cacheless_sec / cached_sec, 2),
        }

    # (b) engine under load. Small horizon so the QPS points finish in
    # seconds; arrivals are open-loop, so queue depth (not generator
    # back-pressure) absorbs any engine slowness.
    serve_model = TransformerLM(**cfg, max_len=128, rope=True,
                                impl="flash" if on_tpu else "full")
    serve_params, _ = serve_model.init(jax.random.key(0))
    qps_rows: dict[str, dict] = {}
    for qps in (2.0, 4.0, math.inf):
        eng = ServingEngine(
            serve_model, serve_params,
            ServeConfig(slots=4, max_len=128, prefill_chunk=16))
        reqs, _ = poisson_workload(
            12, qps, 7, vocab_size=cfg["vocab_size"],
            prompt_len=(8, 24), new_tokens=(8, 24))
        rep = eng.run(reqs)
        lat = rep.latency_summary()
        qps_rows["saturated" if math.isinf(qps) else f"qps{qps:g}"] = {
            "tokens_per_sec": round(rep.tokens_per_sec, 2),
            "per_token_p50_ms": round(lat["per_token_p50_s"] * 1e3, 3),
            "per_token_p99_ms": round(lat["per_token_p99_s"] * 1e3, 3),
            "e2e_p50_s": round(lat["e2e_p50_s"], 4),
            "e2e_p99_s": round(lat["e2e_p99_s"], 4),
            "decode_steps": rep.decode_steps,
        }

    # (c) Equal-HBM paged vs dense. Dense reserves 4 slots × 128 rows =
    # 512 KV rows; paged provisions 65 pages × 8 rows = 520 (the +8 is
    # the reserved garbage page) but maps them to 16 slots. The mixed
    # workload (20 short requests stranding ~87% of a dense row, 4 long
    # ones) is exactly where per-slot reservation wastes the HBM.
    rng = np.random.default_rng(3)
    mixed = []
    for i in range(24):
        plen, new = (48, 48) if i % 6 == 0 else (8, 8)
        mixed.append(Request(
            rid=i, prompt=rng.integers(
                0, cfg["vocab_size"], plen).astype(np.int32),
            max_new_tokens=new, arrival_time=0.0))

    def run_hbm(scfg, hbm_rows):
        t0 = time.perf_counter()
        rep = ServingEngine(serve_model, serve_params, scfg).run(mixed)
        wall = time.perf_counter() - t0
        return {
            "hbm_rows": hbm_rows,
            "decode_steps": rep.decode_steps,
            "occupancy": round(rep.occupancy, 4),
            "hbm_occupancy": hbm_occupancy(rep, hbm_rows),
            "tokens_per_step": round(
                rep.generated_tokens / max(rep.decode_steps, 1), 3),
            "tokens_per_sec_virtual": round(rep.tokens_per_sec, 2),
            "wall_s": round(wall, 2),
        }, rep

    dense_row, dense_rep = run_hbm(
        ServeConfig(slots=4, max_len=128, prefill_chunk=8,
                    step_time_s=STEP_S), 4 * 128)
    paged_row, _ = run_hbm(
        ServeConfig(slots=16, max_len=128, prefill_chunk=8,
                    cache_layout="paged", page_size=8, num_pages=65,
                    step_time_s=STEP_S), 65 * 8)
    # How much of the dense reservation the workload could ever use:
    # resident-step-weighted used-rows fraction of the max_len rows each
    # admitted request pins for its whole lifetime.
    tok_steps = sum(len(s.tokens) for s in dense_rep.requests.values())
    used = sum((s.prompt_len + len(s.tokens)) * len(s.tokens)
               for s in dense_rep.requests.values())
    dense_row["stranded_hbm_frac"] = round(1 - used / (128 * tok_steps), 4)
    equal_hbm = {
        "workload": "20 short (8+8) + 4 long (48+48), all at t=0",
        "rows": {"dense": dense_row, "paged": paged_row},
        "paged_over_dense_tokens_per_step": round(
            paged_row["tokens_per_step"] / dense_row["tokens_per_step"], 3),
    }

    # (d) Prefix sharing: 6 requests repeating a 96-token head with a
    # 4-token divergent tail; slots=1 serializes them so admit→first-
    # token is each request's OWN prefill cost (wall clock — prefill is
    # real compute, which is the point). Request 0 is excluded from both
    # means: it pays the compiles AND (shared run) populates the cache.
    head = rng.integers(0, cfg["vocab_size"], 96).astype(np.int32)
    tails = [rng.integers(0, cfg["vocab_size"], 4).astype(np.int32)
             for _ in range(6)]

    def run_prefix(share):
        scfg = ServeConfig(slots=1, max_len=128, prefill_chunk=8,
                           cache_layout="paged", page_size=8,
                           prefix_sharing=share)
        reqs = [Request(rid=i, prompt=np.concatenate([head, tails[i]]),
                        max_new_tokens=8, arrival_time=0.0)
                for i in range(6)]
        rep = ServingEngine(serve_model, serve_params, scfg).run(reqs)
        ttfts = [rep.requests[i].first_token - rep.requests[i].admit_start
                 for i in range(1, 6)]
        return float(np.mean(ttfts)), rep

    unshared_s, _ = run_prefix(False)
    shared_s, shared_rep = run_prefix(True)
    prefix_sharing = {
        "workload": "6 requests, shared 96-token head, 4-token tails",
        "admit_to_first_token_ms_unshared": round(unshared_s * 1e3, 3),
        "admit_to_first_token_ms_shared": round(shared_s * 1e3, 3),
        "speedup_admit_to_first_token": round(unshared_s / shared_s, 2),
        "pool_stats": shared_rep.pool_stats,
        "shared_pages_per_hit": shared_rep.requests[1].shared_pages,
    }

    # (e) Speculative decoding on damped-residual params (see docstring):
    # blocks scaled ×0.25 so the 1-layer trunk draft tracks the 2-layer
    # target the way a draft tracks a converged model. Parity is checked
    # against the plain engine on the SAME params — damping changes what
    # is computed, never whether spec preserves it.
    damped = {k: (jax.tree.map(lambda x: x * 0.25, v)
                  if k.startswith("block") else v)
              for k, v in serve_params.items()}
    rep_head = np.tile(np.array([5, 7, 11, 13], np.int32), 6)

    def spec_reqs():
        return [Request(rid=i, prompt=rep_head.copy(), max_new_tokens=24,
                        arrival_time=0.0) for i in range(4)]

    srep = ServingEngine(
        serve_model, damped,
        ServeConfig(slots=4, max_len=128, prefill_chunk=8, spec_k=3,
                    step_time_s=STEP_S),
        draft_layers=1).run(spec_reqs())
    dref = ServingEngine(
        serve_model, damped,
        ServeConfig(slots=4, max_len=128, prefill_chunk=8,
                    step_time_s=STEP_S)).run(spec_reqs())
    spec_decode = {
        "workload": "4 requests, repetitive 24-token prompt, 24 new",
        "draft": "1-layer trunk (draft_from_trunk), spec_k=3",
        "mean_accepted_len": round(srep.mean_accepted_len, 3),
        "tokens_per_target_step": round(1 + srep.mean_accepted_len, 3),
        "decode_steps_spec": srep.decode_steps,
        "decode_steps_dense": dref.decode_steps,
        "parity": all(srep.requests[r].tokens == dref.requests[r].tokens
                      for r in srep.requests),
    }

    # (f) Pareto: slots × page_size × cache_kind × spec_k, all paged,
    # equal-capacity pools, one seeded finite-QPS workload, virtual
    # clock. TTFT/TPOT come from the annotated workload ledger — the
    # same per-request fields task6 asserts exact accounting on.
    pareto_rows: dict[str, dict] = {}
    for slots_n in (2, 4):
        for page in (8, 16):
            for kind in ("f32", "int8"):
                for k_spec in (0, 2):
                    scfg = ServeConfig(
                        slots=slots_n, max_len=64, prefill_chunk=8,
                        cache_layout="paged", page_size=page,
                        cache_kind=kind, spec_k=k_spec,
                        step_time_s=STEP_S)
                    eng = ServingEngine(serve_model, serve_params, scfg,
                                        draft_layers=1)
                    reqs, ledger = poisson_workload(
                        10, 8.0, 7, vocab_size=cfg["vocab_size"],
                        prompt_len=(8, 24), new_tokens=(8, 16))
                    t0 = time.perf_counter()
                    rep = eng.run(reqs)
                    wall = time.perf_counter() - t0
                    rep.annotate_ledger(ledger)
                    ttft = [r["ttft_s"] for r in ledger.values()]
                    tpot = [r["tpot_s"] for r in ledger.values()]
                    key = f"s{slots_n}_p{page}_{kind}_k{k_spec}"
                    pareto_rows[key] = {
                        "tokens_per_sec_virtual": round(
                            rep.tokens_per_sec, 2),
                        "ttft_p50_s": pct(ttft, 50),
                        "ttft_p99_s": pct(ttft, 99),
                        "tpot_p50_s": pct(tpot, 50),
                        "tpot_p99_s": pct(tpot, 99),
                        "decode_steps": rep.decode_steps,
                        "wall_s": round(wall, 2),
                    }

    return {
        "metric": "serving_multitenant_tier",
        "config": {**cfg, "slots": slots},
        "protocol": "per_call_median + virtual_step_clock",
        "on_tpu": on_tpu,
        "decode_step": decode_rows,
        "serve_load": {
            "n_requests": 12, "slots": 4, "max_len": 128,
            "prefill_chunk": 16, "rows": qps_rows,
        },
        "equal_hbm": equal_hbm,
        "prefix_sharing": prefix_sharing,
        "spec_decode": spec_decode,
        "pareto": {"step_time_s": STEP_S, "rows": pareto_rows},
    }


def bench_sentinel(on_tpu) -> dict:
    """``--sentinel`` report: the flagship-LM train step timed with and
    without the in-graph step sentinel (``resilience.GradSentinel``)
    wrapping the optimizer — the sentinel tax. Same model config and
    fori timing protocol as the secondary LM row, so the two step times
    differ by exactly the sentinel's finiteness reduction + counter
    selects. Acceptance (BASELINE.md round 9): ``overhead_frac`` ≤ 0.03.
    """
    from tpudml.core.prng import seed_key
    from tpudml.data.datasets import synthetic_lm
    from tpudml.models import TransformerLM
    from tpudml.optim import make_optimizer
    from tpudml.resilience import attach_sentinel
    from tpudml.train import TrainState, make_lm_fused_train_step_body

    if on_tpu:
        cfg = dict(vocab_size=32768, embed_dim=512, num_heads=4, num_layers=6)
        seq_len, batch = 1024, 8
    else:  # CPU dryrun: same shape as the dev-smoke LM row
        cfg = dict(vocab_size=256, embed_dim=64, num_heads=4, num_layers=2)
        seq_len, batch = 128, 4
    model = TransformerLM(
        **cfg, max_len=seq_len, impl="flash" if on_tpu else "full",
        rope=True, compute_dtype=jnp.bfloat16 if on_tpu else None,
        fused_ln=on_tpu,
    )
    seqs = jnp.asarray(synthetic_lm(batch, seq_len, cfg["vocab_size"], seed=1))
    x, y = seqs[:, :-1], seqs[:, 1:]
    tokens = batch * seq_len

    def timed(opt) -> float:
        fused_body = make_lm_fused_train_step_body(
            model, opt, save_scores=on_tpu
        )

        def body(ts, tokens_in, labels):
            new_ts, metrics = fused_body(ts, tokens_in, labels)
            return new_ts, metrics["loss"]

        ts0 = TrainState.create(model, opt, seed_key(0))
        # reps=3 on CPU too: the A/B divides two step times, and a
        # single-rep reading on the 1-core box jitters by ±20% — far
        # above the ≤3% tax this row exists to measure.
        sec, _ = _time_fori(
            body, ts0, (x, y),
            *((8, 40) if on_tpu else (1, 3)), reps=3,
        )
        return sec

    sec_plain = timed(make_optimizer("adamw", 3e-4))
    sec_sent = timed(attach_sentinel(make_optimizer("adamw", 3e-4)))
    return {
        "metric": "sentinel_overhead_lm_step_fori",
        "config": {**cfg, "seq_len": seq_len, "batch": batch,
                   "platform": "tpu" if on_tpu else "cpu_dryrun"},
        "step_ms_plain": round(sec_plain * 1e3, 3),
        "step_ms_sentinel": round(sec_sent * 1e3, 3),
        "tokens_per_sec_plain": round(tokens / sec_plain, 1),
        "tokens_per_sec_sentinel": round(tokens / sec_sent, 1),
        "value": round(sec_sent / sec_plain - 1.0, 4),
        "unit": "overhead_fraction",
    }


def main_sentinel() -> None:
    """Driver for ``python bench.py --sentinel``: prints ONE JSON line,
    same contract as ``main()``, for the sentinel on/off A/B."""
    on_tpu = jax.devices()[0].platform != "cpu"
    print(json.dumps(bench_sentinel(on_tpu)))


def bench_obs(on_tpu) -> dict:
    """``--obs`` report: the LeNet DP train step timed with the flight
    recorder (``tpudml.obs``) off vs on — the observability tax. The on
    position adds one host-side tracer span per dispatch AND the in-graph
    StepStats pytree (grad norm, sentinel counters, comm-bytes constant)
    to the jitted step, so the A/B prices the whole ``obs=True`` knob,
    not just the tracer. Dispatched-step timing (not fori): the tracer
    span wraps the dispatch, which fori would hide. Acceptance
    (docs/OBSERVABILITY.md): ``overhead_frac`` < 0.02.
    """
    from tpudml.core.config import MeshConfig
    from tpudml.core.dist import make_mesh
    from tpudml.core.prng import seed_key
    from tpudml.models import LeNet
    from tpudml.optim import make_optimizer
    from tpudml.parallel.dp import DataParallel

    import numpy as np

    devices = jax.devices()
    mesh = make_mesh(MeshConfig({"data": len(devices)}), devices)
    batch = (64 if on_tpu else 32) * len(devices)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, 28, 28, 1)).astype("float32")
    y = rng.integers(0, 10, size=(batch,)).astype("int32")
    iters, reps = (40, 3) if on_tpu else (10, 3)

    def timed(obs) -> float:
        dp = DataParallel(
            LeNet(), make_optimizer("sgd", 0.01, 0.9), mesh, obs=obs
        )
        ts = dp.create_state(seed_key(0))
        step = dp.make_train_step()
        for _ in range(3):  # compile + warm caches
            ts, m = step(ts, x, y)
        jax.block_until_ready(m["loss"])
        runs = []
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(iters):
                ts, m = step(ts, x, y)
            jax.block_until_ready(m["loss"])
            runs.append((time.perf_counter() - t0) / iters)
        # Best-of-reps on both arms: the A/B divides two step times and
        # the minimum is the least-noise estimator of each.
        return min(runs)

    sec_off = timed(False)
    sec_on = timed(True)
    return {
        "metric": "obs_overhead_dp_step",
        "config": {"model": "lenet", "batch": batch,
                   "world": len(devices), "iters": iters, "reps": reps,
                   "platform": "tpu" if on_tpu else "cpu_dryrun"},
        "step_ms_off": round(sec_off * 1e3, 3),
        "step_ms_on": round(sec_on * 1e3, 3),
        "value": round(sec_on / sec_off - 1.0, 4),
        "unit": "overhead_fraction",
        "budget": 0.02,
    }


def main_obs() -> None:
    """Driver for ``python bench.py --obs``: prints ONE JSON line, same
    contract as ``main()``, for the flight-recorder on/off A/B."""
    on_tpu = jax.devices()[0].platform != "cpu"
    print(json.dumps(bench_obs(on_tpu)))


def bench_drill(*, shrink: bool = True, naive: bool = False) -> dict:
    """MTTR row for the elastic failure drills (``tpudml.elastic``).

    ``shrink=False`` is the PR 14 restart drill: 2-process gloo job run
    once uninterrupted and once with rank 1 hard-killed under the
    controller's restart policy, reporting steps lost, restart latency,
    and the bit-exactness verdict.

    ``shrink=True`` (the default) is the adaptive-recovery drill: the
    kill shrinks the gang, the controller consults the planner at the
    new world, and the run resumes under a *different* engine chain —
    the row grows the re-plan evidence (old/new chain, plan latency,
    receipts, post-shrink throughput). ``naive=True`` adds the A/B arm
    that forces the OLD chain at the shrunken world via explicit CLI
    flags, so ``replan_beats_naive`` is measured, not claimed."""
    import tempfile

    base = tempfile.mkdtemp(prefix="tpudml_bench_drill_")
    if not shrink:
        from tpudml.elastic.drill import run_drill

        rep = run_drill(base)
        return {
            "bench": "elastic_drill",
            "ok": rep["ok"],
            "bit_exact": rep["bit_exact"],
            "world": rep["world"],
            "steps": rep["steps"],
            "kill_step": rep["kill_step"],
            "resume_step": rep["resume_step"],
            "steps_lost": rep["steps_lost"],
            "reforms": rep["reforms"],
            "backoff_s": round(rep["backoff_s"], 3),
            "restart_latency_s": round(rep["restart_latency_s"], 3)
            if rep["restart_latency_s"] is not None
            else None,
            "clean_wall_s": round(rep["clean_wall_s"], 3),
            "drill_wall_s": round(rep["drill_wall_s"], 3),
            "overhead_vs_clean_frac": round(rep["overhead_vs_clean_frac"], 4)
            if rep["overhead_vs_clean_frac"] is not None
            else None,
        }

    from tpudml.elastic.drill import run_shrink_drill

    rep = run_shrink_drill(base, include_naive=naive)
    row = {
        "bench": "elastic_shrink_drill",
        "ok": rep["ok"],
        "bit_exact": rep["bit_exact"],
        "world": rep["world"],
        "final_world": rep["final_world"],
        "steps": rep["steps"],
        "kill_step": rep["kill_step"],
        "resume_step": rep["resume_step"],
        "steps_lost": rep["steps_lost"],
        "reforms": rep["reforms"],
        "backoff_s": round(rep["backoff_s"], 3),
        "restart_latency_s": round(rep["restart_latency_s"], 3)
        if rep["restart_latency_s"] is not None
        else None,
        "drill_wall_s": round(rep["drill_wall_s"], 3),
        # The re-plan evidence: what chain we left, what chain we
        # resumed under, how long the decision took, and why the old
        # config lost (machine-readable receipts).
        "old_chain": rep["old_plan"],
        "new_chain": rep["new_plan"],
        "plan_switched": rep["plan_switched"],
        "chain_switched": rep["chain_switched"],
        "replan_latency_s": round(rep["replan_latency_s"], 4)
        if rep["replan_latency_s"] is not None
        else None,
        "replan_receipts": [r["verdict"] for r in rep["replan_receipts"]],
        "post_shrink_steps_per_s": rep["post_shrink_steps_per_s"],
    }
    if naive:
        row["naive"] = rep["naive"]
        row["replan_beats_naive"] = rep["replan_beats_naive"]
    return row


def main_drill() -> None:
    """Driver for ``python bench.py --drill``: prints ONE JSON line, same
    contract as ``main()``, for the elastic MTTR row — by default the
    shrink-re-plan drill. ``--drill-restart`` runs the plain restart
    drill instead; ``--drill-naive`` adds the old-chain-at-new-world A/B
    arm. Requires a platform where the multi-process drill can run
    (JAX_PLATFORMS=cpu uses gloo)."""
    import sys

    print(json.dumps(bench_drill(
        shrink="--drill-restart" not in sys.argv[1:],
        naive="--drill-naive" in sys.argv[1:],
    )))


def main_serve() -> None:
    """Driver for ``python bench.py --serve``: prints ONE JSON line, same
    contract as ``main()``, for the serving tier. ``--smoke`` runs only
    the cheap dense/paged/spec parity canary (the tier-1 wiring check);
    the bare ``--serve`` runs the full six-section report including the
    Pareto sweep (minutes on CPU)."""
    import sys

    on_tpu = jax.devices()[0].platform != "cpu"
    print(json.dumps(bench_serve(on_tpu, smoke="--smoke" in sys.argv[1:])))


def bench_fleet(on_tpu, smoke=False) -> dict:
    """Serving-fleet row (ROADMAP item 3's success metric): aggregate
    tokens/s and ttft/tpot p50/p99 across N replicas at 2×-overload,
    with one replica killed mid-run and re-formed — the kill arm's tail
    latencies must HOLD against the no-kill arm, which is the whole
    point of drain/re-admit (a dead replica costs re-prefill work, not
    correctness or fairness). A third arm quantizes replica weights to
    int8 to show the DecodeCostModel pricing the smaller param-byte
    term (placement honesty, serve/sched.py).

    Deterministic by construction: the fleet runs on the virtual clock,
    so every number here is a pure function of (seed, config) — the
    CPU-dryrun caveat applies to the roofline CONSTANTS, not the
    scheduling."""
    from tpudml.models.transformer import TransformerLM
    from tpudml.serve.engine import ServeConfig
    from tpudml.serve.fleet import FleetConfig, FleetRouter
    from tpudml.serve.load import poisson_workload
    from tpudml.serve.sched import DecodeCostModel, SLOConfig

    model = TransformerLM(
        vocab_size=64, embed_dim=32, num_heads=4, num_kv_heads=2,
        num_layers=2, max_len=64,
    )
    params = model.init(jax.random.PRNGKey(0))[0]
    replicas, slots, step_time = 3, 2, 0.01
    n = 12 if smoke else 48
    # Capacity ≈ replicas × slots tokens per step = 600 tok/s; at ~6
    # tokens/request that serves ~100 req/s — offer 2× that.
    qps = 200.0
    requests, ledger = poisson_workload(
        n, qps, 17, vocab_size=64, prompt_len=(4, 10), new_tokens=(4, 8),
    )
    slo = SLOConfig(tpot_budget_s=0.5)

    def fleet_cfg(weight_quant=None):
        return FleetConfig(
            engine=ServeConfig(
                slots=slots, max_len=64, prefill_chunk=8,
                step_time_s=step_time, deadline_s=2.0, slo=slo,
                weight_quant=weight_quant,
            ),
            replicas=replicas, max_queue=2 * n,
            reform_after_steps=6,
        )

    def arm(cfg, kills):
        rep = FleetRouter(model, params, cfg).run(requests, kills=kills)
        lat = rep.latency_summary()
        return {
            "replicas": rep.replicas,
            "steps": rep.steps,
            "tokens_per_sec": rep.tokens_per_sec,
            "generated_tokens": rep.generated_tokens,
            "finished": rep.finished,
            "rejected": rep.rejected,
            "expired": rep.expired,
            "kills": rep.kills,
            "drains": rep.drains,
            "readmits": sum(s.readmits for s in rep.requests.values()),
            "peak_queue_depth": rep.peak_queue_depth,
            "events_crc32": rep.events_crc32(),
            "ttft_p50_s": lat["ttft_p50_s"],
            "ttft_p99_s": lat["ttft_p99_s"],
            "tpot_p50_s": lat["per_token_p50_s"],
            "tpot_p99_s": lat["per_token_p99_s"],
        }

    kill_step = 6 if smoke else 12
    no_kill = arm(fleet_cfg(), [])
    kill = arm(fleet_cfg(), [(kill_step, 1)])
    int8_arm = arm(fleet_cfg(weight_quant="int8"), [(kill_step, 1)])
    cm_f32 = DecodeCostModel(model, fleet_cfg().engine, slo)
    cm_int8 = DecodeCostModel(
        model, fleet_cfg(weight_quant="int8").engine, slo
    )
    return {
        "bench": "fleet",
        "on_tpu": bool(on_tpu),
        "smoke": bool(smoke),
        "overload_x": 2.0,
        "requests": n,
        "no_kill": no_kill,
        "kill": kill,
        "int8_kill": int8_arm,
        "tpot_p99_kill_over_no_kill": (
            kill["tpot_p99_s"] / max(no_kill["tpot_p99_s"], 1e-12)
        ),
        "cost_params_bytes": {
            "f32": cm_f32.params_bytes,
            "int8": cm_int8.params_bytes,
            "ratio": cm_f32.params_bytes / max(cm_int8.params_bytes, 1),
        },
    }


def bench_mpmd(*, naive: bool = False) -> dict:
    """MPMD re-mesh row (``tpudml.mpmd``): the 2-stage×2-dp pipeline
    drill — SIGKILL one stage rank mid-run, survivors drain at the
    boundary, the planner is consulted fail-open, and the surviving
    stage groups re-form *in place* (fresh ports, no whole-world
    restart) resuming bit-exactly from the common checkpoint step.

    ``naive=True`` adds the whole-world-restart A/B arm (peers abort on
    peer death so every group's containment fires); both arms anchor
    MTTR on the kill marker's mtime, so ``remesh_beats_naive`` is
    measured, not claimed. CPU-dryrun caveat: absolute steps/s and
    MTTRs are host-CPU numbers (gloo + TCP loopback); the *ratio* and
    the bit-exactness verdict are the portable claims."""
    import tempfile

    from tpudml.mpmd.drill import run_mpmd_drill

    base = tempfile.mkdtemp(prefix="tpudml_bench_mpmd_")
    rep = run_mpmd_drill(base, include_naive=naive)
    row = {
        "bench": "mpmd_remesh_drill",
        "ok": rep["ok"],
        "bit_exact": rep["bit_exact"],
        "in_place": rep["in_place"],
        "stage_worlds": [st["dp"] for st in rep["pipeline"]["stages"]],
        "final_stage_worlds": rep["final_stage_worlds"],
        "steps": rep["steps"],
        "kill_step": rep["kill_step"],
        "resume_step": rep["resume_step"],
        "steps_lost": rep["steps_lost"],
        "reforms": rep["reforms"],
        "fresh_ports": rep["fresh_ports"],
        "remesh_mttr_s": round(rep["remesh_mttr_s"], 3)
        if rep["remesh_mttr_s"] is not None
        else None,
        "replan_receipts": rep["replan_receipts"],
        "steps_per_s": rep["steps_per_s"],
    }
    if naive:
        row["naive_restart_mttr_s"] = (
            round(rep["naive"]["restart_mttr_s"], 3)
            if rep["naive"] and rep["naive"]["restart_mttr_s"] is not None
            else None
        )
        row["remesh_beats_naive"] = rep["remesh_beats_naive"]
    return row


def main_mpmd() -> None:
    """Driver for ``python bench.py --mpmd``: prints ONE JSON line, same
    contract as ``main()``, for the MPMD pipeline re-mesh row.
    ``--mpmd-naive`` adds the whole-world-restart A/B arm so the row
    carries re-mesh MTTR vs restart MTTR. Requires a platform where the
    multi-process drill can run (JAX_PLATFORMS=cpu uses gloo)."""
    import sys

    print(json.dumps(bench_mpmd(naive="--mpmd-naive" in sys.argv[1:])))


def main_fleet() -> None:
    """Driver for ``python bench.py --fleet``: prints ONE JSON line, same
    contract as ``main()``, for the serving-fleet row (N replicas at
    2×-overload with a mid-run replica kill). ``--smoke`` shrinks the
    workload to the wiring-check size."""
    import sys

    on_tpu = jax.devices()[0].platform != "cpu"
    print(json.dumps(bench_fleet(on_tpu, smoke="--smoke" in sys.argv[1:])))


def main_zero1() -> None:
    """Driver for ``python bench.py --zero1``: prints ONE JSON line, same
    contract as ``main()`` but for the ZeRO-1 comparison. Self-provisions
    an 8-device CPU mesh when no accelerator is visible (same dance as
    the analysis CLI), since the comparison is meaningless on one chip."""
    import os

    if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""
    ) and not os.environ.get("TPU_NAME"):
        # Harmless if a real backend is present: the flag only affects the
        # CPU platform. Must be set before the backend initializes.
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip()
    on_tpu = jax.devices()[0].platform != "cpu"
    n_devices = jax.device_count()
    print(json.dumps(bench_zero1(on_tpu, n_devices)))


def main() -> None:
    on_tpu = jax.devices()[0].platform != "cpu"
    n_devices = jax.device_count()

    headline = bench_resnet(on_tpu, n_devices)
    secondary = bench_transformer(on_tpu)
    # The chip-filling LM row (VERDICT r4 item 3) records only on real
    # hardware — the 1-core CPU box cannot compile it in budget, and a
    # tiny stand-in would mislabel the metric.
    secondary_large = bench_transformer(on_tpu, large=True) if on_tpu else None

    baseline = lm_baseline = lm_large_baseline = None
    try:
        with open("BASELINE.json") as f:
            pub = json.load(f).get("published", {})
            # Median-protocol pin first (medians compare to medians —
            # VERDICT r3 item 7: r3 published vs_baseline 0.97 by
            # comparing a one-shot run against a best-of-3 pin); the
            # legacy pins are protocol-incompatible fallbacks.
            baseline = pub.get(
                "cifar10_resnet18_imgs_per_sec_per_chip_fori_median"
            ) or pub.get("cifar10_resnet18_imgs_per_sec_per_chip_fori")
            lm_baseline = pub.get(
                "transformer_lm_tokens_per_sec_per_chip_fori_median"
            )
            lm_large_baseline = pub.get(
                "transformer_lm_large_tokens_per_sec_per_chip_fori_median"
            )
    except Exception:
        pass
    if lm_baseline:
        secondary["vs_baseline"] = round(secondary["value"] / lm_baseline, 3)
    if secondary_large is not None and lm_large_baseline:
        secondary_large["vs_baseline"] = round(
            secondary_large["value"] / lm_large_baseline, 3
        )
    vs = headline["value"] / baseline if baseline else 1.0
    out = {
        **headline,
        # fori-protocol recordings only (see module docstring);
        # 1.0 until an honest pin exists in BASELINE.json.
        "vs_baseline": round(vs, 3),
        "secondary": secondary,
    }
    if secondary_large is not None:
        out["secondary_large"] = secondary_large
    print(json.dumps(out))


if __name__ == "__main__":
    import sys

    # --zero1 / --moe are separate reports (each its own single JSON
    # line); the bare invocation's driver contract is untouched.
    if "--zero1" in sys.argv[1:]:
        main_zero1()
    elif "--plan" in sys.argv[1:]:
        main_plan()
    elif "--moe" in sys.argv[1:]:
        main_moe()
    elif "--serve" in sys.argv[1:]:
        main_serve()
    elif "--fleet" in sys.argv[1:]:
        main_fleet()
    elif any(a.startswith("--mpmd") for a in sys.argv[1:]):
        main_mpmd()
    elif "--sentinel" in sys.argv[1:]:
        main_sentinel()
    elif "--obs" in sys.argv[1:]:
        main_obs()
    elif any(a.startswith("--drill") for a in sys.argv[1:]):
        main_drill()
    else:
        main()
