"""Transformer-LM step-time ablations on the real chip (fori protocol).

Component costs measured by differencing whole-step times across
model/config ablations (vocab size, attention impl, batch, head count) —
the stand-in for a ``jax.profiler`` device trace used in rounds 3-5
(ROADMAP.md A2 replaces it with one). Drove the round-3 MFU tuning
recorded in BASELINE.md.
"""

from __future__ import annotations

import os
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import (  # noqa: E402
    _analytic_lm_flops,
    _make_step_body,
    _peak_flops,
    _time_fori,
)

from tpudml.core.prng import seed_key
from tpudml.data.datasets import synthetic_lm
from tpudml.models import TransformerLM
from tpudml.optim import make_optimizer
from tpudml.train import TrainState


def run(name, batch=8, seq_len=1024, vocab=32768, heads=8, layers=6,
        dim=512, impl="flash", remat=False, fused_ln=False, fused_xent=False,
        opt_name="adamw"):
    model = TransformerLM(
        vocab_size=vocab, embed_dim=dim, num_heads=heads, num_layers=layers,
        max_len=seq_len, impl=impl, rope=True, remat=remat,
        compute_dtype=jnp.bfloat16, fused_ln=fused_ln,
    )
    opt = make_optimizer(opt_name, 3e-4)
    # synthetic_lm returns [n, seq_len+1] already; x/y slices give T=seq_len.
    seqs = jnp.asarray(synthetic_lm(batch, seq_len, vocab, seed=1))
    x, y = seqs[:, :-1], seqs[:, 1:]
    if fused_xent:
        from tpudml.train import make_lm_fused_train_step_body

        # save_scores: speed mode, V=32k fits comfortably on this chip.
        fb = make_lm_fused_train_step_body(model, opt, save_scores=True)

        def body(ts, tokens, labels):
            new_ts, metrics = fb(ts, tokens, labels)
            return new_ts, metrics["loss"]
    else:
        body = _make_step_body(model, opt)
    ts0 = TrainState.create(model, opt, seed_key(0))
    t0 = time.time()
    sec, _ = _time_fori(body, ts0, (x, y), 8, 24, reps=1)
    # Analytic matmul FLOPs: XLA cost analysis can't see inside the
    # Pallas custom calls, which would deflate exactly the fused rows
    # this tool exists to compare (bench.py's _analytic_lm_flops note).
    flops = _analytic_lm_flops(
        dict(embed_dim=dim, num_layers=layers, vocab_size=vocab),
        batch, seq_len,
    )
    peak = _peak_flops(jax.devices()[0])
    mfu = flops / sec / peak if flops and peak else float("nan")
    tokens = batch * seq_len
    print(
        f"{name:34s} {sec*1e3:8.2f} ms/step  {tokens/sec:12.0f} tok/s  "
        f"mfu {mfu:.3f}  ({time.time()-t0:.0f}s incl compile)",
        flush=True,
    )
    return sec


from contextlib import contextmanager  # noqa: E402


@contextmanager
def _patched(obj, name, repl):
    orig = getattr(obj, name)
    setattr(obj, name, repl)
    try:
        yield
    finally:
        setattr(obj, name, orig)


def budget(**cfg):
    """Per-component budget table for the flagship fused step (the 19.3 ms
    config: heads=4, fused_ln, fused-xent save-s, AdamW).

    Each arm removes ONE component — by monkeypatch-to-identity (the r3
    LN-ablation idiom) or config ablation — and its delta to the full step
    prices that component in ONE process, so drift between processes
    differences out. Caveats per arm: the head arm (V=512) also
    shrinks the V-scaled part of the embedding backward, and the
    junction arm keeps the residual adds and the scale/bias affine (the
    delta prices the normalization + fusion structure, not the adds).
    Residual = total − Σ components (QKV/FFN matmuls + dispatch)."""
    import tpudml.ops as ops
    from tpudml.models import transformer as tr
    from tpudml.ops import layernorm_kernel as lnk

    base = dict(heads=4, fused_ln=True, fused_xent=True)
    base.update(cfg)

    def attn_identity(q, k, v, *, causal=True, **kw):
        return v

    def junction_identity(x, r, scale, bias, *, eps=1e-5, block_n=256,
                          interpret=None):
        s = x + r
        return s, s * scale + bias  # params stay live, no moments

    def embed_row0(table, tokens):
        return jnp.broadcast_to(
            table[0], (*tokens.shape, table.shape[-1]))

    total = run("flagship fused (total)", **base)
    rows = []
    with _patched(ops, "flash_attention", attn_identity):
        rows.append(("attention", run("  - attention -> identity", **base)))
    with _patched(lnk, "fused_add_layernorm", junction_identity):
        rows.append(("junctions", run("  - junctions -> add+affine", **base)))
    # Proportional vocab shrink (flagship 32k -> 512, the r2/r3 arm).
    tiny_v = max(8, base.get("vocab", 32768) // 64)
    rows.append(("head", run(f"  - head (V={tiny_v})",
                             **{**base, "vocab": tiny_v})))
    with _patched(tr, "embed_lookup", embed_row0):
        rows.append(("embed", run("  - embed -> row-0 broadcast", **base)))
    rows.append(("adamw", run("  - AdamW -> SGD",
                              **{**base, "opt_name": "sgd"})))

    print("\ncomponent budget (full - ablated):")
    accounted = 0.0
    for name, sec in rows:
        delta = total - sec
        accounted += delta
        print(f"  {name:10s} {delta*1e3:7.2f} ms  "
              f"({delta / total * 100:5.1f}% of step)")
    resid = total - accounted
    print(f"  {'residual':10s} {resid*1e3:7.2f} ms  "
          f"({resid / total * 100:5.1f}% of step)  "
          f"[QKV/FFN matmuls + dispatch]")
    return total, dict(rows)


if __name__ == "__main__":
    which = sys.argv[1:] or ["base", "tinyvocab", "fullattn", "b32", "h4"]
    if "budget" in which:
        budget()
        which = [w for w in which if w != "budget"]
    if "base" in which:
        run("base 6L512d V32k B8 flash")
    if "tinyvocab" in which:
        run("V=512 (head+loss removed)", vocab=512)
    if "fullattn" in which:
        run("impl=full (no flash kernel)", impl="full")
    if "b32" in which:
        run("B=32", batch=32)
    if "h4" in which:
        run("heads=4 (dh=128)", heads=4)
    if "h4fusedln" in which:
        run("heads=4 + fused add+LN junctions", heads=4, fused_ln=True)
    if "h4fusedall" in which:
        run("heads=4 + fused LN + fused xent", heads=4, fused_ln=True,
            fused_xent=True)
    if "h4fusedxent" in which:
        run("heads=4 + fused xent (save-s)", heads=4, fused_xent=True)
    if "b32v512" in which:
        run("B=32 V=512", batch=32, vocab=512)
