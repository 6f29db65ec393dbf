"""Dump the optimized HLO of the flagship LM train step (diagnostic).

Fusion boundaries, buffer sizes, and kernel count are readable from the
compiled executable's optimized HLO text, without running anything.
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import _make_step_body  # noqa: E402

from tpudml.core.prng import seed_key
from tpudml.data.datasets import synthetic_lm
from tpudml.models import TransformerLM
from tpudml.optim import make_optimizer
from tpudml.train import TrainState


def main():
    fused = "fused" in sys.argv[1:]
    model = TransformerLM(
        vocab_size=32768, embed_dim=512, num_heads=4, num_layers=6,
        max_len=1024, impl="flash", rope=True, compute_dtype=jnp.bfloat16,
        fused_ln=fused,
    )
    opt = make_optimizer("adamw", 3e-4)
    seqs = jnp.asarray(synthetic_lm(8, 1024, 32768, seed=1))
    x, y = seqs[:, :-1], seqs[:, 1:]
    body = _make_step_body(model, opt)
    ts0 = TrainState.create(model, opt, seed_key(0))
    compiled = jax.jit(body).lower(ts0, x, y).compile()
    txt = compiled.as_text()
    out = sys.argv[-1] if sys.argv[-1].endswith(".txt") else "/tmp/hlo.txt"
    with open(out, "w") as f:
        f.write(txt)
    print(f"wrote {len(txt)} chars to {out}")


if __name__ == "__main__":
    main()
