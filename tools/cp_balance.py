"""Striped vs contiguous ring-CP balance: real-chip kernel-fold timings.

The balanced-causal claim (tpudml/parallel/cp.py): with CONTIGUOUS
sequence layout, ring device i folds 1 causal diagonal block + i full
off-diagonal blocks, so the last device does ~2x the mean work and the
synchronous ring runs at the max; the STRIPED layout gives every device
the same ~half-visible fold per ring step. A 1-core virtual mesh cannot
show this (it serializes all devices: wall-clock = total, not max), so
this tool times the three fold kinds the ring actually issues — causal
diagonal, strict-causal (striped k_shift=1), and full off-diagonal —
with the real Pallas kernels on the chip, and derives both layouts'
per-ring-position time profiles.
"""

from __future__ import annotations

import os
import sys
from functools import partial

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from tools.micro_lm import time_fn  # fori-protocol timer with LICM guard
from tpudml.ops import flash_forward_lse

def main():
    B, T_BLOCK, H, D = 2, 2048, 4, 128  # big enough to clear host-timing noise
    DEVICES = 8

    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, T_BLOCK, H, D), jnp.bfloat16)

    t_diag = time_fn(
        "diag fold (causal)",
        partial(flash_forward_lse, causal=True),
        q, q, q, iters_lo=50, iters_hi=300,
    )
    t_strict = time_fn(
        "striped fold (strict causal, k_shift=1)",
        partial(flash_forward_lse, causal=True, k_shift=1),
        q, q, q, iters_lo=50, iters_hi=300,
    )
    t_full = time_fn(
        "off-diag fold (full)",
        partial(flash_forward_lse, causal=False),
        q, q, q, iters_lo=50, iters_hi=300,
    )

    print(f"\nderived per-ring-position totals (D={DEVICES}, ms):")
    contig = [(t_diag + i * t_full) * 1e3 for i in range(DEVICES)]
    # Striped: every ring step folds a ~half-visible block (diagonal-causal
    # on the own block, strict-causal on arriving ones) — identical on every
    # device by construction.
    striped = [(t_diag + (DEVICES - 1) * t_strict) * 1e3 for _ in range(DEVICES)]
    mean_c, max_c = sum(contig) / DEVICES, max(contig)
    print("contiguous:", " ".join(f"{t:6.2f}" for t in contig))
    print("striped:   ", " ".join(f"{t:6.2f}" for t in striped))
    print(
        f"contiguous max/mean imbalance: {max_c / mean_c:.2f}  "
        f"(ring step time is the MAX device)\n"
        f"striped max = {striped[0]:.2f} ms vs contiguous max = {max_c:.2f} ms "
        f"-> projected ring speedup {max_c / striped[0]:.2f}x"
    )


if __name__ == "__main__":
    main()
