"""Shared tiling helpers for the Pallas kernels (flash attention, fused
linear-cross-entropy, fused LayerNorm): one definition of the block
rounding and row-padding boilerplate so a tiling/padding fix (e.g. a
different sublane multiple per dtype) lands everywhere at once."""

from __future__ import annotations

import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

# Unless told otherwise the chip's compiler scopes a kernel to 16 MiB of
# VMEM (of 128 MiB on v4/v5e/v5p/v6e) and refuses one whose double-buffered
# tiles plus in-kernel temporaries outgrow that. The vocab-tiled kernels
# sit at the edge at real widths — d=1024, block_v=2048: the lean
# fused-xent dx pass needs 16.49 MiB, the f32-weight decode head 16.09 MiB
# — so they state this ceiling rather than shrink tiles, which would
# reorder their accumulations and change results
# (tests/test_tpu_compile.py compiles them at those widths).
WIDE_TILE_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=32 * 1024 * 1024)


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def pad_rows(x, n_pad: int):
    """Zero-pad the leading (row) axis of a 2-D array up to ``n_pad``."""
    return (
        jnp.pad(x, ((0, n_pad - x.shape[0]), (0, 0)))
        if n_pad != x.shape[0] else x
    )
