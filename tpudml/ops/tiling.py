"""Shared tiling helpers for the Pallas kernels (flash attention, fused
linear-cross-entropy, fused LayerNorm): one definition of the block
rounding and row-padding boilerplate so a tiling/padding fix (e.g. a
different sublane multiple per dtype) lands everywhere at once."""

from __future__ import annotations

import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

# Unless told otherwise the chip's compiler scopes a kernel to 16 MiB of
# VMEM (of 128 MiB on v4/v5e/v5p/v6e) and refuses one whose double-buffered
# tiles plus in-kernel temporaries outgrow that. The decode head sits at the
# edge at real widths — d=1024, block_v=2048, f32 weights: 16.09 MiB — so it
# states this ceiling rather than shrink tiles, which would reorder its
# accumulations and change results (tests/test_tpu_compile.py compiles it at
# those widths). The fused cross-entropy kernels sat here too until PR 46
# (the lean dx pass: 16.49 MiB at 256 x 2048); they now take tiles from
# their shape and state a ceiling of their own, sized from those tiles
# (``xent_kernel._plan``, ``_vmem_params``), so this one is the decode
# head's alone.
WIDE_TILE_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=32 * 1024 * 1024)


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def pad_rows(x, n_pad: int):
    """Zero-pad the leading (row) axis of a 2-D array up to ``n_pad``."""
    return (
        jnp.pad(x, ((0, n_pad - x.shape[0]), (0, 0)))
        if n_pad != x.shape[0] else x
    )
