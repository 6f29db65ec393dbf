"""Fused greedy decode head (Pallas, TPU): head matmul + argmax + step
statistics with the [B, V] logits row never materialized.

The serving engine's decode step ends in ``logits = feats @ W_head``
([B, V] — 128 KB/slot f32 at V=32k) followed by a SEPARATE argmax tail:
the logits land in HBM, the reduction reads them back, and the step
statistics (max logit, log-sum-exp) need yet another pass. BASELINE.md
round 7 measured that tail at ~1.9 ms/step on the flagship. This kernel
is the xent trick (``ops/xent_kernel.py``) applied to inference: stream
W one vocab tile at a time through VMEM and fold the pick into the
matmul epilogue —

- grid (B-blocks, V-blocks), V innermost. Per tile:
  s = feats_tile @ W_tile + bias (f32 on the MXU), folded into a running
  online softmax (m, l) per row PLUS a running argmax index: the tile's
  first-occurrence max column, kept only when the tile max strictly
  beats the running max — exactly ``jnp.argmax``'s first-occurrence
  tie-breaking, proven by the greedy-parity tests.
- final tile emits tokens [B] int32 and the in-graph step statistics
  (max logit [B], lse [B]) — everything the engine and the obs tier
  read per step, with no [B, V] round-trip to HBM.

The int8 variant takes the quantized head (int8 codes [d, V] + f32
per-output-channel scales [V], ``serve/fleet/quant.py`` layout) and
dequantizes PER TILE inside the kernel with exactly the oracle's op
order (``q.astype(f32) * scale``), so its logits — and therefore its
greedy picks — are bitwise those of the dequantized-weights path.

Inference only: no custom_vjp (the serving engine never differentiates
through decode). Dispatch: compiled kernel on TPU; reference math
elsewhere unless ``interpret=True`` forces the Pallas interpreter
(tests). TPU note: the int8 path wants d a multiple of the int8 sublane
tile (32) for compiled-mode efficiency; the CPU-dryrun fixtures run
interpret mode where tiling is advisory.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudml.ops.tiling import WIDE_TILE_PARAMS
from tpudml.ops.xent_kernel import _padded_dims

_INT_SENTINEL = jnp.iinfo(jnp.int32).max


def _head_body(s, col, tok_ref, max_ref, lse_ref, m_ref, l_ref, idx_ref):
    """Shared epilogue: fold one masked f32 score tile into the running
    (max, normalizer, argmax-index) state; finalize on the last tile."""
    vj = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(vj == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)
        idx_ref[:] = jnp.zeros_like(idx_ref)

    tm = jnp.max(s, axis=-1, keepdims=True)  # [bn, 1]
    # First-occurrence column of the tile max; a fully-padded tile is
    # all -inf -> tm = -inf, the strict > below keeps the running state.
    ti = jnp.min(
        jnp.where(s == tm, col, _INT_SENTINEL), axis=-1, keepdims=True
    )
    m_prev = m_ref[:]
    # STRICTLY greater: an equal later tile must not steal the pick —
    # jnp.argmax keeps the first occurrence.
    idx_ref[:] = jnp.where(tm > m_prev, ti, idx_ref[:])
    m_new = jnp.maximum(m_prev, tm)
    l_ref[:] = l_ref[:] * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.exp(s - m_new), axis=-1, keepdims=True
    )
    m_ref[:] = m_new

    @pl.when(vj == nv - 1)
    def _():
        tok_ref[:] = idx_ref[:]
        max_ref[:] = m_ref[:]
        lse_ref[:] = m_ref[:] + jnp.log(l_ref[:])


def _head_kernel(x_ref, w_ref, b_ref, tok_ref, max_ref, lse_ref, m_ref,
                 l_ref, idx_ref, *, block_v: int, v_valid: int):
    vj = pl.program_id(1)
    s = jax.lax.dot_general(
        x_ref[:], w_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + b_ref[:].astype(jnp.float32)
    col = vj * block_v + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if v_valid != block_v * pl.num_programs(1):
        s = jnp.where(col < v_valid, s, -jnp.inf)
    _head_body(s, col, tok_ref, max_ref, lse_ref, m_ref, l_ref, idx_ref)


def _head_kernel_int8(x_ref, wq_ref, scale_ref, b_ref, tok_ref, max_ref,
                      lse_ref, m_ref, l_ref, idx_ref, *, block_v: int,
                      v_valid: int):
    vj = pl.program_id(1)
    # Oracle op order (serve/fleet/quant.py _dequant_kernel): codes to
    # f32 FIRST, then the per-output-channel scale — bitwise equality
    # with the dequantized-params path depends on it.
    w = wq_ref[:].astype(jnp.float32) * scale_ref[:]
    s = jax.lax.dot_general(
        x_ref[:], w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + b_ref[:].astype(jnp.float32)
    col = vj * block_v + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if v_valid != block_v * pl.num_programs(1):
        s = jnp.where(col < v_valid, s, -jnp.inf)
    _head_body(s, col, tok_ref, max_ref, lse_ref, m_ref, l_ref, idx_ref)


def _head_call(kernel, inputs, vocab_rows, n, d, v, block_n, block_v,
               interpret):
    """Shared pallas_call plumbing for both weight layouts. ``inputs``
    are the pre-padded operands; the first is the [·, d] row operand,
    the rest are vocab-tiled with leading sizes ``vocab_rows`` (d for a
    weight matrix, 1 for scale/bias rows)."""
    block_n, block_v, n_pad, v_pad = _padded_dims(n, v, block_n, block_v)
    grid = (n_pad // block_n, v_pad // block_v)
    row_spec = pl.BlockSpec((block_n, 1), lambda i, j: (i, 0))
    toks, mx, lse = pl.pallas_call(
        partial(kernel, block_v=block_v, v_valid=v),
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
        ],
        grid=grid,
        in_specs=[pl.BlockSpec((block_n, d), lambda i, j: (i, 0))]
        + [pl.BlockSpec((rows, block_v), lambda i, j: (0, j))
           for rows in vocab_rows],
        out_specs=[row_spec, row_spec, row_spec],
        scratch_shapes=[
            pltpu.VMEM((block_n, 1), jnp.float32),  # running max
            pltpu.VMEM((block_n, 1), jnp.float32),  # running normalizer
            pltpu.VMEM((block_n, 1), jnp.int32),    # running argmax col
        ],
        interpret=interpret,
        compiler_params=WIDE_TILE_PARAMS,
    )(*inputs)
    return toks[:n, 0], mx[:n, 0], lse[:n, 0]


def _pad_operands(x, n, v, block_n, block_v):
    block_n, block_v, n_pad, v_pad = _padded_dims(n, v, block_n, block_v)
    xf = jnp.pad(x, ((0, n_pad - n), (0, 0))) if n_pad != n else x
    return xf, n_pad, v_pad


def _head_forward(x, w, b, block_n, block_v, interpret):
    n, d = x.shape
    d2, v = w.shape
    assert d == d2, (x.shape, w.shape)
    xf, n_pad, v_pad = _pad_operands(x, n, v, block_n, block_v)
    wf = jnp.pad(w, ((0, 0), (0, v_pad - v))) if v_pad != v else w
    bf = (jnp.pad(b, (0, v_pad - v)) if v_pad != v else b)[None, :]
    return _head_call(
        _head_kernel, (xf, wf, bf), (d, 1), n, d, v, block_n, block_v,
        interpret,
    )


def _head_forward_int8(x, wq, scale, b, block_n, block_v, interpret):
    n, d = x.shape
    d2, v = wq.shape
    assert d == d2, (x.shape, wq.shape)
    xf, n_pad, v_pad = _pad_operands(x, n, v, block_n, block_v)
    wqf = jnp.pad(wq, ((0, 0), (0, v_pad - v))) if v_pad != v else wq
    # Padded scale columns are 1.0 so the dequantized pad stays 0 (codes
    # pad to 0); the -inf column mask makes the value irrelevant anyway.
    sf = (jnp.pad(scale, (0, v_pad - v), constant_values=1.0)
          if v_pad != v else scale)[None, :]
    bf = (jnp.pad(b, (0, v_pad - v)) if v_pad != v else b)[None, :]
    return _head_call(
        _head_kernel_int8, (xf, wqf, sf, bf), (d, 1, 1), n, d, v, block_n,
        block_v, interpret,
    )


def _reference_head(x, w, b):
    """XLA reference: materialized logits, same f32 statistics."""
    logits = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ) + b.astype(jnp.float32)
    m = jnp.max(logits, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[:, None]), axis=-1))
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), m, lse


# The dispatch runs inside NAMED nested jits so the call survives as a
# recognizably-named pjit equation in any traced decode program — the
# marker analysis rule J119 keys on to prove a decode step's head tail
# is fused (mirrored as string literals in tpudml/analysis/jaxpr_pass.py,
# pinned by test_analysis). XLA inlines inner jits at lowering, so the
# marker costs nothing on the chip.
def _fused_decode_head(x, w, b, block_n, block_v, interpret):
    if interpret is None:
        if jax.default_backend() != "tpu":
            return _reference_head(x, w, b)
        interpret = False
    return _head_forward(x, w, b, block_n, block_v, interpret)


FUSED_HEAD_MARKER = _fused_decode_head.__name__

_fused_decode_head_jit = jax.jit(_fused_decode_head, static_argnums=(3, 4, 5))


def _fused_decode_head_int8(x, wq, scale, b, block_n, block_v, interpret):
    if interpret is None:
        if jax.default_backend() != "tpu":
            from tpudml.serve.fleet.quant import _dequant_kernel

            return _reference_head(x, _dequant_kernel(wq, scale), b)
        interpret = False
    return _head_forward_int8(x, wq, scale, b, block_n, block_v, interpret)


FUSED_HEAD_INT8_MARKER = _fused_decode_head_int8.__name__

_fused_decode_head_int8_jit = jax.jit(
    _fused_decode_head_int8, static_argnums=(4, 5, 6)
)


def fused_decode_head(
    x: jax.Array,
    w: jax.Array,
    bias: jax.Array | None = None,
    *,
    block_n: int = 256,
    block_v: int = 2048,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Greedy pick + step statistics of ``x @ w [+ bias]`` without
    materializing the [B, V] logits (module docstring).

    ``x`` [..., d] flattens to [B, d]. Returns ``(tokens [B] int32,
    max_logit [B] f32, lse [B] f32)`` — tokens exactly equal
    ``argmax(x @ w + bias)`` (first-occurrence ties included), and the
    statistics are the f32 online-softmax values (max logit and
    log-sum-exp; entropy-adjacent telemetry derives from their
    difference). On non-TPU backends dispatches to the XLA reference
    unless ``interpret=True`` forces the Pallas interpreter."""
    d = x.shape[-1]
    v = w.shape[-1]
    xn = x.reshape(-1, d)
    b = jnp.zeros((v,), w.dtype) if bias is None else bias
    return _fused_decode_head_jit(xn, w, b, block_n, block_v, interpret)


def fused_decode_head_int8(
    x: jax.Array,
    wq: jax.Array,
    scale: jax.Array,
    bias: jax.Array | None = None,
    *,
    block_n: int = 256,
    block_v: int = 2048,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`fused_decode_head` over the QUANTIZED head: ``wq`` int8
    codes [d, V] with f32 per-output-channel ``scale`` [V]
    (``serve/fleet/quant.py`` layout), dequantized per vocab tile inside
    the kernel in the oracle's exact op order — greedy picks are bitwise
    those of running the f32 kernel on ``dequantize(wq, scale)``."""
    d = x.shape[-1]
    v = wq.shape[-1]
    if scale.shape != (v,):
        raise ValueError(f"scale {scale.shape} must be ({v},)")
    xn = x.reshape(-1, d)
    b = jnp.zeros((v,), jnp.float32) if bias is None else bias
    return _fused_decode_head_int8_jit(
        xn, wq, scale, b, block_n, block_v, interpret
    )
