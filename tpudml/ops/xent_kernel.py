"""Fused linear-cross-entropy (Pallas, TPU): head matmul + softmax loss
with the [N, V] logits matrix never materialized.

The LM loss path computes ``logits = x @ W`` ([N, V] — 0.5 GB bf16 at
N=8k tokens, V=32k) and reduces it to one scalar. Even with the
memory-lean XLA loss (tpudml/nn/losses.py), the logits buffer itself
must exist between the matmul and the reductions, and the backward keeps
it (or recomputes it) at full width. This kernel streams W one vocab
tile at a time through VMEM — flash-attention's trick applied to the
classifier head:

- forward: grid (N-blocks, V-blocks), V innermost. Per tile:
  s = x_tile @ W_tile (f32 on the MXU), folded into a running online
  softmax (m, l) per row plus the label's logit (fused iota-compare
  pick). Emits lse [N] and picked [N]; loss = mean(lse - picked).
  Residuals: x, W, labels, lse — O(N + params), NOT O(N·V).
- backward, lean mode: recompute s per tile; dlogits =
  (exp(s - lse) - onehot)·g/N. Two kernels, mirroring the attention
  backward split:
  dX (V innermost): dx_tile += dlogits @ W_tileᵀ;
  dW (N innermost): dW_tile += x_tileᵀ @ dlogits.
- backward, save-s mode: the forward additionally streams its f32 score
  tiles to HBM, and both backward kernels read them instead of
  recomputing — the backward drops from 4 matmuls' worth of MXU work to
  the 2 the cotangents actually need. Saved scores are f32 and both modes
  take the same tiles, so gradients are bit-identical to the lean mode's
  recomputation. The trade is an N_pad·V_pad·4-byte residual in place of
  the O(N) contract; the DEFAULT (``save_s=None``) picks the mode
  automatically — save-s while that residual fits
  ``SAVE_S_AUTO_MAX_BYTES`` (2 GiB), the lean O(N) contract beyond. Pass
  ``save_s=False`` to force the O(N) guarantee regardless of size.
- tiles: every kernel re-reads one operand once a block of the other
  axis (the forward and dX the whole head once a ROW block, dW all of x
  once a VOCABULARY tile), so small tiles make a matmul-shaped kernel
  memory-bound. ``_plan`` chooses them from the call's shape so that the
  re-reads cost less than the matmul, and each kernel states the VMEM its
  tiles need (``_vmem_params``); the chip's numbers are beside ``_plan``.

Exactness: same math as ``softmax_cross_entropy`` over the materialized
logits (f32 statistics); pinned by tests against the XLA reference.
Dispatch: compiled kernel on TPU; reference math elsewhere (tests force
``interpret=True``).
"""

from __future__ import annotations

import itertools
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


from tpudml.ops.tiling import round_up as _round_up  # shared tiling helper


# ---------------------------------------------------------------- forward


def _fwd_body(x_ref, w_ref, b_ref, label_ref, lse_ref, picked_ref, m_ref,
              l_ref, z_ref, s_ref, *, block_v: int, v_valid: int):
    vj = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(vj == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)
        z_ref[:] = jnp.zeros_like(z_ref)

    s = jax.lax.dot_general(
        x_ref[:], w_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + b_ref[:].astype(jnp.float32)  # [bn, bv] (+ broadcast [1, bv] bias)
    col = vj * block_v + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if v_valid != block_v * nv:
        # Padded vocab columns must carry no probability mass.
        s = jnp.where(col < v_valid, s, -jnp.inf)
    if s_ref is not None:
        # save-s mode: stream the masked f32 scores out; the backward
        # reads them instead of recomputing the matmul (padded columns
        # carry -inf → p = 0 there with no masking needed).
        s_ref[:] = s
    label = label_ref[:]  # [bn, 1] int32
    # The pick must exclude padded columns even when a (buggy) label
    # lands in [V, V_pad): such labels see picked = 0 → loss = lse, the
    # SAME no-pull-up semantics as any other out-of-range label, instead
    # of picking the -inf a padded column carries (+inf loss).
    z_ref[:] += jnp.sum(
        jnp.where((col == label) & (col < v_valid), s, 0.0),
        axis=-1, keepdims=True,
    )
    m_prev = m_ref[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    l_ref[:] = l_ref[:] * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.exp(s - m_new), axis=-1, keepdims=True
    )
    m_ref[:] = m_new

    @pl.when(vj == nv - 1)
    def _():
        lse_ref[:] = m_ref[:] + jnp.log(l_ref[:])
        picked_ref[:] = z_ref[:]


def _fwd_kernel(x_ref, w_ref, b_ref, label_ref, lse_ref, picked_ref, m_ref,
                l_ref, z_ref, *, block_v: int, v_valid: int):
    _fwd_body(x_ref, w_ref, b_ref, label_ref, lse_ref, picked_ref, m_ref,
              l_ref, z_ref, None, block_v=block_v, v_valid=v_valid)


def _fwd_kernel_save(x_ref, w_ref, b_ref, label_ref, lse_ref, picked_ref,
                     s_ref, m_ref, l_ref, z_ref, *, block_v: int,
                     v_valid: int):
    _fwd_body(x_ref, w_ref, b_ref, label_ref, lse_ref, picked_ref, m_ref,
              l_ref, z_ref, s_ref, block_v=block_v, v_valid=v_valid)


def _fused_forward(x, w, b, labels, block_n, block_v, interpret,
                   save_s=False):
    n, d = x.shape
    d2, v = w.shape
    assert d == d2, (x.shape, w.shape)
    plan = _plan(n, d, v, x.dtype, w.dtype, block_n, block_v)
    (block_n, block_v), n_pad, v_pad = plan.tile, plan.n_pad, plan.v_pad
    xf = jnp.pad(x, ((0, n_pad - n), (0, 0))) if n_pad != n else x
    wf = jnp.pad(w, ((0, 0), (0, v_pad - v))) if v_pad != v else w
    bf = (jnp.pad(b, (0, v_pad - v)) if v_pad != v else b)[None, :]
    # Padded rows pick label -1 → match no column → picked 0, lse finite.
    lf = jnp.pad(labels.astype(jnp.int32), (0, n_pad - n),
                 constant_values=-1)[:, None]
    out_shape = [
        jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
        jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
    ]
    out_specs = [
        pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
    ]
    if save_s:
        out_shape.append(
            jax.ShapeDtypeStruct((n_pad, v_pad), jnp.float32)
        )
        out_specs.append(pl.BlockSpec((block_n, block_v), lambda i, j: (i, j)))
    outs = pl.pallas_call(
        partial(_fwd_kernel_save if save_s else _fwd_kernel,
                block_v=block_v, v_valid=v),
        out_shape=out_shape,
        grid=(n_pad // block_n, v_pad // block_v),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, block_v), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_v), lambda i, j: (0, j)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((block_n, 1), jnp.float32),  # running max
            pltpu.VMEM((block_n, 1), jnp.float32),  # running normalizer
            pltpu.VMEM((block_n, 1), jnp.float32),  # picked accumulator
        ],
        name="xent_fwd_save" if save_s else "xent_fwd",
        interpret=interpret,
        compiler_params=_vmem_params("fwd", plan.tile, d, x.dtype, w.dtype,
                                     save_s),
    )(xf, wf, bf, lf)
    if save_s:
        lse, picked, s = outs
        return lse[:n, 0], picked[:n, 0], s
    lse, picked = outs
    return lse[:n, 0], picked[:n, 0]


# --------------------------------------------------------------- backward
# save-s kernels: identical math to the lean kernels below, with the
# score recomputation matmul replaced by a read of the forward's saved
# f32 scores (padded columns already carry -inf → p = 0 unmasked).


def _dx_s_kernel(s_ref, w_ref, label_ref, lse_ref, dx_ref, acc_ref, *,
                 block_v: int, v_valid: int, inv_n: float):
    vj = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(vj == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    s = s_ref[:]
    col = vj * block_v + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    p = jnp.exp(s - lse_ref[:])
    onehot = (col == label_ref[:]) & (col < v_valid)
    dlog = (p - onehot.astype(jnp.float32)) * inv_n
    acc_ref[:] += jax.lax.dot_general(
        dlog.astype(w_ref.dtype), w_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [bn, d]

    @pl.when(vj == nv - 1)
    def _():
        dx_ref[:] = acc_ref[:].astype(dx_ref.dtype)


def _dw_s_kernel(s_ref, x_ref, label_ref, lse_ref, dw_ref, db_ref, acc_ref,
                 db_acc, *, block_v: int, v_valid: int, inv_n: float):
    vj = pl.program_id(1)
    ni = pl.program_id(2)
    nn = pl.num_programs(2)

    @pl.when(ni == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        db_acc[:] = jnp.zeros_like(db_acc)

    s = s_ref[:]
    col = vj * block_v + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    p = jnp.exp(s - lse_ref[:])
    onehot = (col == label_ref[:]) & (col < v_valid)
    dlog = (p - onehot.astype(jnp.float32)) * inv_n
    acc_ref[:] += jax.lax.dot_general(
        x_ref[:], dlog.astype(x_ref.dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [d, bv]
    db_acc[:] += jnp.sum(dlog, axis=0, keepdims=True)

    @pl.when(ni == nn - 1)
    def _():
        dw_ref[:] = acc_ref[:].astype(dw_ref.dtype)
        db_ref[:] = db_acc[:].astype(db_ref.dtype)


def _bwd_prologue(x, w, labels, lse, block_n, block_v, save_s):
    """Shared backward setup for BOTH modes: the call's plan and the
    padded-row contract — labels pad to -1 (match no column) and lse
    pads to +inf so p = exp(s − lse) = 0 on padded rows, making their
    dlogits exactly zero in every backward kernel."""
    n, d = x.shape
    _, v = w.shape
    plan = _plan(n, d, v, x.dtype, w.dtype, block_n, block_v)
    n_pad, v_pad = plan.n_pad, plan.v_pad
    xf = jnp.pad(x, ((0, n_pad - n), (0, 0))) if n_pad != n else x
    wf = jnp.pad(w, ((0, 0), (0, v_pad - v))) if v_pad != v else w
    lf = jnp.pad(labels.astype(jnp.int32), (0, n_pad - n),
                 constant_values=-1)[:, None]
    lsef = jnp.pad(lse.astype(jnp.float32), (0, n_pad - n),
                   constant_values=jnp.inf)[:, None]
    params = partial(_vmem_params, d=d, x_dtype=x.dtype, w_dtype=w.dtype,
                     save_s=save_s)
    return n, d, v, plan, params, xf, wf, lf, lsef


def _scale_cotangents(dx, dw, db, g, x, w, b):
    """The scalar cotangent g is a traced value, so it cannot fold into
    the kernels' static inv_n; 1/n scales inside, g multiplies outside
    (one fused elementwise pass over dx/dW/db)."""
    gf = g.astype(jnp.float32)
    return (
        (dx.astype(jnp.float32) * gf).astype(x.dtype),
        (dw.astype(jnp.float32) * gf).astype(w.dtype),
        (db * gf).astype(b.dtype),
    )


def _fused_backward_saved(x, w, b, labels, lse, s, g, block_n, block_v,
                          interpret):
    n, d, v, plan, params, xf, wf, lf, lsef = _bwd_prologue(
        x, w, labels, lse, block_n, block_v, True)
    n_pad, v_pad = plan.n_pad, plan.v_pad
    assert s.shape == (n_pad, v_pad), (s.shape, n_pad, v_pad)
    block_n, block_v = plan.tile
    dx = pl.pallas_call(
        partial(_dx_s_kernel, block_v=block_v, v_valid=v, inv_n=1.0 / n),
        out_shape=jax.ShapeDtypeStruct(xf.shape, x.dtype),
        grid=(n_pad // block_n, v_pad // block_v),
        in_specs=[
            pl.BlockSpec((block_n, block_v), lambda i, j: (i, j)),
            pl.BlockSpec((d, block_v), lambda i, j: (0, j)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
        scratch_shapes=[pltpu.VMEM((block_n, d), jnp.float32)],
        name="xent_bwd_dx_saved",
        interpret=interpret,
        compiler_params=params("dx", plan.tile),
    )(s, wf, lf, lsef)[:n]
    block_n, bv_dw = plan.dw
    dw, db = pl.pallas_call(
        partial(_dw_s_kernel, block_v=bv_dw, v_valid=v, inv_n=1.0 / n),
        out_shape=[
            jax.ShapeDtypeStruct(wf.shape, w.dtype),
            jax.ShapeDtypeStruct((1, v_pad), jnp.float32),
        ],
        grid=(1, v_pad // bv_dw, n_pad // block_n),
        in_specs=[
            pl.BlockSpec((block_n, bv_dw), lambda _, j, i: (i, j)),
            pl.BlockSpec((block_n, d), lambda _, j, i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda _, j, i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda _, j, i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((d, bv_dw), lambda _, j, i: (0, j)),
            pl.BlockSpec((1, bv_dw), lambda _, j, i: (0, j)),
        ],
        scratch_shapes=[
            pltpu.VMEM((d, bv_dw), jnp.float32),
            pltpu.VMEM((1, bv_dw), jnp.float32),
        ],
        name="xent_bwd_dw_saved",
        interpret=interpret,
        compiler_params=params("dw", plan.dw),
    )(s, xf, lf, lsef)
    return _scale_cotangents(dx, dw[:, :v], db[0, :v], g, x, w, b)


def _dx_kernel(x_ref, w_ref, b_ref, label_ref, lse_ref, dx_ref, acc_ref, *,
               block_v: int, v_valid: int, inv_n: float):
    vj = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(vj == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    s = jax.lax.dot_general(
        x_ref[:], w_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + b_ref[:].astype(jnp.float32)
    col = vj * block_v + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    p = jnp.exp(s - lse_ref[:])
    if v_valid != block_v * nv:
        p = jnp.where(col < v_valid, p, 0.0)
    onehot = (col == label_ref[:]) & (col < v_valid)
    dlog = (p - onehot.astype(jnp.float32)) * inv_n
    acc_ref[:] += jax.lax.dot_general(
        dlog.astype(w_ref.dtype), w_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [bn, d]

    @pl.when(vj == nv - 1)
    def _():
        dx_ref[:] = acc_ref[:].astype(dx_ref.dtype)


def _dw_kernel(w_ref, x_ref, b_ref, label_ref, lse_ref, dw_ref, db_ref,
               acc_ref, db_acc, *, block_v: int, v_valid: int, inv_n: float):
    vj = pl.program_id(1)
    ni = pl.program_id(2)
    nn = pl.num_programs(2)

    @pl.when(ni == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        db_acc[:] = jnp.zeros_like(db_acc)

    s = jax.lax.dot_general(
        x_ref[:], w_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + b_ref[:].astype(jnp.float32)  # [bn, bv]
    col = vj * block_v + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    p = jnp.exp(s - lse_ref[:])
    if v_valid != block_v * pl.num_programs(1):
        p = jnp.where(col < v_valid, p, 0.0)
    onehot = (col == label_ref[:]) & (col < v_valid)
    dlog = (p - onehot.astype(jnp.float32)) * inv_n
    acc_ref[:] += jax.lax.dot_general(
        x_ref[:], dlog.astype(x_ref.dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [d, bv]
    db_acc[:] += jnp.sum(dlog, axis=0, keepdims=True)  # [1, bv]

    @pl.when(ni == nn - 1)
    def _():
        dw_ref[:] = acc_ref[:].astype(dw_ref.dtype)
        db_ref[:] = db_acc[:].astype(db_ref.dtype)


def _fused_backward(x, w, b, labels, lse, g, block_n, block_v, interpret):
    n, d, v, plan, params, xf, wf, lf, lsef = _bwd_prologue(
        x, w, labels, lse, block_n, block_v, False)
    n_pad, v_pad = plan.n_pad, plan.v_pad
    block_n, block_v = plan.tile
    bf = (jnp.pad(b, (0, v_pad - v)) if v_pad != v else b)[None, :]
    dx = pl.pallas_call(
        partial(_dx_kernel, block_v=block_v, v_valid=v, inv_n=1.0 / n),
        out_shape=jax.ShapeDtypeStruct(xf.shape, x.dtype),
        grid=(n_pad // block_n, v_pad // block_v),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, block_v), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_v), lambda i, j: (0, j)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
        scratch_shapes=[pltpu.VMEM((block_n, d), jnp.float32)],
        name="xent_bwd_dx",
        interpret=interpret,
        compiler_params=params("dx", plan.tile),
    )(xf, wf, bf, lf, lsef)[:n]
    block_n, block_v_dw = plan.dw
    dw, db = pl.pallas_call(
        partial(_dw_kernel, block_v=block_v_dw, v_valid=v, inv_n=1.0 / n),
        out_shape=[
            jax.ShapeDtypeStruct(wf.shape, w.dtype),
            jax.ShapeDtypeStruct((1, v_pad), jnp.float32),
        ],
        grid=(1, v_pad // block_v_dw, n_pad // block_n),
        in_specs=[
            pl.BlockSpec((d, block_v_dw), lambda _, j, i: (0, j)),
            pl.BlockSpec((block_n, d), lambda _, j, i: (i, 0)),
            pl.BlockSpec((1, block_v_dw), lambda _, j, i: (0, j)),
            pl.BlockSpec((block_n, 1), lambda _, j, i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda _, j, i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((d, block_v_dw), lambda _, j, i: (0, j)),
            pl.BlockSpec((1, block_v_dw), lambda _, j, i: (0, j)),
        ],
        scratch_shapes=[
            pltpu.VMEM((d, block_v_dw), jnp.float32),
            pltpu.VMEM((1, block_v_dw), jnp.float32),
        ],
        name="xent_bwd_dw",
        interpret=interpret,
        compiler_params=params("dw", plan.dw),
    )(wf, xf, bf, lf, lsef)
    return _scale_cotangents(dx, dw[:, :v], db[0, :v], g, x, w, b)


# --------------------------------------------------------------- dispatch


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _fused(x, w, b, labels, block_n, block_v, interpret, save_s):
    lse, picked = _fused_forward(x, w, b, labels, block_n, block_v, interpret)
    return jnp.mean(lse - picked)


def _fused_fwd(x, w, b, labels, block_n, block_v, interpret, save_s):
    if save_s:
        lse, picked, s = _fused_forward(
            x, w, b, labels, block_n, block_v, interpret, save_s=True
        )
        return jnp.mean(lse - picked), (x, w, b, labels, lse, s)
    lse, picked = _fused_forward(x, w, b, labels, block_n, block_v, interpret)
    return jnp.mean(lse - picked), (x, w, b, labels, lse, None)


def _fused_bwd(block_n, block_v, interpret, save_s, res, g):
    import numpy as np

    x, w, b, labels, lse, s = res
    if save_s:
        dx, dw, db = _fused_backward_saved(
            x, w, b, labels, lse, s, g, block_n, block_v, interpret
        )
    else:
        dx, dw, db = _fused_backward(
            x, w, b, labels, lse, g, block_n, block_v, interpret
        )
    return dx, dw, db, np.zeros(labels.shape, dtype=jax.dtypes.float0)


_fused.defvjp(_fused_fwd, _fused_bwd)


# The speed mode's f32 score residual is N_pad·V_pad·4 bytes; it is on by
# default while that stays a modest slice of a v5e's HBM (16 GB) and the
# O(N) lean mode runs beyond: 2 GiB covers 8k x 50k (1.66 GB) and
# 16k x 32k with room for the model; a 131k-token context (16 GiB of
# scores) drops to lean — the regime the O(N) contract exists for.
SAVE_S_AUTO_MAX_BYTES = 2 * 1024**3


class _Plan(NamedTuple):
    """One call's tiling: (rows, vocabulary columns) a grid step takes in
    the forward and dX, the same in dW, and the padded problem all three
    tile."""

    tile: tuple[int, int]
    dw: tuple[int, int]
    n_pad: int
    v_pad: int


# The tiles, measured on both sides (v5e, PR 46; N 8,192 x d 1,024 x V 50,257,
# bf16, save-s: gpt2-medium.pretrain-1k's head; ms a call by the kernel's own
# name in a device trace, rows x vocabulary columns; the matmul alone is 4.32 ms
# at the peak). A grid step fetches its W tile anew, so the whole head
# (105 MB) is read once a ROW block and the f32 scores (1.66 GB) move once:
# at 256 rows that is 3.4 + 1.7 GB = 6.2 ms at the memory's roof for 4.4 ms of
# matmul, and dW reads all of x once a VOCABULARY tile into an accumulator it
# rewrites every 256 rows.
# Columns of a line: x512 x1024 x1152 x1408 x1536 x2048 (- = not run).
#   forward  256 rows  9.14  8.31  8.11  7.93  7.84  7.80 (the rule until PR 46)
#            512       6.24  5.51  5.33  5.24  5.19  5.20
#            1,024     5.89  5.18  5.04  4.92  4.89  4.98
#            2,048     5.79  5.13  4.99  4.90  4.89  6.01
#   dX       256       6.99  6.68  6.61  6.62  6.62  6.68 (until PR 46)
#            512       5.10  4.95  4.87  4.78  4.75  4.61
#            1,024     4.61  4.72  4.67  4.67  4.59  4.48
#            2,048     4.50  4.63  4.59  4.64  4.51  4.49
#   dW       256       6.18  5.39  5.25  5.11  5.04  4.95; x640 5.99 (until PR 46)
#            512       5.70  5.09  4.98  4.87  4.86  4.80; x2560 4.75
#            1,024     5.32  4.83  4.73  4.70  4.71  4.69; x2560 4.69
#            2,048     4.93  4.66  4.59  4.56  4.57  4.62
#            4,096     4.83  5.53   -     -     -     -
# 1,024 rows bring every kernel within 6-13 % of its matmul and the width
# then moves it by 2 %: of the 128-multiples from half the widest tile up the
# plan takes the one that pads the vocabulary least (50,257 -> 1,536-wide,
# v_pad 50,688; 2,048 pads it to 51,200 for 1 % more of everything). 2,048
# rows read 1.6 % less over the three kernels for twice the VMEM and twice
# the padding of a ragged N: not taken. The other loop order of the forward
# (vocabulary outer, the W tile resident while row blocks stream, the running
# max / normaliser / pick as [N, 1] columns resident across the grid: a
# scratch copy of this kernel, never in the tree) at x2048: 256 rows 5.24, 512
# 5.10, 1,024 4.98; x1024: 512 5.47, 1,024 5.19; x4096: 256 5.37, 512 5.26 —
# it needs no deep row block, and at 1,024 rows it equals the kept order (4.98
# for 4.98; the chip compiler schedules both bodies in the same 23.5k bundles a
# step), so the rows-outer order stays: its statistics are per row block
# (any N), and dX has no such order (its [N, d] f32 partial sums would cross
# HBM). Lean mode recomputes s, two matmuls a backward kernel: forward 256 x
# 2048 5.13, 1,024 x 1024 5.16, x2048 5.01; dX 8.91 / 8.83 / 8.82; dW 256 x
# 640 9.25, 1,024 x 1024 8.90, x2048 8.87 — MXU-bound at any of these.
_ROWS = 1024                # rows a grid step takes (the contraction dW makes)
_TILE_V = 2048              # widest vocabulary tile
_VMEM_BUDGET = 64 * 2**20   # what a kernel's tiles may hold, as modelled
_VMEM_CEILING = 100 * 2**20  # of v5e's 128 MiB


def _vmem_bytes(kernel: str, tile: tuple[int, int], d: int, x_item: int,
                w_item: int, save_s: bool) -> int:
    """What a grid step of ``kernel`` ("fwd", "dx", "dw") holds in VMEM at
    ``tile``: the blocks the pipeline double-buffers, the scratch, and the
    body's temporaries — two float32 [rows, columns] tiles (s; p or
    dlogits), a copy of each operand tile as the MXU wants it, and the
    backward's float32 product before it is accumulated. Generous: compiled
    for a described v5e at 1,024 x 2,048, d 1,024, bf16, the kernels need
    34 / 33 / 47 MiB (lean 25 / 27 / 40) where this says 55 / 60 / 68
    (39 / 48 / 60)."""
    bn, bv = tile
    x_tile, w_tile, s_tile = bn * d * x_item, d * bv * w_item, bn * bv * 4
    column = bn * 128 * 4  # a [bn, 1] float32 as VMEM holds it
    row = 8 * bv * 4       # a [1, bv] one
    temporaries = 2 * s_tile + x_tile + w_tile
    if kernel == "fwd":
        pipelined = (x_tile + w_tile + row + 3 * column
                     + (s_tile if save_s else 0))
        scratch = 3 * column
    elif kernel == "dx":
        pipelined = ((s_tile if save_s else x_tile + row) + w_tile
                     + 2 * column + x_tile)
        scratch = bn * d * 4
        temporaries += bn * d * 4
    else:
        pipelined = ((s_tile if save_s else w_tile + row) + x_tile
                     + 2 * column + w_tile + row)
        scratch = d * bv * 4 + row
        temporaries += d * bv * 4
    return 2 * pipelined + scratch + temporaries


def _vmem_params(kernel: str, tile: tuple[int, int], d: int, x_dtype,
                 w_dtype, save_s: bool) -> pltpu.CompilerParams:
    """The kernel's own VMEM ceiling, from its tiles (a quarter over the
    model, never under ``WIDE_TILE_PARAMS``' 32 MiB, which is
    ``ops/decode_head.py``'s to keep): the default scope of 16 MiB is under
    what the plan's tiles need."""
    need = _vmem_bytes(kernel, tile, d, jnp.dtype(x_dtype).itemsize,
                       jnp.dtype(w_dtype).itemsize, save_s)
    return pltpu.CompilerParams(
        vmem_limit_bytes=min(max(need + need // 4, 32 * 2**20),
                             _VMEM_CEILING))


def _padded_dims(n: int, v: int, block_n: int, block_v: int):
    """Clamp blocks to the rounded-up problem (rows to 8, vocab to 128)
    and pad the problem to a block multiple (``ops/decode_head.py`` tiles
    by the same rule)."""
    block_n = min(block_n, _round_up(n, 8))
    block_v = min(block_v, _round_up(v, 128))
    return block_n, block_v, _round_up(n, block_n), _round_up(v, block_v)


def _halvings(start: int, least: int) -> list[int]:
    return [start >> i for i in range(start.bit_length())
            if start >> i >= least]


def _open_rows(n: int) -> int:
    """The row block of a call that left it open: ``_ROWS``, halved (to a
    quarter at most) while the padding it asks for is over a sixteenth of
    the rows."""
    for rows in (_ROWS, _ROWS // 2):
        if _round_up(n, min(rows, _round_up(n, 8))) - n <= n // 16:
            return rows
    return _ROWS // 4


def _open_tile_v(v: int, widest: int) -> int:
    """The vocabulary tile of a call that left it open: of the
    128-multiples from half of ``widest`` up, the one that pads the
    vocabulary least (the wider of two that pad alike)."""
    if _round_up(v, 128) <= widest:
        return widest  # one tile: ``_padded_dims`` clamps it
    return min(range(widest, widest // 2 - 1, -128),
               key=lambda tile: _round_up(v, tile))


def _plan(n: int, d: int, v: int, x_dtype, w_dtype,
          block_n: int | None = None, block_v: int | None = None) -> _Plan:
    """The tiling of one call, from what the call can see: rows, width,
    vocabulary, operand dtypes. Not the mode: lean and save-s take the same
    tiles (ones that fit VMEM in both), so their sums run in the same order
    and their gradients agree to the last bit. Every consumer — forward,
    backward prologue and the save-s threshold — asks here, so all see the
    SAME (n_pad, v_pad) and the residual's size cannot drift from its
    estimate.
    ``block_n`` / ``block_v`` given are honoured as they come (clamped to
    the problem); left open they take the measured best that fits VMEM."""
    items = jnp.dtype(x_dtype).itemsize, jnp.dtype(w_dtype).itemsize

    def fits(kernel, tile):
        return max(_vmem_bytes(kernel, tile, d, *items, mode)
                   for mode in (True, False)) <= _VMEM_BUDGET

    # rows first, then ever narrower tiles; blocks given over the budget run
    # as given (the loop ends on its last candidate)
    for rows, widest in itertools.product(
            [block_n] if block_n else _halvings(_open_rows(n), 8),
            [block_v] if block_v else _halvings(_TILE_V, 128)):
        bn, bv, n_pad, v_pad = _padded_dims(
            n, v, rows, block_v or _open_tile_v(v, widest))
        if fits("fwd", (bn, bv)) and fits("dx", (bn, bv)):
            break
    # dW: as deep as the row block, the widest divisor of v_pad that fits
    dw_v = next((tile for tile in range(bv, 127, -128)
                 if v_pad % tile == 0 and fits("dw", (bn, tile))), bv)
    return _Plan((bn, bv), (bn, dw_v), n_pad, v_pad)


def _auto_save_s(n: int, d: int, v: int, x_dtype, w_dtype,
                 block_n: int | None, block_v: int | None) -> bool:
    """save_s=None resolution: speed mode iff the padded f32 score
    residual fits the auto budget."""
    plan = _plan(n, d, v, x_dtype, w_dtype, block_n, block_v)
    return plan.n_pad * plan.v_pad * 4 <= SAVE_S_AUTO_MAX_BYTES


def _reference_xent(xn, w, b, ln):
    """Differentiable XLA reference with the SAME out-of-range-label
    semantics as the kernel (loss = lse, no pull-up) —
    ``softmax_cross_entropy`` would CLAMP invalid ids to an edge class,
    silently training differently per backend."""
    v = w.shape[-1]
    logits = (xn @ w + b).astype(jnp.float32)
    m = jnp.max(logits, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[:, None]), axis=-1))
    ids = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    picked = jnp.sum(
        jnp.where(ids == ln[:, None].astype(jnp.int32), logits, 0.0),
        axis=-1,
    )
    valid = (ln >= 0) & (ln < v)
    return jnp.mean(lse - jnp.where(valid, picked, 0.0))


# The unsharded dispatch runs inside a NAMED nested jit so the call
# survives as a recognizably-named pjit equation in any traced step —
# the marker tpudml.analysis rule J107 keys on to flag a full-vocab
# fused-xent call whose W operand is actually vocab-sharded on a mesh
# axis (a partial-vocab softmax that trains wrong silently). The
# sharded wrapper below carries a DIFFERENT name, so the correct
# composition stays silent. XLA inlines inner jits at lowering, so the
# marker costs nothing on the chip.
def _fused_xent_unsharded(x, w, b, labels, block_n, block_v, interpret,
                          save_s):
    if interpret is None:
        if jax.default_backend() != "tpu":
            return _reference_xent(x, w, b, labels)
        interpret = False
    return _fused(x, w, b, labels, block_n, block_v, interpret, save_s)


FUSED_XENT_MARKER = _fused_xent_unsharded.__name__

_fused_xent_unsharded_jit = jax.jit(
    _fused_xent_unsharded, static_argnums=(4, 5, 6, 7)
)


def linear_cross_entropy(
    x: jax.Array,
    w: jax.Array,
    labels: jax.Array,
    bias: jax.Array | None = None,
    *,
    block_n: int | None = None,
    block_v: int | None = None,
    interpret: bool | None = None,
    save_s: bool | None = None,
) -> jax.Array:
    """Mean softmax cross-entropy of ``x @ w [+ bias]`` against integer
    ``labels`` without materializing the [N, V] logits (see module
    docstring).

    ``x`` [..., d] flattens to [N, d]; ``labels`` [...] to [N]. Labels
    outside [0, V) contribute loss = lse (no pull-up) — mask such rows
    out beforehand. ``save_s=True`` is the SPEED mode: it keeps the
    [N_pad, V_pad] f32 scores as a backward residual (2 fewer backward
    matmuls); the
    default ``save_s=None`` resolves it AUTOMATICALLY: speed mode while
    the score residual fits ``SAVE_S_AUTO_MAX_BYTES``, the O(N) lean
    mode beyond (the long-context regimes the memory contract exists
    for). Pass ``False`` to force the O(N) contract regardless.
    ``block_n`` / ``block_v`` left open take the tiles ``_plan`` chooses
    from the shape; given, they are honoured (tests, sweeps). On
    non-TPU backends dispatches to the XLA reference math unless
    ``interpret=True`` forces the Pallas interpreter.

    ``w`` here is the FULL vocab projection. When the head is
    vocab-sharded over a mesh axis, use
    :func:`sharded_linear_cross_entropy` inside the ``shard_map``
    region instead — feeding a vocab shard to this function computes a
    partial-vocab softmax (rule J107 flags exactly that)."""
    d = x.shape[-1]
    v = w.shape[-1]
    xn = x.reshape(-1, d)
    ln = labels.reshape(-1)
    if xn.shape[0] != ln.shape[0]:
        raise ValueError(f"{x.shape} rows != {labels.shape} labels")
    if save_s is None:
        save_s = _auto_save_s(xn.shape[0], d, v, x.dtype, w.dtype, block_n,
                              block_v)
    b = jnp.zeros((v,), w.dtype) if bias is None else bias
    return _fused_xent_unsharded_jit(
        xn, w, b, ln, block_n, block_v, interpret, save_s
    )


# ------------------------------------------------- vocab-sharded variant
# The distributed form of the fused head: each shard of a vocab-sharded
# W ([d, V/W] per chip) streams only its local tiles through the SAME
# Pallas kernels above and emits per-shard partial statistics
# (lse_local, picked_local); shards merge with the online log-sum-exp
# combination rule ring attention uses per arriving K/V block
# (tpudml/parallel/cp.py _merge_blocks), here one pmax + one psum over
# the mesh axis (collectives.plogsumexp). Label semantics do the shard
# routing for free: shifting labels by -shard·V_local makes out-of-shard
# labels out-of-range, which the kernel already maps to picked = 0 — so
# psum(picked_local) recovers the one true pick with no gather.
#
# Backward: p = exp(s_local − lse_GLOBAL) is exactly this shard's slice
# of the global softmax, so the existing backward kernels run unchanged
# with the merged lse as input — dW/db stay 1/W shard-local with NO
# extra collective, and dX comes back as a per-shard partial that the
# enclosing shard_map transpose psums once (W's axis is mentioned in
# its in_spec, x's is not: the single dX reduce is derived, not coded).
# The custom_vjp therefore returns dX UN-summed — summing here too
# would double-count by the axis size.


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _fused_sharded(x, w, b, labels, axis_name, block_n, block_v, interpret,
                   save_s):
    loss, _ = _fused_sharded_fwd(
        x, w, b, labels, axis_name, block_n, block_v, interpret, save_s
    )
    return loss


def _fused_sharded_fwd(x, w, b, labels, axis_name, block_n, block_v,
                       interpret, save_s):
    from tpudml.comm.collectives import plogsumexp

    v_local = w.shape[-1]
    shard = jax.lax.axis_index(axis_name)
    ln = labels.astype(jnp.int32) - shard * v_local
    s = None
    if save_s:
        lse_loc, picked_loc, s = _fused_forward(
            x, w, b, ln, block_n, block_v, interpret, save_s=True
        )
    else:
        lse_loc, picked_loc = _fused_forward(
            x, w, b, ln, block_n, block_v, interpret
        )
    lse = plogsumexp(lse_loc, axis_name)
    picked = jax.lax.psum(picked_loc, axis_name)
    return jnp.mean(lse - picked), (x, w, b, ln, lse, s)


def _fused_sharded_bwd(axis_name, block_n, block_v, interpret, save_s,
                       res, g):
    import numpy as np

    x, w, b, ln, lse, s = res
    # shard_map (check_vma=False) transposition convention: the
    # cotangent of an output whose spec does not mention an axis arrives
    # DIVIDED by that axis size, and body psums transpose to psums —
    # that is how the pure-autodiff reference path regains the factor
    # through the merge collectives' transposes. This custom_vjp
    # replaces those transposes, so it must restore the factor itself:
    # psum of the (replicated) cotangent over the merge axis. Verified
    # by the TP/FSDP/FSDP×TP interpret-mode parity tests — dropping
    # this psum deflates every gradient by exactly the axis size.
    g = jax.lax.psum(g, axis_name)
    if save_s:
        dx, dw, db = _fused_backward_saved(
            x, w, b, ln, lse, s, g, block_n, block_v, interpret
        )
    else:
        dx, dw, db = _fused_backward(
            x, w, b, ln, lse, g, block_n, block_v, interpret
        )
    # dx is this shard's PARTIAL over its vocab slice — the shard_map
    # transpose supplies the one cross-shard reduce (see block comment).
    return dx, dw, db, np.zeros(ln.shape, dtype=jax.dtypes.float0)


_fused_sharded.defvjp(_fused_sharded_fwd, _fused_sharded_bwd)


def _sharded_reference(xn, w, b, ln, axis_name):
    """Differentiable sharded XLA reference (non-TPU dispatch): local
    partial-vocab statistics merged with the identical plogsumexp/psum
    rule. Grad-exact vs the unsharded reference by construction —
    autodiff of the merge reproduces p = exp(s − lse_global) per
    shard."""
    from tpudml.comm.collectives import plogsumexp

    v_local = w.shape[-1]
    shard = jax.lax.axis_index(axis_name)
    ln = ln.astype(jnp.int32) - shard * v_local
    logits = (xn @ w + b).astype(jnp.float32)
    m = jnp.max(logits, axis=-1)
    lse_loc = m + jnp.log(jnp.sum(jnp.exp(logits - m[:, None]), axis=-1))
    lse = plogsumexp(lse_loc, axis_name)
    ids = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    picked_loc = jnp.sum(
        jnp.where(ids == ln[:, None], logits, 0.0), axis=-1
    )
    valid = (ln >= 0) & (ln < v_local)
    picked = jax.lax.psum(jnp.where(valid, picked_loc, 0.0), axis_name)
    return jnp.mean(lse - picked)


# Named marker for the CORRECT sharded composition — distinct from
# FUSED_XENT_MARKER, so J107 stays silent on it.
def _fused_xent_sharded(x, w, b, labels, axis_name, block_n, block_v,
                        interpret, save_s):
    if interpret is None:
        if jax.default_backend() != "tpu":
            return _sharded_reference(x, w, b, labels, axis_name)
        interpret = False
    return _fused_sharded(
        x, w, b, labels, axis_name, block_n, block_v, interpret, save_s
    )


SHARDED_XENT_MARKER = _fused_xent_sharded.__name__

_fused_xent_sharded_jit = jax.jit(
    _fused_xent_sharded, static_argnums=(4, 5, 6, 7, 8)
)


def sharded_linear_cross_entropy(
    x: jax.Array,
    w: jax.Array,
    labels: jax.Array,
    bias: jax.Array | None = None,
    *,
    axis_name: str,
    block_n: int | None = None,
    block_v: int | None = None,
    interpret: bool | None = None,
    save_s: bool | None = None,
) -> jax.Array:
    """Vocab-sharded :func:`linear_cross_entropy`: call INSIDE a
    ``shard_map`` region where ``axis_name`` is bound, with ``w`` the
    LOCAL [d, V/W] vocab shard (``bias`` its [V/W] slice) and ``labels``
    GLOBAL ids; every shard must hold the same ``x`` rows. Returns the
    replicated global mean loss — identical to the unsharded call on
    the concatenated W, to float tolerance (pinned by parity tests under
    TP, FSDP, and FSDP×TP meshes).

    ``save_s=None`` auto-resolves against the LOCAL vocab: the f32
    score residual is N_pad·(V/W)_pad·4 bytes PER SHARD — 1/W of the
    unsharded residual — so sharding widens the regime where the speed
    mode fits ``SAVE_S_AUTO_MAX_BYTES``. Gradient contract: dW/db are
    shard-local (1/W per chip, no collective); dX is returned as a
    per-shard partial for the enclosing shard_map transpose to reduce
    once."""
    d = x.shape[-1]
    v_local = w.shape[-1]
    xn = x.reshape(-1, d)
    ln = labels.reshape(-1)
    if xn.shape[0] != ln.shape[0]:
        raise ValueError(f"{x.shape} rows != {labels.shape} labels")
    if save_s is None:
        save_s = _auto_save_s(xn.shape[0], d, v_local, x.dtype, w.dtype,
                              block_n, block_v)
    b = jnp.zeros((v_local,), w.dtype) if bias is None else bias
    return _fused_xent_sharded_jit(
        xn, w, b, ln, axis_name, block_n, block_v, interpret, save_s
    )
