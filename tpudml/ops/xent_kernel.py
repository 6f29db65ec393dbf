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
- backward, save-s mode (round 4): the forward additionally streams its
  f32 score tiles to HBM, and both backward kernels read them instead
  of recomputing — the backward drops from 4 matmuls' worth of MXU work
  to the 2 the cotangents actually need (recomputing s cost ~2 ms at
  [8192,512]×[512,32k]; XLA's lean path wins at memory-fitting sizes
  for exactly this reason — it keeps the logits). Saved scores are f32,
  so gradients are bit-identical to the lean mode's recomputation. The
  trade is an N_pad·V_pad·4-byte residual in place of the O(N)
  contract; since round 5 the DEFAULT (``save_s=None``) picks the mode
  automatically — save-s while that residual fits
  ``SAVE_S_AUTO_MAX_BYTES`` (2 GiB), the lean O(N) contract beyond
  (measured in-situ: save-s 19.29 ms/step vs lean 21.54 at the
  flagship, BASELINE.md round 5). Pass ``save_s=False`` to force the
  O(N) guarantee regardless of size.

Exactness: same math as ``softmax_cross_entropy`` over the materialized
logits (f32 statistics); pinned by tests against the XLA reference.
Dispatch: compiled kernel on TPU; reference math elsewhere (tests force
``interpret=True``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


from tpudml.ops.tiling import WIDE_TILE_PARAMS
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
    block_n, block_v, n_pad, v_pad = _padded_dims(n, v, block_n, block_v)
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
        compiler_params=WIDE_TILE_PARAMS,
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


def _bwd_prologue(x, w, labels, lse, block_n, block_v):
    """Shared backward setup for BOTH modes: block clamping and the
    padded-row contract — labels pad to -1 (match no column) and lse
    pads to +inf so p = exp(s − lse) = 0 on padded rows, making their
    dlogits exactly zero in every backward kernel."""
    n, d = x.shape
    _, v = w.shape
    block_n, block_v, n_pad, v_pad = _padded_dims(n, v, block_n, block_v)
    xf = jnp.pad(x, ((0, n_pad - n), (0, 0))) if n_pad != n else x
    wf = jnp.pad(w, ((0, 0), (0, v_pad - v))) if v_pad != v else w
    lf = jnp.pad(labels.astype(jnp.int32), (0, n_pad - n),
                 constant_values=-1)[:, None]
    lsef = jnp.pad(lse.astype(jnp.float32), (0, n_pad - n),
                   constant_values=jnp.inf)[:, None]
    return n, d, v, block_n, block_v, n_pad, v_pad, xf, wf, lf, lsef


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


def _pick_bv_dw(v_pad: int, block_v: int, bv_cap: int) -> int:
    """dW vocab tile: ``block_v`` when it already meets the VMEM cap,
    else the largest 128-multiple divisor of ``v_pad`` under the cap —
    repeated halving could strand a non-power-of-two ``block_v`` (e.g.
    384) above it. When ``block_v`` exceeds the cap it is ≥ 256 and a
    multiple of 128 (small vocabs clamp block_v to v_pad ≤ cap), so 128
    always divides ``v_pad`` and the search cannot come up empty; the
    ``block_v`` fallback keeps the pre-search behavior (tile above cap)
    for any exotic hand-picked block size."""
    cap = max(128, bv_cap)
    if block_v <= cap:
        return block_v
    for cand in range(cap - cap % 128, 127, -128):
        if v_pad % cand == 0:
            return cand
    return block_v


def _fused_backward_saved(x, w, b, labels, lse, s, g, block_n, block_v,
                          interpret):
    (n, d, v, block_n, block_v, n_pad, v_pad, xf, wf, lf, lsef
     ) = _bwd_prologue(x, w, labels, lse, block_n, block_v)
    assert s.shape == (n_pad, v_pad), (s.shape, n_pad, v_pad)
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
        compiler_params=WIDE_TILE_PARAMS,
    )(s, wf, lf, lsef)[:n]
    # dW tile cap: the f32 s tiles + f32 accumulator must fit scoped VMEM
    # (~16 MB): 4·d·bv (acc) + 8·bn·bv (s ×2 buffers) + 8·d·bv (dw out
    # ×2, f32 worst case) ≤ ~12 MB. Pick the largest 128-multiple divisor
    # of v_pad under the cap (_pick_bv_dw) — 128 always qualifies.
    bv_cap = max(
        128, (12 * 1024 * 1024) // (12 * d + 8 * block_n) // 128 * 128
    )
    bv_dw = _pick_bv_dw(v_pad, block_v, bv_cap)
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
        compiler_params=WIDE_TILE_PARAMS,
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
    (n, d, v, block_n, block_v, n_pad, v_pad, xf, wf, lf, lsef
     ) = _bwd_prologue(x, w, labels, lse, block_n, block_v)
    # The dW kernel holds a [d, block_v] f32 scratch PLUS double-buffered
    # [d, block_v] in/out W tiles; cap its vocab tile so the working set
    # stays under the ~16 MB scoped-VMEM limit (5 live [d, bv] f32 tiles
    # + x/dlog  ->  bv <= 12 MB / (5 * 4 * d)).
    bv_budget = max(128, (12 * 1024 * 1024) // (5 * 4 * d) // 128 * 128)
    block_v_dw = min(block_v, bv_budget)
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
        compiler_params=WIDE_TILE_PARAMS,
    )(xf, wf, bf, lf, lsef)[:n]
    v_pad_dw = _round_up(v, block_v_dw)
    wfd = jnp.pad(w, ((0, 0), (0, v_pad_dw - v))) if v_pad_dw != v else w
    bfd = (jnp.pad(b, (0, v_pad_dw - v)) if v_pad_dw != v else b)[None, :]
    dw, db = pl.pallas_call(
        partial(_dw_kernel, block_v=block_v_dw, v_valid=v, inv_n=1.0 / n),
        out_shape=[
            jax.ShapeDtypeStruct(wfd.shape, w.dtype),
            jax.ShapeDtypeStruct((1, v_pad_dw), jnp.float32),
        ],
        grid=(1, v_pad_dw // block_v_dw, n_pad // block_n),
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
        compiler_params=WIDE_TILE_PARAMS,
    )(wfd, xf, bfd, lf, lsef)
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


# save_s auto threshold (round 5, VERDICT r4 item 5): the speed mode's
# f32 score residual is N_pad·V_pad·4 bytes; keep it on by default while
# that stays a modest slice of v5e-class HBM (16 GB) and fall back to the
# O(N) lean mode beyond. 2 GiB covers the flagship (8k×32k = 1 GiB) and
# the chip-filling config (16k×32k = 2 GiB) with room for the model;
# 131k-token long-context regimes (16 GiB of scores) auto-drop to lean —
# exactly the regime the O(N) contract exists for. The speed win was
# measured at kernel granularity in round 5 (2026-07-31, older than this
# code).
SAVE_S_AUTO_MAX_BYTES = 2 * 1024**3


def _padded_dims(n: int, v: int, block_n: int, block_v: int):
    """The kernel tiling rule, in one place: clamp blocks to the
    rounded-up problem (rows to 8, vocab to 128), pad the problem to a
    block multiple. Every consumer — forward, backward prologue, and
    the save-s auto threshold — must see the SAME (block_n, block_v,
    n_pad, v_pad) or residual-size estimates drift from reality."""
    block_n = min(block_n, _round_up(n, 8))
    block_v = min(block_v, _round_up(v, 128))
    return block_n, block_v, _round_up(n, block_n), _round_up(v, block_v)


def _auto_save_s(n: int, v: int, block_n: int, block_v: int) -> bool:
    """save_s=None resolution: speed mode iff the padded f32 score
    residual fits the auto budget."""
    _, _, n_pad, v_pad = _padded_dims(n, v, block_n, block_v)
    return n_pad * v_pad * 4 <= SAVE_S_AUTO_MAX_BYTES


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
    block_n: int = 256,
    block_v: int = 2048,
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
    matmuls — 8.21 → 5.97 ms at [8192,32k] at kernel granularity,
    21.54 → 19.29 ms/step in-situ: round 5, 2026-07-31, older than this
    code); the
    default ``save_s=None`` resolves it AUTOMATICALLY: speed mode while
    the score residual fits ``SAVE_S_AUTO_MAX_BYTES``, the O(N) lean
    mode beyond (the long-context regimes the memory contract exists
    for). Pass ``False`` to force the O(N) contract regardless. On
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
        save_s = _auto_save_s(xn.shape[0], v, block_n, block_v)
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
    block_n: int = 256,
    block_v: int = 2048,
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
        save_s = _auto_save_s(xn.shape[0], v_local, block_n, block_v)
    b = jnp.zeros((v_local,), w.dtype) if bias is None else bias
    return _fused_xent_sharded_jit(
        xn, w, b, ln, axis_name, block_n, block_v, interpret, save_s
    )
