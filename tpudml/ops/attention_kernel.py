"""Fused attention kernels (Pallas, TPU) — flash-attention tiling, both
directions, each one algorithm at two tilings chosen from T, head dim and
dtype alone (``_forward_plan``, ``_backward_plan``).

Forward: softmax(QKᵀ)V with BOTH operands blocked — the [T, T] score matrix
never exists: an online softmax over K/V tiles (running max m, normalizer
l, f32 accumulator, rescaled by exp(m_prev − m_new) a tile); the row
log-sum-exp is emitted as a residual.
- resident (``flash_fwd_resident[_rows]``): a head's Q, K, V are one VMEM
  block, the tile loops run inside the kernel, m, l, the accumulator values;
- streaming (``flash_fwd``): K/V tiles stream through the grid into scratch,
  VMEM O(block_q·D + block_k·D) whatever T: heads too long to sit in VMEM,
  ring attention's and the serving prefill's ``flash_forward_lse``.

Backward: the flash recipe — no O(T²) transient. With the forward's lse and
Δ = rowsum(dO ⊙ O), each visible score tile is recomputed: p = exp(s − lse),
dp = dO·Vᵀ, ds = p ⊙ (dp − Δ); dV += pᵀ·dO, dK += scale · dsᵀ·Q, dQ +=
scale · ds·K, float32 accumulators.
- one pass (``flash_bwd[_rows]``): a head's Q, K, V, dO, O, lse are one VMEM
  block, every tile is recomputed once for all three products, Δ inside;
- two kernels (``flash_bwd_dq``, ``flash_bwd_dkv``) stream tiles and
  recompute each twice: long heads, ring attention's ``flash_block_grads``.
The resident kernels read the model's own [B, T, H·D] rows where H·D splits
into lane blocks of whole heads (``*_rows``: no fold copy; D 64 x T 1024, both
directions, 8,192 tokens: 1.44 ms folded with its copies, 0.88 from the rows).
Causal runs skip the tiles above the diagonal; Q and K pad independently, masks
use global positions: any T works. Off the TPU ``flash_attention`` is reference
math unless ``interpret=True`` (tests); ``blocked_backward=False``: its vjp.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudml.nn.attention import NEG_INF, dot_product_attention


from tpudml.ops.tiling import round_up as _round_up  # shared tiling helper


def _plan(t: int, block_q: int, block_k: int) -> tuple[int, int, int, int]:
    """(block_q, block_k, t_pad_q, t_pad_k): blocks are capped from above
    at round_up(t, 8) (so tiny T doesn't allocate oversized tiles), never
    raised — callers control the lower bound; Q/K pad independently."""
    block_q = min(block_q, _round_up(t, 8))
    block_k = min(block_k, _round_up(t, 8))
    return block_q, block_k, _round_up(t, block_q), _round_up(t, block_k)


def _fold_pad(arrays, b, h, t, d, t_pad):
    """[B, T, H, D] → [B·H, T_pad, D] per array (shared by fwd/bwd so the
    layouts can never diverge)."""
    out = []
    for x in arrays:
        f = x.transpose(0, 2, 1, 3).reshape(b * h, t, d)
        if t_pad != t:
            f = jnp.pad(f, ((0, 0), (0, t_pad - t), (0, 0)))
        out.append(f)
    return out


def _unfold(x, b, h, t, d):
    return x[:, :t].reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _scores(q, k, qi, kj, *, scale, causal, block_q, block_k, t_valid, nk,
            k_shift=0):
    """Recomputable masked score tile [block_q, block_k] in f32.
    ``k_shift`` offsets the causal diagonal (striped ring layout: blocks
    from later-striped devices are visible only STRICTLY below it)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        s = jnp.where(q_pos >= k_pos + k_shift, s, NEG_INF)
    if t_valid != block_k * nk:  # static: nk is a trace-time constant
        # Padded keys (K rounded up to its tile multiple) must get no
        # attention mass; padded Q rows are sliced off outside.
        s = jnp.where(k_pos < t_valid, s, NEG_INF)
    return s


# --------------------------------------------------------------- forward


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                scale: float, causal: bool, block_q: int, block_k: int,
                t_valid: int, k_shift: int = 0):
    kj = pl.program_id(2)
    qi = pl.program_id(1)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)

    def fold_block():
        s = _scores(
            q_ref[0], k_ref[0], qi, kj, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, t_valid=t_valid, nk=nk,
            k_shift=k_shift,
        )
        m_prev = m_ref[:]  # [block_q, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0]
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = m_new

    if causal:
        # Skip K tiles entirely above the (shifted) diagonal: tile
        # (qi, kj) contributes only if its last query row can attend its
        # first key.
        pl.when((qi + 1) * block_q - 1 >= kj * block_k + k_shift)(fold_block)
    else:
        fold_block()

    @pl.when(kj == nk - 1)
    def _():
        o_ref[0] = (acc_ref[:] / l_ref[:]).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:] + jnp.log(l_ref[:])


def _flash_forward(q, k, v, causal, block_q, block_k, interpret, k_shift=0):
    """Returns (out [B,T,H,D], lse [B·H, t_pad_q, 1] f32)."""
    b, t, h, d = q.shape
    scale = 1.0 / (d ** 0.5)
    block_q, block_k, t_pad_q, t_pad_k = _plan(t, block_q, block_k)
    (qf,) = _fold_pad((q,), b, h, t, d, t_pad_q)
    kf, vf = _fold_pad((k, v), b, h, t, d, t_pad_k)
    out, lse = pl.pallas_call(
        partial(
            _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, t_valid=t, k_shift=k_shift,
        ),
        out_shape=[
            jax.ShapeDtypeStruct(qf.shape, q.dtype),
            jax.ShapeDtypeStruct((b * h, t_pad_q, 1), jnp.float32),
        ],
        grid=(b * h, t_pad_q // block_q, t_pad_k // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, kj: (bh, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, i, kj: (bh, kj, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, i, kj: (bh, kj, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, kj: (bh, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, i, kj: (bh, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),  # output accumulator
            pltpu.VMEM((block_q, 1), jnp.float32),  # running max
            pltpu.VMEM((block_q, 1), jnp.float32),  # running normalizer
        ],
        name="flash_fwd",
        interpret=interpret,
    )(qf, kf, vf)
    return _unfold(out, b, h, t, d), lse


# -------------------------------------------------------------- backward


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, scale, causal, block_q, block_k, t_valid,
               k_shift: int = 0):
    kj = pl.program_id(2)
    qi = pl.program_id(1)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def fold_block():
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = _scores(
            q_ref[0], k, qi, kj, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, t_valid=t_valid, nk=nk,
            k_shift=k_shift,
        )
        p = jnp.exp(s - lse_ref[0])  # lse_ref[0]: [bq, 1]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_ref[0])
        acc_ref[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        pl.when((qi + 1) * block_q - 1 >= kj * block_k + k_shift)(fold_block)
    else:
        fold_block()

    @pl.when(kj == nk - 1)
    def _():
        dq_ref[0] = (acc_ref[:] * scale).astype(dq_ref.dtype)


def _dkdv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                 dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal, block_q,
                 block_k, t_valid, nk, k_shift: int = 0):
    qi = pl.program_id(2)
    kj = pl.program_id(1)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def fold_block():
        q = q_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = _scores(
            q, k_ref[0], qi, kj, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, t_valid=t_valid, nk=nk,
            k_shift=k_shift,
        )
        p = jnp.exp(s - lse_ref[0])  # lse_ref[0]: [bq, 1]
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # pᵀ·dO → [bk, d]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_ref[0])
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # dsᵀ·Q → [bk, d]

    if causal:
        pl.when((qi + 1) * block_q - 1 >= kj * block_k + k_shift)(fold_block)
    else:
        fold_block()

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, o, lse, g, causal, block_q, block_k, interpret):
    b, t, h, d = q.shape
    calls, *plan = _backward_plan(
        t, d, q.dtype, block_q, block_k, _heads_per_block(h, d))
    if calls is _backward_one_pass:  # a head resident in VMEM; Δ inside
        return calls(q, k, v, o, lse, g, causal, *plan, interpret)
    t_pad_q, t_pad_k = plan[2:]  # the dQ and dK/dV kernels, heads folded
    qf, dof, of = _fold_pad((q, g, o), b, h, t, d, t_pad_q)
    kf, vf = _fold_pad((k, v), b, h, t, d, t_pad_k)
    delta = jnp.sum(  # Δ = rowsum(dO ⊙ O) [B·H, t_pad_q, 1], once outside
        dof.astype(jnp.float32) * of.astype(jnp.float32), axis=-1, keepdims=True)
    lse = _stat_columns(lse, b * h, t, t_pad_q)
    grads = calls(qf, kf, vf, dof, lse, delta, b, h, t, d, causal, *plan, interpret)
    return tuple(_unfold(x, b, h, t, d) for x in grads)


def _backward_calls(qf, kf, vf, dof, lse, delta, b, h, t, d, causal, block_q,
                    block_k, t_pad_q, t_pad_k, interpret, k_shift=0):
    """The two backward pallas_calls on pre-folded [B·H, t_pad, ·] inputs
    (shared by the full backward and the per-block ring entry point)."""
    scale = 1.0 / (d ** 0.5)
    bh = b * h
    nq, nk = t_pad_q // block_q, t_pad_k // block_k
    q_spec = pl.BlockSpec((1, block_q, d), lambda i, j, r: (i, j, 0))
    row_spec = pl.BlockSpec((1, block_q, 1), lambda i, j, r: (i, j, 0))

    dqf = pl.pallas_call(
        partial(
            _dq_kernel, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, t_valid=t, k_shift=k_shift,
        ),
        out_shape=jax.ShapeDtypeStruct(qf.shape, qf.dtype),
        grid=(bh, nq, nk),
        in_specs=[
            q_spec,
            pl.BlockSpec((1, block_k, d), lambda bh, i, kj: (bh, kj, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, i, kj: (bh, kj, 0)),
            q_spec,
            row_spec,
            row_spec,
        ],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        name="flash_bwd_dq",
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta)

    k_spec = pl.BlockSpec((1, block_k, d), lambda bh, kj, i: (bh, kj, 0))
    qrow_spec = pl.BlockSpec((1, block_q, d), lambda bh, kj, i: (bh, i, 0))
    lrow_spec = pl.BlockSpec((1, block_q, 1), lambda bh, kj, i: (bh, i, 0))
    dkf, dvf = pl.pallas_call(
        partial(
            _dkdv_kernel, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, t_valid=t, nk=nk, k_shift=k_shift,
        ),
        out_shape=[
            jax.ShapeDtypeStruct(kf.shape, kf.dtype),
            jax.ShapeDtypeStruct(vf.shape, vf.dtype),
        ],
        grid=(bh, nk, nq),
        in_specs=[k_spec, k_spec, qrow_spec, qrow_spec, lrow_spec, lrow_spec],
        out_specs=[k_spec, k_spec],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        name="flash_bwd_dkv",
        interpret=interpret,
    )(kf, vf, qf, dof, lse, delta)

    return dqf, dkf, dvf


# ------------------------------------------------- blockwise entry points
#
# Ring context parallelism (tpudml.parallel.cp) composes attention from
# per-K/V-block partials: each arriving block runs a flash forward that
# also RETURNS its log-sum-exp so blocks merge exactly, and the ring
# backward re-runs the tile kernels per block with the GLOBALLY-merged
# softmax statistics (lse, Δ) — the flash decomposition dq = Σ_b ds_b·K_b,
# dk_b = ds_bᵀ·Q with p_b = exp(s_b − lse_global).


def _fold_rows(x, t_pad):
    """[B, H, T] → [B·H, t_pad, 1] (row-statistic layout of the kernels)."""
    b, h, t = x.shape
    f = x.reshape(b * h, t, 1)
    if t_pad != t:
        f = jnp.pad(f, ((0, 0), (0, t_pad - t), (0, 0)))
    return f


def flash_forward_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    k_shift: int = 0,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Flash forward that also returns the row log-sum-exp.

    Returns (out [B,T,H,D], lse [B,H,T] f32). ``causal`` here masks by
    LOCAL tile positions — for a ring block pair this is exactly the
    diagonal (same-length, aligned) block; off-diagonal visible blocks
    pass causal=False. ``k_shift=1`` makes the diagonal strict (the
    striped ring layout's later-device blocks).
    """
    b, t, h, d = q.shape
    (default_fwd_bq, _), default_bk = _default_blocks(d)
    out, lse = _flash_forward(
        q, k, v, causal,
        default_fwd_bq if block_q is None else block_q,
        default_bk if block_k is None else block_k,
        interpret, k_shift=k_shift,
    )
    return out, lse[:, :t, 0].reshape(b, h, t)


def flash_block_grads(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    do: jax.Array,
    lse: jax.Array,
    delta: jax.Array,
    *,
    causal: bool = False,
    k_shift: int = 0,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-block flash backward with EXTERNAL softmax statistics.

    ``lse``/``delta`` [B,H,T] come from the globally-merged attention
    (delta = rowsum(dO ⊙ O_final)), so the returned (dq, dk, dv) are this
    block's exact contributions to the global gradients; summing over
    blocks reproduces the full backward.
    """
    b, t, h, d = q.shape
    (_, default_bwd_bq), default_bk = _default_blocks(d)
    if block_q is None:
        block_q = default_bwd_bq
    if block_k is None:
        block_k = default_bk
    block_q, block_k, t_pad_q, t_pad_k = _plan(t, block_q, block_k)
    qf, dof = _fold_pad((q, do), b, h, t, d, t_pad_q)
    kf, vf = _fold_pad((k, v), b, h, t, d, t_pad_k)
    lsef = _fold_rows(lse.astype(jnp.float32), t_pad_q)
    deltaf = _fold_rows(delta.astype(jnp.float32), t_pad_q)
    dqf, dkf, dvf = _backward_calls(
        qf, kf, vf, dof, lsef, deltaf, b, h, t, d, causal, block_q, block_k,
        t_pad_q, t_pad_k, interpret, k_shift=k_shift,
    )
    return tuple(_unfold(x, b, h, t, d) for x in (dqf, dkf, dvf))


# ------------------------------------------------ a head resident in VMEM
#
# Below the entry points the serving programs call, so that their source
# lines, which a compiled program's fingerprint holds, stay where they were.
#
# Both resident kernels read and write ROWS [B', t_pad, H'·D], one lane block
# of ``heads`` whole heads a program, in one of two operand forms chosen from
# (H, D) alone (``_heads_per_block``):
# - the model's own rows (``*_rows`` in the kernel's name): [B, T, H, D] seen
#   as [B, T, H·D], a bitcast of what the projections write, where H·D splits
#   into lane blocks: a head where D % 128 == 0, 128 // D neighbouring heads
#   where D divides 128 and the head count divides so. No transpose, no pad of
#   a 64-wide minor dimension to 128 lanes exists around the kernels;
# - folded: every other shape (an odd head count at D 64, D 96) goes through
#   ``_fold_pad`` to [B·H, t_pad, D], a head a block: B' = B·H, H' = 1.
# Heads that share a block share its lanes: head i's scores are taken with
# the other heads' lanes of Q (and dO) zeroed, against the whole K (and V)
# block — the zeros add exactly nothing to the float32 accumulation, and the
# MXU, 128 deep and wide, runs the passes a 64-wide head alone would; P·V
# and dS·K come out 128 lanes wide, of which head i's are kept; Qᵀ, dOᵀ and
# the transposed dK, dV accumulators hold the heads along sublanes, where a
# slice at a multiple of D is tile-aligned. lse is rows too, [B', H'/heads,
# heads, t_pad] float32 (a [T, 1] column is stored 128-fold), turned between
# row and column a Q tile inside the kernels.


def _heads_per_block(h: int, d: int) -> int:
    """Heads in one lane block of the model's [B, T, H·D] rows; 0 where the
    rows do not split into whole lane blocks of whole heads (fold)."""
    if d % 128 == 0:
        return 1
    if 128 % d == 0 and h % (128 // d) == 0:
        return 128 // d
    return 0


def _rows(arrays, b, h, t, d, t_pad, heads):
    """[B, T, H, D] → [B', t_pad, H'·D] per array: the model's own rows
    (``heads`` > 0; a copy only where T is off the tile), else folded."""
    if not heads:
        return _fold_pad(arrays, b, h, t, d, t_pad)
    rows = [x.reshape(b, t, h * d) for x in arrays]
    if t_pad != t:
        rows = [jnp.pad(x, ((0, 0), (0, t_pad - t), (0, 0))) for x in rows]
    return rows


def _from_rows(x, b, h, t, d, heads):
    return x[:, :t].reshape(b, t, h, d) if heads else _unfold(x, b, h, t, d)


def _row_grid(b, h, heads):
    """(B', lane blocks a row, heads a block) of either operand form: the
    grid of a resident kernel is its first two."""
    return (b, h // heads, heads) if heads else (b * h, 1, 1)


def _stat_rows(lse, shape, t, t_pad):
    """lse as the one-pass backward reads it, rows of ``shape`` [B', G,
    heads, t_pad]: from the resident forward's rows (padded to the
    forward's own Q tile) or the streaming forward's [B·H, ·, 1] columns."""
    if lse.ndim == 3:
        lse = lse[:, :, 0]
    if lse.shape[-1] != t_pad:
        lse = lse[..., :t]
        lse = jnp.pad(lse, [(0, 0)] * (lse.ndim - 1) + [(0, t_pad - t)])
    return lse.reshape(shape)


def _stat_columns(lse, bh, t, t_pad):
    """lse as the dQ and dK/dV kernels read it, [B·H, ≥ t_pad, 1] columns:
    the streaming forward's own, or the resident forward's rows folded."""
    if lse.ndim == 3:
        return lse
    return _fold_rows(lse.reshape(1, bh, -1)[:, :, :t], t_pad)


def _turned(x):
    """A [n, 1] column as a [1, n] row, or a row as a column: through a full
    128-wide tile, which is the transpose the chip has."""
    if x.shape[1] == 1:
        return jnp.broadcast_to(x, (x.shape[0], 128)).T[:1]
    return jnp.broadcast_to(x, (128, x.shape[1])).T[:, :1]


def _head_lanes(rows: int, width: int, heads: int):
    """For each head of a block, the mask [rows, width] of its own lanes
    (None where the block is one head)."""
    if heads == 1:
        return [None]
    head = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1) // (width // heads)
    return [head == i for i in range(heads)]


def _own(lanes, x):
    """``x`` with the lanes of the block's other heads zeroed."""
    return x if lanes is None else jnp.where(lanes, x, jnp.zeros_like(x))


def _one_pass_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                     dq_ref, dk_ref, dv_ref, dq_acc, qt_ref, dot_ref,
                     lse_col, delta_col, *, scale, causal, block_q, block_k,
                     t_valid, heads):
    """One lane block of ``heads`` heads resident in VMEM: the loops over the
    visible (K tile, Q tile) pairs run here, unrolled (their bounds are
    static, so the pairs above the causal diagonal do not exist), and each
    score tile is recomputed once and feeds dV, dK and dQ.

    dV and dK accumulate transposed, [D, block_k] += dOᵀ·P and Qᵀ·dS, so P
    and dS enter all three products as the [block_q, block_k] tiles they
    are: Q and dO turn once a block and dK, dV once a K tile, where Pᵀ and
    dSᵀ would turn once a pair. Δ = rowsum(dO ⊙ O) over a head's lanes is
    taken here, from blocks the kernel holds anyway, and kept with lse as a
    column a head."""
    nq = q_ref.shape[1] // block_q
    nk = k_ref.shape[1] // block_k
    width = q_ref.shape[2]
    d = width // heads
    nn = (((1,), (0,)), ((), ()))
    lanes = _head_lanes(block_q, width, heads)
    dq_acc[:] = jnp.zeros_like(dq_acc)
    qt_ref[:] = q_ref[0].T
    dot_ref[:] = do_ref[0].T
    for qi in range(nq):
        qs = slice(qi * block_q, (qi + 1) * block_q)
        do_o = (do_ref[0, qs, :].astype(jnp.float32)
                * o_ref[0, qs, :].astype(jnp.float32))
        for i in range(heads):
            lse_col[i, qs, :] = _turned(lse_ref[0, 0, i:i + 1, qs])
            delta_col[i, qs, :] = jnp.sum(
                _own(lanes[i], do_o), axis=-1, keepdims=True)
    for kj in range(nk):
        ks = slice(kj * block_k, (kj + 1) * block_k)
        k = k_ref[0, ks, :]
        v = v_ref[0, ks, :]
        dkt = [jnp.zeros((d, block_k), jnp.float32)] * heads
        dvt = list(dkt)
        for qi in range(kj * block_k // block_q if causal else 0, nq):
            qs = slice(qi * block_q, (qi + 1) * block_q)
            q = q_ref[0, qs, :]
            do = do_ref[0, qs, :]
            for i in range(heads):
                own = slice(i * d, (i + 1) * d)
                s = _scores(
                    _own(lanes[i], q), k, qi, kj, scale=scale,
                    # a tile wholly below the diagonal needs no causal mask
                    causal=causal and qi * block_q < (kj + 1) * block_k - 1,
                    block_q=block_q, block_k=block_k, t_valid=t_valid, nk=nk,
                )
                p = jnp.exp(s - lse_col[i, qs, :])
                dp = jax.lax.dot_general(
                    _own(lanes[i], do), v, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                ds = (p * (dp - delta_col[i, qs, :])).astype(k.dtype)
                dvt[i] += jax.lax.dot_general(
                    dot_ref[own, qs], p.astype(k.dtype), nn,
                    preferred_element_type=jnp.float32)
                dkt[i] += jax.lax.dot_general(
                    qt_ref[own, qs], ds, nn, preferred_element_type=jnp.float32)
                dq_i = jax.lax.dot_general(
                    ds, k, nn, preferred_element_type=jnp.float32)
                dq = dq_i if i == 0 else jnp.where(lanes[i], dq_i, dq)
            dq_acc[qs, :] += dq
        dk_ref[0, ks, :] = (
            jnp.concatenate(dkt, axis=0).T * scale).astype(dk_ref.dtype)
        dv_ref[0, ks, :] = jnp.concatenate(dvt, axis=0).T.astype(dv_ref.dtype)
    dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


@partial(jax.jit, static_argnums=(6, 7, 8, 9, 10, 11))
def _backward_one_pass(q, k, v, o, lse, g, causal, block_q, block_k, t_pad_q,
                       t_pad_k, interpret):
    """The backward as one pallas_call, a lane block of heads a program:
    (dQ, dK, dV) [B, T, H, D]. Under a jit of its own, as the forward is: a
    model's layers of one shape trace the unrolled kernel once, not once a
    layer (gpt2-medium's step: 24 x 2 kernels, 4 s of its 12.8 to trace)."""
    b, t, h, d = q.shape
    heads = _heads_per_block(h, d)
    qr, dor, orr = _rows((q, g, o), b, h, t, d, t_pad_q, heads)
    kr, vr = _rows((k, v), b, h, t, d, t_pad_k, heads)
    n, blocks, per = _row_grid(b, h, heads)
    width = per * d
    lse = _stat_rows(lse, (n, blocks, per, t_pad_q), t, t_pad_q)

    def block(t_pad):
        return pl.BlockSpec((1, t_pad, width), lambda i, j: (i, 0, j))

    stats = pl.BlockSpec((1, 1, per, t_pad_q), lambda i, j: (i, j, 0, 0))
    grads = pl.pallas_call(
        partial(
            _one_pass_kernel, scale=1.0 / (d ** 0.5), causal=causal,
            block_q=block_q, block_k=block_k, t_valid=t, heads=per,
        ),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (qr, kr, vr)],
        grid=(n, blocks),
        in_specs=[block(t_pad_q), block(t_pad_k), block(t_pad_k),
                  block(t_pad_q), block(t_pad_q), stats],
        out_specs=[block(t_pad_q), block(t_pad_k), block(t_pad_k)],
        scratch_shapes=[
            pltpu.VMEM((t_pad_q, width), jnp.float32),  # dQ accumulator
            pltpu.VMEM((width, t_pad_q), qr.dtype),  # Qᵀ
            pltpu.VMEM((width, t_pad_q), qr.dtype),  # dOᵀ
            pltpu.VMEM((per, t_pad_q, 1), jnp.float32),  # lse, a column a head
            pltpu.VMEM((per, t_pad_q, 1), jnp.float32),  # Δ
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_ONE_PASS_VMEM_LIMIT),
        name="flash_bwd_rows" if heads else "flash_bwd",
        interpret=interpret,
    )(qr, kr, vr, dor, orr, lse)
    return tuple(_from_rows(x, b, h, t, d, heads) for x in grads)


def _resident_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                         block_q, block_k, t_valid, heads):
    """``_fwd_kernel``'s online softmax over one lane block of ``heads``
    heads resident in VMEM: the loops over the visible (Q tile, K tile)
    pairs run here, unrolled (static bounds: the pairs above the causal
    diagonal do not exist), and a Q tile's running max, normaliser and
    float32 accumulator are values carried across its K tiles, not scratch
    read and rescaled a grid step; O and lse are written once a Q tile. K
    tile 0 opens the state, which is what ``_fwd_kernel`` computes there from
    m = -inf, l = 0 (every row sees key 0 in it: no ``k_shift`` here)."""
    nq = q_ref.shape[1] // block_q
    nk = k_ref.shape[1] // block_k
    nn = (((1,), (0,)), ((), ()))
    lanes = _head_lanes(block_q, q_ref.shape[2], heads)
    for qi in range(nq):
        qs = slice(qi * block_q, (qi + 1) * block_q)
        # K tiles up to the diagonal of the tile's last row (a padded Q row's
        # may lie past the last K tile)
        last = (qi + 1) * block_q - 1
        for i in range(heads):
            q = _own(lanes[i], q_ref[0, qs, :])
            for kj in range(min(nk, last // block_k + 1) if causal else nk):
                ks = slice(kj * block_k, (kj + 1) * block_k)
                s = _scores(
                    q, k_ref[0, ks, :], qi, kj, scale=scale,
                    # a tile wholly below the diagonal needs no causal mask
                    causal=causal and qi * block_q < (kj + 1) * block_k - 1,
                    block_q=block_q, block_k=block_k, t_valid=t_valid, nk=nk,
                )
                v = v_ref[0, ks, :]
                m_tile = jnp.max(s, axis=-1, keepdims=True)
                m_new = m_tile if kj == 0 else jnp.maximum(m, m_tile)
                p = jnp.exp(s - m_new)
                l_tile = jnp.sum(p, axis=-1, keepdims=True)
                pv = jax.lax.dot_general(
                    p.astype(v.dtype), v, nn, preferred_element_type=jnp.float32)
                if kj == 0:
                    l, acc = l_tile, pv
                else:
                    alpha = jnp.exp(m - m_new)
                    l = l * alpha + l_tile
                    acc = acc * alpha + pv
                m = m_new
            # P·V is every head's lanes wide: head i keeps its own
            out = acc / l if i == 0 else jnp.where(lanes[i], acc / l, out)
            lse_ref[0, 0, i:i + 1, qs] = _turned(m + jnp.log(l))
        o_ref[0, qs, :] = out.astype(o_ref.dtype)


@partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _forward_resident(q, k, v, causal, block_q, block_k, interpret):
    """``_flash_forward`` as one pallas_call, a lane block of heads a
    program: (out [B,T,H,D], lse rows [B', H'/heads, heads, t_pad_q] f32)."""
    b, t, h, d = q.shape
    block_q, block_k, t_pad_q, t_pad_k = _plan(t, block_q, block_k)
    heads = _heads_per_block(h, d)
    (qr,) = _rows((q,), b, h, t, d, t_pad_q, heads)
    kr, vr = _rows((k, v), b, h, t, d, t_pad_k, heads)
    n, blocks, per = _row_grid(b, h, heads)

    def block(t_pad):
        return pl.BlockSpec((1, t_pad, per * d), lambda i, j: (i, 0, j))

    out, lse = pl.pallas_call(
        partial(
            _resident_fwd_kernel, scale=1.0 / (d ** 0.5), causal=causal,
            block_q=block_q, block_k=block_k, t_valid=t, heads=per,
        ),
        out_shape=[
            jax.ShapeDtypeStruct(qr.shape, q.dtype),
            jax.ShapeDtypeStruct((n, blocks, per, t_pad_q), jnp.float32),
        ],
        grid=(n, blocks),
        in_specs=[block(t_pad_q), block(t_pad_k), block(t_pad_k)],
        out_specs=[
            block(t_pad_q),
            pl.BlockSpec((1, 1, per, t_pad_q), lambda i, j: (i, j, 0, 0)),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_ONE_PASS_VMEM_LIMIT),
        name="flash_fwd_resident_rows" if heads else "flash_fwd_resident",
        interpret=interpret,
    )(qr, kr, vr)
    return _from_rows(out, b, h, t, d, heads), lse


# ------------------------------------------------------------- dispatch


# Measured-best default tiles by head dim (v5e, T=1024 sweeps) for the
# kernels that stream tiles — the forward and the two-kernel backward:
# - forward wants the largest Q tile that fits VMEM (fewer grid
#   programs, bigger MXU ops; 8,192 tokens a layer at (512,512): 0.83 ms
#   at dh=64, 0.74 a layer inside gpt2-medium's step);
# - the dQ and dK/dV kernels carry more scratch/live values per program
#   and prefer smaller Q tiles (8,192 tokens a layer: 3.31 ms at dh=64,
#   T=1024; 4.61 ms at dh=128, T=8192);
# - at dh>=128 (full-lane tiles) larger K blocks win in BOTH directions
#   (fwd 0.61 ms at bk=1024 vs 0.83 at 512, T=2048 1.00 vs 1.37; bwd
#   (256,1024) 0.56 ms vs (128,512) 0.90 ms per layer).
# The resident forms have their own tiles (``_RESIDENT_TILE``,
# ``_ONE_PASS_TILE``).
def _default_blocks(d: int) -> tuple[tuple[int, int], int]:
    """((fwd_block_q, bwd_block_q), block_k) by head dim."""
    if d >= 128:
        return (512, 256), 1024
    return (512, 128), 512


# The one-pass backward (a head resident in VMEM) against the dQ and dK/dV
# kernels that stream tiles: one algorithm at two tilings, chosen from the
# shapes alone. The block must fit — what the pipeline double-buffers (Q, K, V,
# dO, O in; dQ, dK, dV out; lse rows are next to nothing) plus the kernel's
# scratch (dQ float32, Qᵀ, dOᵀ, an lse and a Δ column a head), minor dims
# padded to 128 lanes as VMEM holds them: 7.3 MB at T 1024 x D 64 bf16 (a pair
# of heads a block), 12.6 MB at T 2048 x D 128 — and the unrolled tile loop
# must stay short (T 4096 at 512: 36 visible pairs, 30 s to compile). Measured
# on both sides (v5e, 8,192 tokens a layer, causal, bf16, ms: two kernels /
# one pass, PR 34): D 64 at T 512 1.83 / 0.67, T 1024 3.31 / 0.81, T 2048
# 5.58 / 1.16; D 128 at T 1024 0.91 / 0.39, T 2048 1.51 / 0.61. Tiles of 256
# read within 10 % of 512 at four times the pairs; a rolled `fori_loop` over
# the pairs costs 1.03 for 0.84, Pᵀ and dSᵀ turned per pair 0.97, and nothing
# of the masks or the `exp` shows in the time: the five matmuls bound it.
# Both operand forms of the one pass (PR 43, same shapes; ms, folded / the
# model's rows: the kernel alone, then forward + backward with the copies XLA
# puts around them): D 64 at T 512 0.503 / 0.424, 1.213 / 0.655; T 1024 0.608 /
# 0.557, 1.438 / 0.881; T 2048 0.953 / 0.903, 1.940 / 1.430; D 128 at T 1024
# 0.358 / 0.367, 0.732 / 0.545; T 2048 0.575 / 0.586, 1.027 / 0.840 (PR 42's
# kernels with their Δ outside and lse columns: 1.389, 1.512, 2.047, 0.786,
# 1.097). A pair of heads a block halves the DMA'd lanes and the programs: at
# T 1024 a head and tile pair takes 1.45 us where its five matmuls, as padded,
# need 1.36. At D 128 the kernel alone is 2 % slower from the rows (it takes Δ
# and turns lse itself) and its program a fifth faster. Two heads a block
# unroll twice the bodies: T 2048 x D 64 builds in 10.2 s on the chip's host,
# folded in 6.9.
_ONE_PASS_TILE = 512
_ONE_PASS_MAX_PAIRS = 16
_ONE_PASS_VMEM_BUDGET = 16 * 2 ** 20
# The budget plus the score tiles' room: under the default 16 MiB the chip's
# compiler refuses the non-causal T 2048 x D 128 head (16 pairs).
_ONE_PASS_VMEM_LIMIT = 32 * 2 ** 20


def _one_pass_fits(block_q: int, block_k: int, t_pad_q: int, t_pad_k: int,
                   d: int, dtype, heads: int = 1) -> bool:
    itemsize = jnp.dtype(dtype).itemsize
    lanes = _round_up(heads * d, 128)
    pipelined = 2 * 4 * lanes * itemsize * (t_pad_q + t_pad_k)
    columns = 2 * heads * 128 * 4  # lse, Δ: a [T, 1] float32 column a head
    scratch = t_pad_q * (lanes * 4 + 2 * heads * d * itemsize + columns)
    pairs = (t_pad_q // block_q) * (t_pad_k // block_k)
    return (pairs <= _ONE_PASS_MAX_PAIRS
            and pipelined + scratch <= _ONE_PASS_VMEM_BUDGET)


def _backward_plan(t: int, d: int, dtype, block_q: int | None,
                   block_k: int | None, heads: int = 1):
    """(calls, block_q, block_k, t_pad_q, t_pad_k) of the backward: which
    form runs (``_backward_one_pass``, ``heads`` heads a lane block, or
    ``_backward_calls``) at which tiles; a tile the caller left open takes
    that form's measured best."""
    plan = _plan(t, block_q or _ONE_PASS_TILE, block_k or _ONE_PASS_TILE)
    if _one_pass_fits(*plan, d, dtype, max(heads, 1)):
        return (_backward_one_pass, *plan)
    (_, default_bq), default_bk = _default_blocks(d)
    return (_backward_calls,
            *_plan(t, block_q or default_bq, block_k or default_bk))


# The forward with a head resident in VMEM against the kernel that streams
# K/V tiles: one algorithm at two tilings, chosen as the backward's are. The
# head must fit — what the pipeline double-buffers (Q, K, V in; O out; lse
# rows are next to nothing), minor dims padded to 128 lanes, plus a score
# tile's s and p: 4.7 MB at T 1024 x D 64 bf16, 6.8 MB at T 2048 — and the
# unrolled tile loop must stay short. Measured on both sides (v5e, 8,192
# tokens a layer, bf16, ms: streaming at ``_default_blocks`` / resident at
# 512, PR 41): causal, D 64 at T 512 0.583 / 0.244, T 1024 0.833 / 0.326,
# T 2048 1.374 / 0.514; D 128 at T 1024
# 0.608 / 0.325, T 2048 0.999 / 0.513; not causal, D 64 at T 1024 0.963 /
# 0.410, D 128 at T 2048 (16 pairs) 1.094 / 0.802; float32, D 64 at T 1024
# 1.002 / 0.431, D 128 0.652 / 0.440. Past 16 pairs the resident form still
# runs faster and costs more to build than it is worth: T 2560 (25 pairs)
# 1.667 / 0.606 causal, 1.639 / 0.971 not, 3.3 and 10.2 s to compile; at
# T 3072 not causal and T 4096 (36 visible pairs) the chip's compiler refuses
# it (40 MB of scoped VMEM under the 32 MiB limit); T 4096 at 256-row tiles
# 2.441 / 0.830 after 15.6 s. Tiles at T 512 / 1024 / 2048, D 64, causal:
# 512 0.244 / 0.326 / 0.514, 256 0.289 / 0.316 / 0.474, (256, 512) 0.255 /
# 0.327 / 0.508, 128 0.270 / 0.345 / 0.908, (1024, 512) - / 0.410 / 0.601;
# not causal at T 1024, 512 0.410, 256 0.458: 512, which is also the tile
# the backward pads lse to.
# Both operand forms of the resident forward (PR 43; causal, bf16, ms, folded
# / the model's rows: the kernel alone, then with the copies XLA puts around
# it): D 64 at T 512 0.229 / 0.230, 0.435 / 0.230; T 1024 0.347 / 0.321, 0.554 /
# 0.321; T 2048 0.504 / 0.526, 0.711 / 0.526; D 128 at T 1024 0.173 / 0.175,
# 0.275 / 0.175; T 2048 0.246 / 0.254, 0.348 / 0.254: the kernel is what it was
# (two matmuls and the softmax of a head do not care whose lanes lie beside
# them) and the fold of q, k, v and the unfold of the output, 0.10-0.21 ms at
# the memory's roof, are gone. lse leaves as rows (a [T, 1] float32 column is
# stored 128-fold: 67 MB a layer at 8 x 1024 x 16 heads for 0.5 MB of numbers).
_RESIDENT_TILE = 512
_RESIDENT_MAX_PAIRS = 16


def _resident_fits(block_q: int, block_k: int, t_pad_q: int, t_pad_k: int,
                   d: int, dtype) -> bool:
    itemsize = jnp.dtype(dtype).itemsize
    lanes = _round_up(d, 128)
    pipelined = 2 * 2 * lanes * itemsize * (t_pad_q + t_pad_k)  # Q, O; K, V
    tile = block_q * block_k * (4 + 4 + itemsize)  # s, p; p as the operand
    pairs = (t_pad_q // block_q) * (t_pad_k // block_k)
    return (pairs <= _RESIDENT_MAX_PAIRS
            and pipelined + tile <= _ONE_PASS_VMEM_BUDGET)


def _forward_plan(t: int, d: int, dtype, block_q: int | None,
                  block_k: int | None, k_shift: int = 0):
    """(forward, block_q, block_k): which form runs (``_forward_resident``
    or ``_flash_forward``) at which tiles; a tile the caller left open takes
    that form's measured best. A shifted diagonal (ring attention's blocks)
    streams: the resident kernel has none."""
    tiles = (block_q or _RESIDENT_TILE, block_k or _RESIDENT_TILE)
    if k_shift == 0 and _resident_fits(*_plan(t, *tiles), d, dtype):
        return (_forward_resident, *tiles)
    (default_bq, _), default_bk = _default_blocks(d)
    return _flash_forward, block_q or default_bq, block_k or default_bk


def _planned_forward(q, k, v, causal, block_q, block_k, interpret):
    """The whole-sequence forward in the form its shape takes."""
    forward, block_q, block_k = _forward_plan(
        q.shape[1], q.shape[3], q.dtype, block_q, block_k)
    return forward(q, k, v, causal, block_q, block_k, interpret)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, block_q, block_k, interpret, blocked_backward):
    out, _ = _planned_forward(
        q, k, v, causal, block_q[0], block_k[0], interpret)
    return out


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret, blocked_backward):
    out, lse = _planned_forward(
        q, k, v, causal, block_q[0], block_k[0], interpret)
    res = (q, k, v, out, lse) if blocked_backward else (q, k, v)
    return out, res


def _flash_bwd(causal, block_q, block_k, interpret, blocked_backward, res, g):
    if blocked_backward:
        q, k, v, o, lse = res
        return _flash_backward(
            q, k, v, o, lse, g, causal, block_q[1], block_k[1], interpret
        )
    q, k, v = res
    # Fallback: exact gradients by recomputing the reference math under
    # vjp (O(T²) transient inside XLA; debugging aid).
    _, vjp = jax.vjp(
        lambda q, k, v: dot_product_attention(q, k, v, causal=causal), q, k, v
    )
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    block_q: int | tuple[int, int] | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    blocked_backward: bool = True,
) -> jax.Array:
    """Fused blocked attention over [B, T, H, D]; same semantics as
    ``dot_product_attention``. Dispatch: compiled kernels on TPU; on other
    backends the reference math (full speed under XLA) unless
    ``interpret=True`` forces the Pallas interpreter (tests).

    ``block_q``: one int for both directions, or a (forward, backward)
    pair; ``block_q``/``block_k`` default (None) to the measured-best
    tiles of the form each direction's shape takes (``_forward_plan``,
    ``_backward_plan``: one pass over a head resident in VMEM, or the
    kernels that stream tiles). ``_plan`` still caps every block at the
    padded T.

    Under a GSPMD engine (an active ``parallel.sharding.KernelLayout``)
    the call runs per shard of batch and heads: the SPMD partitioner
    cannot partition the kernel itself."""
    from tpudml.parallel.sharding import per_shard

    bthd = ("batch", None, "head", None)
    return per_shard(
        lambda q, k, v: _flash_attention_local(
            q, k, v, causal, block_q, block_k, interpret, blocked_backward),
        (bthd, bthd, bthd), bthd,
    )(q, k, v)


def _flash_attention_local(q, k, v, causal, block_q, block_k, interpret,
                           blocked_backward):
    if interpret is None:
        if jax.default_backend() != "tpu":
            return dot_product_attention(q, k, v, causal=causal)
        interpret = False
    # (forward, backward) tiles; a tile left None is chosen with its
    # direction's form (``_forward_plan``, ``_backward_plan``).
    pair = block_q is not None and not isinstance(block_q, int)
    bq = tuple(block_q) if pair else (block_q, block_q)
    bk = (block_k, block_k)
    return _flash(q, k, v, causal, bq, bk, interpret, blocked_backward)
