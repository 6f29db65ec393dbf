"""Decode attention over the serving cache where it lies (Pallas, TPU).

One query row a slot against the whole K/V cache is a matrix-vector
product for every head, which XLA lowers to multiply + reduce on the
vector unit behind converts of the cache — and, for the grouped einsum,
behind a transposed copy of it (PERF.md §5: 110 of a 121 ms decode step
at 64 slots x 8192 rows, 9 % of the memory roof). Where several query
heads share a K/V head the product has a matrix in it: the group's
``H / Hkv`` query heads are the rows of ``q · Kᵀ`` and of ``P · V``. This
kernel streams K and V through VMEM one row block at a time, straight
from the cache buffers in the layout the chip gives them, runs both
products on the MXU and keeps an online softmax (running max, sum and
float32 accumulator) in scratch: every byte of the cache is read once and
nothing cache-sized is written.

The chip stores ``[B, L, Hkv, D]`` with the K/V heads of a row beside each
other (with one head, ``[B, L, D]``), so a row block holds all of them and
the grid runs over (slot, row block): the block is read as ``[rows · Hkv,
D]``, every query head is scored against all of it, and the mask keeps,
for query head ``h``, the columns of K/V head ``h // (H / Hkv)`` at rows
``<= pos[b]``. The wasted MXU columns (all but one in ``Hkv``) cost
nothing beside the read. Masked columns are ``NEG_INF`` before the
float32 softmax, so a stale row has weight exactly zero, as in
:func:`tpudml.nn.attention.decode_attention`. Operands stay in the wider
of the cache's and the query's float type; scores, statistics and the
accumulator are float32; ``P`` is cast to the operand type for the second
product.

**Every row block of every slot is read, whatever ``pos`` says**: no
``pos``-bounded grid, no skipped DMA, no early exit. The benchmark's
``serve.decode_hbm`` counts the whole dense cache a step
(``benchmarks/counts.py``) and a step that read only live rows would read
over 100 % of the roof. The row bound is a few lines here (clamp the
block index at ``pos[b] // block`` so that a repeated index skips the
DMA, and ``pl.when`` the body) once that count follows live rows:
ROADMAP.md A1.

K and V blocks have their own widths (a 192-wide key stored in 256 lanes
beside a 128-wide value: ``scale`` is then the caller's, of the head and
not of the lanes), and a ``sink`` [H] starts the online softmax at
``m = sink, l = 1`` with an empty accumulator: one more column of weight
``exp(sink)`` and no value, at no cost a block. A window layer's ring of W
rows needs no bound of its own: the rows ``<= pos`` are the keys the query
sees, all W of them once the ring has wrapped.

Inference only. ``serve/cache.py:decode_kernel`` says which caches take
this path; everything else keeps its einsum.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudml.nn.attention import NEG_INF

# Rows of the [rows · Hkv, D] matrix a grid step reads (a slot's rows times
# its K/V heads), so that a block's bytes and the [H, rows · Hkv] scores do
# not grow with Hkv. On the v5e, bf16, a layer of 64 x 8192 x 1 x 128 takes
# 1.04 / 0.65 / 0.45 / 0.39 / 0.39 ms at 256 / 512 / 1024 / 2048 / 4096 (its
# bytes 0.33), one of 128 x 4096 x 2 x 128 0.92 / 0.75 / 0.74 / 0.75 at 1024
# / 2048 / 4096 / 8192 (0.66): below 2048 the ~0.35 us a grid step shows
# (PERF.md §6, PR 31).
BLOCK_ROWS = 2048


def kernel_interpret() -> bool | None:
    """The kernel's ``interpret`` on this backend: False on a TPU, None
    where there is no kernel to run (tests put True here)."""
    return False if jax.default_backend() == "tpu" else None


def block_rows(max_len: int, kv_heads: int) -> int:
    """The rows of a slot a grid step reads: ``BLOCK_ROWS`` over the K/V
    heads, or the whole (shorter) cache."""
    return min(BLOCK_ROWS // kv_heads, max_len)


def _rows(ref):
    """A K or V block ``[1, rows, Hkv, D]`` (``[1, rows, D]`` with one
    head) as the matrix ``[rows · Hkv, D]``, row ``r · Hkv + h`` from ``(r,
    h)``, with no relayout: the chip keeps the heads of a row in
    neighbouring sublanes, two 16-bit ones packed in a 32-bit word, which
    is how it keeps neighbouring rows of a matrix. So the ref is viewed,
    not the value reshaped: Mosaic relayouts a reshaped ``[rows, 2, D]``
    value tile by tile (1.7 ms a layer at 128 x 4096 x 2 x 128, bf16,
    against 0.74 for this and 1.05 for the einsum; PERF.md §6, PR 31)."""
    if len(ref.shape) == 3:
        return ref[0]
    _, rows, kv_heads, d = ref.shape
    pack = 4 // ref.dtype.itemsize
    if pack > 1 and kv_heads % pack == 0:
        words = ref.bitcast(jnp.uint32).reshape(rows * kv_heads // pack, d)
        return pltpu.bitcast(words[:], ref.dtype)
    return ref.reshape(rows * kv_heads, d)[:]


def _kernel(pos_ref, q_ref, k_ref, v_ref, *rest, scale: float, block: int,
            kv_heads: int, group: int, sink: bool):
    sink_ref = rest[0] if sink else None
    o_ref, m_ref, l_ref, acc_ref = rest[sink:]
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        if sink:  # a column of weight exp(sink) that carries no value
            m_ref[:] = sink_ref[:]
            l_ref[:] = jnp.ones_like(l_ref)
        else:
            m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    ct = jnp.promote_types(q_ref.dtype, k_ref.dtype)
    q = q_ref[:].astype(ct)  # [H, D]
    k = _rows(k_ref).astype(ct)
    v = _rows(v_ref).astype(ct)
    # Float32 operands in float32 (the MXU's default is one bf16 pass).
    precision = jax.lax.Precision.HIGHEST if ct == jnp.float32 else None
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32) * scale  # [H, rows · Hkv]
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if kv_heads == 1:
        keep = j * block + col <= pos_ref[b]
    else:  # column c is row c // Hkv of K/V head c % Hkv
        head = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        keep = (j * block + col // kv_heads <= pos_ref[b]) & (
            col % kv_heads == head // group)
    s = jnp.where(keep, s, NEG_INF)
    m_prev = m_ref[:]  # [H, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
        p.astype(ct), v, (((1,), (0,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32)
    m_ref[:] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[:] = (acc_ref[:] / l_ref[:]).astype(o_ref.dtype)


def decode_attn(q: jax.Array, k: jax.Array, v: jax.Array, pos: jax.Array, *,
                scale: float | None = None, sink: jax.Array | None = None,
                block: int | None = None, name: str = "decode_attn",
                interpret: bool = False, kv_heads: int | None = None) -> jax.Array:
    """:func:`tpudml.nn.attention.decode_attention_grouped` as one kernel:
    q [B, 1, H, D] over the cache buffers k [B, L, Hkv, D] and v [B, L, Hkv,
    Dv] as stored, per-slot positions ``pos`` [B] -> [B, 1, H, Dv] in q's
    type. ``L`` is a multiple of ``block`` (default :func:`block_rows`);
    ``scale`` defaults to ``D ** -0.5``; ``sink`` [H] float32 joins every
    head's denominator (`tpudml.nn.attention.attention_by_position`);
    ``name`` is the kernel's in a device trace (a window layer's ring reads
    as ``decode_attn_window``). With ``kv_heads`` given, k and v are the same
    rows stored FLAT, [B, L * Hkv, 1, D] (row ``r * Hkv + h``): the matrix the
    kernel reads, for a head count the chip would pad to its sublane tile
    (ten heads to sixteen) or, to avoid that, store L-minor."""
    b, _, h, d = q.shape
    flat = kv_heads is not None
    if flat:
        length, dv = v.shape[1] // kv_heads, v.shape[-1]
    else:
        length, kv_heads, dv = v.shape[1:]
    block = block or block_rows(length, kv_heads)
    if length % block or h % kv_heads:
        raise ValueError(
            f"decode_attn: {length} rows in blocks of {block}, {h} query "
            f"heads over {kv_heads}")
    if kv_heads == 1 or flat:
        # The chip keeps a size-1 head axis out of the tiles: [B, L, D].
        k, v = k.reshape(b, length * kv_heads, d), v.reshape(b, length * kv_heads, dv)
        k_spec, v_spec = (pl.BlockSpec((1, block * kv_heads, w), lambda i, j, pos: (i, j, 0))
                          for w in (d, dv))
    else:
        k_spec, v_spec = (pl.BlockSpec((1, block, kv_heads, w),
                                       lambda i, j, pos: (i, j, 0, 0))
                          for w in (d, dv))
    q_spec, o_spec = (pl.BlockSpec((None, None, h, w),
                                   lambda i, j, pos: (i, 0, 0, 0))
                      for w in (d, dv))
    operands, specs = [q, k, v], [q_spec, k_spec, v_spec]
    if sink is not None:
        operands.append(sink.astype(jnp.float32).reshape(h, 1))
        specs.append(pl.BlockSpec((h, 1), lambda i, j, pos: (0, 0)))
    return pl.pallas_call(
        partial(_kernel, scale=scale or 1.0 / d ** 0.5, block=block,
                kv_heads=kv_heads, group=h // kv_heads,
                sink=sink is not None),
        out_shape=jax.ShapeDtypeStruct((b, 1, h, dv), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, length // block),
            in_specs=specs,
            out_specs=o_spec,
            scratch_shapes=[
                pltpu.VMEM((h, 1), jnp.float32),  # running max
                pltpu.VMEM((h, 1), jnp.float32),  # running sum
                pltpu.VMEM((h, dv), jnp.float32),  # output accumulator
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name=name,
        interpret=interpret,
    )(pos.astype(jnp.int32), *operands)


# ------------------------------------------------------------ latent form
# Below `decode_attn` on purpose: a Pallas program's fingerprint holds its
# kernel's source lines, and the K/V form's must not move (PERF.md §6, PR 34).


def _latent_kernel(pos_ref, q_ref, c_ref, *rest, v_dim: int, **static):
    """`_kernel` with ONE cache operand: the row block is the key whole and,
    in its first ``v_dim`` lanes, the value: a view of the same VMEM block, so
    the rows come from HBM once."""
    _kernel(pos_ref, q_ref, c_ref, c_ref.at[:, :, :v_dim], *rest, **static)


def decode_attn_latent(q: jax.Array, rows: jax.Array, pos: jax.Array, *,
                       v_dim: int, scale: float, block: int | None = None,
                       interpret: bool = False) -> jax.Array:
    """Absorbed latent attention (`tpudml.nn.attention.LatentAttention`) as
    one kernel, ``decode_attn_latent`` in a device trace: q [B, 1, H, W]
    (``[q~ | q_rope]`` and the stored row's zero lanes) over the latent cache
    ``rows`` [B, L, W] as stored, per-slot positions ``pos`` [B] -> [B, 1, H,
    v_dim] in q's type: ``softmax(q . row * scale) row[:v_dim]`` over the rows
    ``<= pos``. All H query heads share the one row a token, so they are the
    matmul's rows and no mask separates heads; online softmax, mask, float
    types and ``NEG_INF`` are `decode_attn`'s, whose body this runs. At 128
    heads x (576 + 512) x 2 operations over a 1,152-byte row (242 FLOP a byte
    against the v5e's ridge of 240) this is the one form here that the MXU
    bounds about as much as the memory does. Every row block is read whatever
    ``pos`` says, as `decode_attn` (ROADMAP.md A1)."""
    b, _, h, w = q.shape
    length = rows.shape[1]
    block = block or block_rows(length, 1)
    if length % block or rows.shape[-1] != w or not 0 < v_dim <= w:
        raise ValueError(f"decode_attn_latent: {length} rows in blocks of {block}, "
                         f"q {w} wide over rows of {rows.shape[-1]}, value {v_dim}")
    at_slot = lambda i, j, pos: (i, 0, 0, 0)  # noqa: E731
    return pl.pallas_call(
        partial(_latent_kernel, v_dim=v_dim, scale=scale, block=block, kv_heads=1,
                group=h, sink=False),
        out_shape=jax.ShapeDtypeStruct((b, 1, h, v_dim), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, length // block),
            in_specs=[pl.BlockSpec((None, None, h, w), at_slot),
                      pl.BlockSpec((1, block, w), lambda i, j, pos: (i, j, 0))],
            out_specs=pl.BlockSpec((None, None, h, v_dim), at_slot),
            scratch_shapes=[
                pltpu.VMEM((h, 1), jnp.float32),  # running max
                pltpu.VMEM((h, 1), jnp.float32),  # running sum
                pltpu.VMEM((h, v_dim), jnp.float32),  # output accumulator
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="decode_attn_latent",
        interpret=interpret,
    )(pos.astype(jnp.int32), q, rows)
