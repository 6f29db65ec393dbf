"""Preallocated KV caches for incremental decode.

Layout is ``[B, max_len, kv_heads, head_dim]`` per layer — B is the
engine's SLOT count (one row per in-flight sequence, continuous
batching rewrites rows in place), and the head axis is the GQA
``kv_heads`` so the cache shrinks with the KV-group count and shards
over the tensor-parallel axis exactly like the K/V projections
(``P(None, None, "model", None)``).

Kinds:

- ``"f32"`` / ``"bf16"``: plain dtype storage; a read casts back to the
  compute dtype.
- ``"int8"``: per-(token, head) symmetric quantization — ``scale =
  amax(|x|)/127`` over head_dim, stored alongside as f32
  ``[B, max_len, kv_heads]``; the decode read dequantizes in-kernel
  (``q * scale``), so HBM traffic in the cache-bound decode regime drops
  4× vs f32.
- ``"bf16_sim"`` / ``"int8_sim"``: test oracles — write the
  quantize→dequantize ROUNDTRIP into an f32 cache. A real quantized
  cache must produce bitwise the values of its ``_sim`` twin (the
  dequant is deterministic), which is how tests/test_serve.py pins
  "dequant in the decode kernel is exactly the write-side roundtrip"
  without demanding the impossible (lossy int8 matching full-precision
  logits at 1e-6).

Writes happen BEFORE the attention read at a step, so slot positions
beyond a sequence's current token only ever hold zeros-or-stale values
that the causal mask (``k_pos <= pos``) excludes; no masking state is
stored in the cache itself.

K and V need not be as wide as each other (``init_cache``'s ``v_dim``),
and K may be stored wider than the head (``stored_width``: a 192-wide key
in 256 lanes, the rest zero), so every cache this file makes has rows of
whole 128-lane tiles or of at most one. A layer whose queries see only
the last W positions keeps a RING of W rows: position p lies in row
``p % W`` (``ring_positions``, ``read_ring_slot``, ``write_ring_chunk``;
the decode step writes through ``write_token`` at ``pos % W``). A model
gives each of its layers the shape it needs (``models/hybrid.py``).

A second kind of per-slot state lives beside the K/V cache: the
``RecurrentState`` of a state-space layer (end of this file), Mamba-2's
``[B, H, P, N]`` or Mamba-1's ``[B, 1, N, E]`` in the same dataclass. A
model's per-layer cache tuple may hold both kinds, and ``None`` for a layer
that keeps nothing between tokens — also one that reads ANOTHER layer's
cache (a cross layer: the tuple's entry of the layer that owns the cache is
the only one, and only its owner writes it).

A third kind keeps neither keys nor values: the ``LatentCache`` of a latent
attention layer, one ``[c_kv | k_r]`` row a token that is key and value at once
(before the recurrent state, below).

A K/V head count that is no whole sublane tile (ten pair-rows) is stored
FLAT, ``[B, L * Hkv, 1, D]``: every function here then takes rows, not
tokens, and the caller multiplies its positions by ``Hkv``
(`tpudml.nn.attention.DifferentialAttention`); ``kernel_block`` says how the
kernel reads it.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
from jax import lax

from tpudml.ops.tiling import round_up

KINDS = ("f32", "bf16", "int8", "bf16_sim", "int8_sim")

# Floor on the per-(token, head) scale: an all-zero row (unwritten cache
# positions) would otherwise divide 0/0 at dequant time.
_SCALE_EPS = 1e-8


@jax.tree_util.register_dataclass
@dataclass
class KVCache:
    """One layer's cache: K/V plus (int8 only) per-(token, head) scales."""

    k: jax.Array  # [B, L, Hkv, Dk] storage dtype
    v: jax.Array  # [B, L, Hkv, Dv]
    k_scale: jax.Array  # [B, L, Hkv] f32; zeros-shaped [0] when unused
    v_scale: jax.Array
    kind: str = field(metadata=dict(static=True))

    @property
    def max_len(self) -> int:
        return self.k.shape[1]


def _store_dtype(kind: str):
    return {
        "f32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8,
        "bf16_sim": jnp.float32, "int8_sim": jnp.float32,
    }[kind]


def stored_width(head_dim: int) -> int:
    """The lanes a K or V row of ``head_dim`` is stored in: itself up to
    one 128-lane tile, whole tiles beyond. A 192-wide key is stored 256
    wide, the last 64 lanes zero (a zero lane adds nothing to ``q . k``),
    so that both fast paths of the decode step (:func:`row_scatter`,
    :func:`decode_kernel`) hold for it. Left 192 wide the chip keeps the
    cache L-minor: a row scatter then transposes all of it (12.3 ms a
    layer at 128 x 8192 x 4, against 0.13) and the kernel re-lays every
    block (10.9 ms against 4.3); the price is a third more K (PERF.md §6,
    PR 37: both sides)."""
    return head_dim if head_dim <= 128 else round_up(head_dim, 128)


def fit_width(x: jax.Array, width: int) -> jax.Array:
    """x [..., D] with zero lanes up to ``width`` (itself where D is)."""
    if x.shape[-1] == width:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def init_cache(batch: int, max_len: int, kv_heads: int, head_dim: int,
               kind: str = "f32", v_dim: int | None = None) -> KVCache:
    """``max_len`` rows a slot, K ``head_dim`` wide and V ``v_dim``
    (default: as K). A ring is a cache of its window's rows."""
    if kind not in KINDS:
        raise ValueError(f"unknown cache kind {kind!r}; one of {KINDS}")
    shape = (batch, max_len, kv_heads, head_dim)
    vshape = (batch, max_len, kv_heads, v_dim or head_dim)
    sshape = (batch, max_len, kv_heads) if kind == "int8" else (0,)
    # k/v (and the scales) must be DISTINCT buffers: the engine donates
    # the cache pytree every step, and XLA rejects donating one buffer
    # twice — so no `z = zeros(...); KVCache(k=z, v=z, ...)` aliasing.
    return KVCache(
        k=jnp.zeros(shape, _store_dtype(kind)),
        v=jnp.zeros(vshape, _store_dtype(kind)),
        k_scale=jnp.zeros(sshape, jnp.float32),
        v_scale=jnp.zeros(sshape, jnp.float32),
        kind=kind,
    )


def _quant(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x [..., Dh] f32-ish -> (int8 codes, f32 scale [...])."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), _SCALE_EPS) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def _dequant(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale[..., None]


def _encode(x: jax.Array, kind: str) -> tuple[jax.Array, jax.Array | None]:
    """Storage-form (values, scales-or-None) of new K/V rows."""
    if kind == "int8":
        return _quant(x)
    if kind == "int8_sim":
        q, s = _quant(x)
        return _dequant(q, s), None
    if kind == "bf16":
        return x.astype(jnp.bfloat16), None
    if kind == "bf16_sim":
        return x.astype(jnp.bfloat16).astype(jnp.float32), None
    return x.astype(jnp.float32), None


def row_scatter(head_dim: int) -> bool:
    """Whether ``write_token`` writes its rows by one scatter a tensor.

    The chip stores ``[B, L, Hkv, Dh]`` row-major only when a row fills
    whole 128-lane tiles; the scatter is then one in-place operation
    (5 us a tensor at 64 x 8192 x 1 x 128 on the v5e, against 219 us
    for the per-slot form; PERF.md §6, PR 29). With ``Dh`` 64 or 96 the
    chip lays the cache out with L minor-most (``{1,3,2,0:T(8,128)}``)
    and a row scatter transposes the whole cache to row-major and back,
    two cache-sized ``copy`` operations a tensor (1,249 against 535 us
    at 64 x 1024 x 16 x 64). Those layouts keep the per-slot
    ``dynamic_update_slice``, which the compiler runs in place, as a
    ``while`` of B passes."""
    return head_dim % 128 == 0


def decode_kernel(kind: str, max_len: int, kv_heads: int, num_heads: int,
                  head_dim: int, v_dim: int | None = None) -> bool:
    """Whether ``MultiHeadAttention.apply_decode`` reads this cache with
    the Pallas kernel (``ops/decode_attn.py``) and not with an einsum.

    The kernel makes the query heads that share a K/V head the rows of a
    matmul, so it wants some to share (``kv_heads < num_heads``: with one
    query head a K/V head there is no matrix); it reads the cache as the
    chip lays it out where a row fills whole 128-lane tiles (as
    :func:`row_scatter`; the L-minor layout of ``Dh`` 64 or 96 wants
    another kernel, not written), stored in a float type it can put on the
    MXU, in whole row blocks; and it needs a TPU (tests interpret it). At
    64 slots x 8192 rows, 16 x 128 over 1, bf16, the einsum's step takes
    120.9 ms and the kernel's 11.6 (PERF.md §6, PR 31). Everything else —
    MHA, the int8 and ``*_sim`` kinds, a ragged ``max_len`` — keeps the
    einsum. ``head_dim`` and ``v_dim`` are the widths as stored."""
    from tpudml.ops.decode_attn import block_rows

    block = block_rows(max_len, kv_heads)
    return (_kernel_reads(kind, kv_heads, num_heads, head_dim, v_dim)
            and block % 16 == 0 and max_len % block == 0)


def _kernel_reads(kind: str, kv_heads: int, num_heads: int, head_dim: int,
                  v_dim: int | None) -> bool:
    """:func:`decode_kernel` but for the row blocks."""
    from tpudml.ops.decode_attn import kernel_interpret

    return (kernel_interpret() is not None and row_scatter(head_dim)
            and row_scatter(v_dim or head_dim)
            and kv_heads < num_heads and kind in ("bf16", "f32"))


def kernel_block(kind: str, max_len: int, kv_heads: int, num_heads: int,
                 head_dim: int, v_dim: int | None = None) -> int | None:
    """:func:`decode_kernel` for a K/V head count that ``BLOCK_ROWS`` need
    not be a multiple of (ten pair-rows: `DifferentialAttention`): the rows
    of a slot a grid step of the kernel reads, or None where the cache keeps
    the einsum. The fewest whole 16-row tiles that divide ``max_len`` and
    with their heads fill ``BLOCK_ROWS`` (256 x 10 of 4096 or of a ring of
    512), or the whole of a shorter cache."""
    from tpudml.ops.decode_attn import BLOCK_ROWS

    if not _kernel_reads(kind, kv_heads, num_heads, head_dim, v_dim):
        return None
    return next((rows for rows in range(16, max_len + 1, 16) if max_len % rows == 0
                 and (rows * kv_heads >= BLOCK_ROWS or rows == max_len)), None)


def _update_rows(buf: jax.Array, rows: jax.Array,
                 pos: jax.Array) -> jax.Array:
    """rows [B, Q, ...] into buf [B, L, ...] at per-slot rows
    [start, start + Q), one ``dynamic_update_slice`` per slot: a negative
    ``pos`` counts from the end, then the window is clamped into
    [0, L - Q]."""
    at = (0,) * (buf.ndim - 2)
    return jax.vmap(
        lambda c, r, p: lax.dynamic_update_slice(c, r, (p, *at))
    )(buf, rows, pos)


def _scatter_rows(buf: jax.Array, rows: jax.Array,
                  pos: jax.Array) -> jax.Array:
    """``_update_rows`` as one scatter: the same rows for every ``pos``."""
    b, length = buf.shape[:2]
    q = rows.shape[1]
    start = jnp.clip(jnp.where(pos < 0, pos + length, pos), 0, length - q)
    slot = jnp.arange(b)[:, None]
    row = start[:, None] + jnp.arange(q)[None, :]
    # Slots never share a row and a slot's rows ascend; in bounds by the
    # clamp. The promises let the compiler emit one in-place scatter.
    return buf.at[slot, row].set(
        rows, mode="promise_in_bounds", unique_indices=True,
        indices_are_sorted=True)


def write_token(cache: KVCache, k_new: jax.Array, v_new: jax.Array,
                pos: jax.Array) -> KVCache:
    """Write Q tokens per slot: k_new/v_new [B, Q, Hkv, Dh] at per-slot
    rows ``pos`` .. ``pos + Q - 1`` (``pos`` [B]; continuous batching:
    every slot sits at its own depth; Q = 1 for plain decode, the verify
    window for speculative decode)."""
    ks, kscale = _encode(k_new, cache.kind)
    vs, vscale = _encode(v_new, cache.kind)
    put = _scatter_rows if row_scatter(cache.k.shape[-1]) else _update_rows
    k_sc, v_sc = cache.k_scale, cache.v_scale
    if cache.kind == "int8":
        k_sc, v_sc = put(k_sc, kscale, pos), put(v_sc, vscale, pos)
    return KVCache(k=put(cache.k, ks, pos), v=put(cache.v, vs, pos),
                   k_scale=k_sc, v_scale=v_sc, kind=cache.kind)


def write_chunk(cache: KVCache, k_new: jax.Array, v_new: jax.Array,
                slot: jax.Array, start: int) -> KVCache:
    """Prefill write: k_new/v_new [1, C, Hkv, Dh] into one slot's rows
    [start, start+C). ``start`` is static (one compiled prefill program
    per chunk index, shared across slots/requests); ``slot`` is a traced
    scalar."""
    ks, kscale = _encode(k_new, cache.kind)
    vs, vscale = _encode(v_new, cache.kind)
    at = (slot, start, 0, 0)
    k = lax.dynamic_update_slice(cache.k, ks, at)
    v = lax.dynamic_update_slice(cache.v, vs, at)
    k_sc, v_sc = cache.k_scale, cache.v_scale
    if cache.kind == "int8":
        k_sc = lax.dynamic_update_slice(k_sc, kscale, (slot, start, 0))
        v_sc = lax.dynamic_update_slice(v_sc, vscale, (slot, start, 0))
    return KVCache(k=k, v=v, k_scale=k_sc, v_scale=v_sc, kind=cache.kind)


def write_ring_chunk(cache: KVCache, k_new: jax.Array, v_new: jax.Array,
                     slot: jax.Array, start: int, n_real) -> KVCache:
    """Prefill write into a ring of L rows: of k_new/v_new [1, C, Hkv, D]
    at positions [start, start + C), of which the first ``n_real`` (traced)
    are real, row ``r`` takes the LAST real position that lies in it
    (``p % L == r``) and keeps what it held where the chunk has none. C may
    pass L (the chunk's early rows are then never stored) and the padded
    tail never lands: in a ring it would lie over rows that still count."""
    if cache.kind == "int8":
        raise ValueError("a ring cache is not stored int8")
    length = cache.max_len
    last = jnp.asarray(n_real, jnp.int32) - 1  # index of the last real token
    idx = last - (last + start - jnp.arange(length)) % length  # [L], < 0: none
    take = (idx >= 0)[None, :, None, None]
    at = jnp.maximum(idx, 0)
    out = []
    for buf, new in ((cache.k, k_new), (cache.v, v_new)):
        rows, _ = _encode(new[:, at], cache.kind)
        old = lax.dynamic_slice_in_dim(buf, slot, 1, axis=0)
        out.append(lax.dynamic_update_slice_in_dim(
            buf, jnp.where(take, rows.astype(buf.dtype), old), slot, axis=0))
    return KVCache(k=out[0], v=out[1], k_scale=cache.k_scale,
                   v_scale=cache.v_scale, kind=cache.kind)


def ring_positions(pos: jax.Array, length: int) -> jax.Array:
    """[B, L]: the position each row of a ring of ``length`` rows holds
    once the token at ``pos`` [B] is written — the latest ``p <= pos`` with
    ``p % length == row``; negative where the row holds nothing yet."""
    row = jnp.arange(length)[None, :]
    return pos[:, None] - (pos[:, None] - row) % length


def read_ring_slot(cache: KVCache, slot: jax.Array, start: int,
                   dtype) -> tuple[jax.Array, jax.Array]:
    """One slot's ring in order of position before a chunk at ``start``
    (static): [1, L, Hkv, D], row j the position ``start - L + j`` (a
    negative one holds nothing the mask lets through)."""
    length = cache.max_len
    k = lax.dynamic_slice_in_dim(cache.k, slot, 1, axis=0)
    v = lax.dynamic_slice_in_dim(cache.v, slot, 1, axis=0)
    if start % length:
        k, v = (jnp.roll(a, -(start % length), axis=1) for a in (k, v))
    return k.astype(dtype), v.astype(dtype)


def read_all(cache: KVCache, dtype) -> tuple[jax.Array, jax.Array]:
    """Full-cache read for the decode step: [B, L, Hkv, Dh] in the
    compute dtype, dequantized in the int8 case (this IS the "dequant in
    the decode kernel" — the int8 codes live in HBM, the f32 product is
    a register-level transient of the attention computation)."""
    if cache.kind == "int8":
        k = _dequant(cache.k, cache.k_scale)
        v = _dequant(cache.v, cache.v_scale)
        return k.astype(dtype), v.astype(dtype)
    return cache.k.astype(dtype), cache.v.astype(dtype)


def read_slot_prefix(cache: KVCache, slot: jax.Array, length: int,
                     dtype) -> tuple[jax.Array, jax.Array]:
    """One slot's first ``length`` rows (static) for a prefill chunk's
    attention window: [1, length, Hkv, Dh]."""
    _, _, h, d = cache.k.shape
    at = (slot, 0, 0, 0)
    k = lax.dynamic_slice(cache.k, at, (1, length, h, d))
    v = lax.dynamic_slice(cache.v, at, (1, length, h, cache.v.shape[-1]))
    if cache.kind == "int8":
        k = _dequant(k, lax.dynamic_slice(cache.k_scale, (slot, 0, 0),
                                          (1, length, h)))
        v = _dequant(v, lax.dynamic_slice(cache.v_scale, (slot, 0, 0),
                                          (1, length, h)))
    return k.astype(dtype), v.astype(dtype)


def cache_bytes(cache: KVCache | LatentCache) -> int:
    """Total storage bytes (K + V + scales; a latent cache's rows) — the
    number the int8 option exists to shrink."""
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))


# -------------------------------------------------------------- latent cache
# A third kind of per-slot state: what a latent attention layer
# (`tpudml.nn.attention.LatentAttention`) keeps of a token is ONE row, the
# compressed K/V beside the shared rotary key, and the row is key and (in its
# first lanes) value at once. Positions mask it as they mask a K/V cache, so
# nothing is zeroed when a slot changes hands.


@jax.tree_util.register_dataclass
@dataclass
class LatentCache:
    """One latent attention layer's cache."""

    rows: jax.Array  # [B, L, W]: a token's [c_kv | k_r] in `stored_width` lanes, the rest zero
    kind: str = field(metadata=dict(static=True))

    @property
    def max_len(self) -> int:
        return self.rows.shape[1]


def init_latent_cache(batch: int, max_len: int, row_width: int,
                      kind: str = "f32") -> LatentCache:
    """``max_len`` rows a slot, each ``row_width`` values in whole 128-lane
    tiles (`stored_width`: 576 in 640), so that the row scatter and the decode
    kernel hold for it as for a K/V cache. Stored in a float type: a row has
    no per-head scale to quantize by."""
    if kind not in KINDS or kind.startswith("int8"):
        raise ValueError(f"a latent cache is stored {KINDS[:2]} or bf16_sim, not {kind!r}")
    return LatentCache(jnp.zeros((batch, max_len, stored_width(row_width)),
                                 _store_dtype(kind)), kind)


def _latent_rows(cache: LatentCache, rows: jax.Array) -> jax.Array:
    return fit_width(_encode(rows, cache.kind)[0], cache.rows.shape[-1])


def write_latent_token(cache: LatentCache, rows: jax.Array, pos: jax.Array) -> LatentCache:
    """`write_token` for a latent cache: rows [B, Q, row_width] at per-slot
    rows ``pos`` .. ``pos + Q - 1``."""
    put = _scatter_rows if row_scatter(cache.rows.shape[-1]) else _update_rows
    return LatentCache(put(cache.rows, _latent_rows(cache, rows), pos), cache.kind)


def write_latent_chunk(cache: LatentCache, rows: jax.Array, slot: jax.Array,
                       start: int) -> LatentCache:
    """`write_chunk` for a latent cache: rows [1, C, row_width] into one
    slot's rows [start, start + C)."""
    return LatentCache(lax.dynamic_update_slice(
        cache.rows, _latent_rows(cache, rows), (slot, start, 0)), cache.kind)


def read_latent_prefix(cache: LatentCache, slot: jax.Array, length: int, dtype) -> jax.Array:
    """One slot's first ``length`` rows (static): [1, length, W]."""
    return lax.dynamic_slice(
        cache.rows, (slot, 0, 0), (1, length, cache.rows.shape[-1])).astype(dtype)


# ----------------------------------------------------------- recurrent state
# The second kind of per-slot state: what a state-space layer carries from
# token to token. No mask hides it — a slot's state is whatever its last
# update left — so the engine zeroes it when a new request takes the slot
# (``reset_slot_state``) and prefill is told how many tokens of a padded
# chunk are real (nn/mamba.py).


@jax.tree_util.register_dataclass
@dataclass
class RecurrentState:
    """One state-space layer's per-slot state."""

    conv: jax.Array  # [B, K-1, conv_dim]: the convolution's last K-1 inputs
    # The recurrence's state: [B, H, P, N] (Mamba-2: heads, head size, state
    # size) or [B, 1, N, E] (Mamba-1: the E channels in the lanes, or the chip
    # pads a 16-wide last axis to 128, eight times the bytes).
    ssm: jax.Array


def init_recurrent_state(batch: int, window: int, conv_dim: int, heads: int,
                         head_dim: int, state_size: int, dtype=jnp.float32,
                         state_dtype=jnp.float32) -> RecurrentState:
    return RecurrentState(
        conv=jnp.zeros((batch, window, conv_dim), dtype),
        ssm=jnp.zeros((batch, heads, head_dim, state_size), state_dtype))


def read_slot_state(state: RecurrentState, slot: jax.Array):
    """(window [1, K-1, conv_dim], ssm [1, H, P, N]) of one slot (traced)."""
    return (lax.dynamic_slice_in_dim(state.conv, slot, 1, axis=0),
            lax.dynamic_slice_in_dim(state.ssm, slot, 1, axis=0))


def write_slot_state(state: RecurrentState, slot: jax.Array, window: jax.Array,
                     ssm: jax.Array) -> RecurrentState:
    return RecurrentState(
        conv=lax.dynamic_update_slice_in_dim(
            state.conv, window.astype(state.conv.dtype), slot, axis=0),
        ssm=lax.dynamic_update_slice_in_dim(
            state.ssm, ssm.astype(state.ssm.dtype), slot, axis=0))


def reset_slot_state(caches: tuple, slot: jax.Array) -> tuple:
    """Zero one slot's recurrent state in every layer that has one; K/V
    caches (and layers with no cache, ``None``) pass through."""
    def zero(c):
        if not isinstance(c, RecurrentState):
            return c
        return write_slot_state(c, slot, jnp.zeros_like(c.conv[:1]),
                                jnp.zeros_like(c.ssm[:1]))
    return tuple(zero(c) for c in caches)

