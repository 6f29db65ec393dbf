"""Attention ops and the multi-head attention module.

The reference has no attention anywhere (models are a 28×28 CNN and an
MLP; SURVEY.md §5.7) — but long-context support is first-class in this
framework, so attention is built TPU-first from the start:

- layout [B, T, H, D] with the contraction kept as two einsums that XLA
  maps straight onto the MXU;
- optional causal masking by *global* position offsets, so the same code
  is correct when the sequence axis is sharded across devices (ring /
  Ulysses context parallelism in ``tpudml.parallel.cp``);
- the module's ``impl`` field selects full, flash (Pallas kernel), ring,
  or Ulysses attention, letting one model definition run single-chip or
  sequence-sharded.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from tpudml.comm.collectives import axis_size
from tpudml.nn.layers import Dense, Module, RMSNorm

NEG_INF = -1e30  # large-finite mask value: avoids inf-inf → NaN in softmax


def sharded_positions(
    axis_name: str, t_local: int, seq_sharded: bool, seq_layout: str
) -> jax.Array:
    """GLOBAL token positions of this device's [t_local] sequence shard —
    the ONE definition RoPE, the position table, and the ring masks all
    derive from (a divergence between them is silent model corruption):
    contiguous → idx·Tl + j; striped → idx + W·j; unsharded → j."""
    if not seq_sharded:
        return jnp.arange(t_local)
    if seq_layout == "striped":
        world = axis_size(axis_name)
        return jax.lax.axis_index(axis_name) + world * jnp.arange(t_local)
    return jax.lax.axis_index(axis_name) * t_local + jnp.arange(t_local)


def rotary_embedding(
    x: jax.Array, positions: jax.Array, base: float = 10000.0
) -> jax.Array:
    """Rotary position embedding (RoPE) over [B, T, H, D_head].

    ``positions`` are GLOBAL token positions [T] — under a sharded
    sequence axis each device passes its shard's offset positions, and
    because RoPE encodes relative position in the q·k phase difference,
    ring/Ulysses attention then needs no further position handling.
    A [B, T] ``positions`` gives each batch row its own positions — the
    continuous-batching decode regime, where every cache slot sits at
    its own depth.
    """
    d = x.shape[-1] // 2
    return rotary_by_table(x, positions, base ** (-jnp.arange(d, dtype=jnp.float32) / d))


def rotary_by_table(
    x: jax.Array, positions: jax.Array, inv_freq: jax.Array, factor: float = 1.0
) -> jax.Array:
    """:func:`rotary_embedding` from the table itself: ``inv_freq`` [D / 2],
    the angle a position turns lane pair (i, i + D / 2) by (YaRN's are not
    powers of one base: :func:`yarn_inv_freq`); ``factor`` scales cos and
    sin."""
    d = x.shape[-1] // 2
    # [T, d] or [B, T, d]; the batch dim (if any) then aligns with x's.
    angles = positions.astype(jnp.float32)[..., :, None] * inv_freq
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if factor != 1.0:
        cos, sin = factor * cos, factor * sin
    cos = cos[..., :, None, :].astype(x.dtype)
    sin = sin[..., :, None, :].astype(x.dtype)
    if positions.ndim == 1:
        cos, sin = cos[None], sin[None]
    x1, x2 = x[..., :d], x[..., d:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor ``0.1 * mscale * ln(factor) + 1`` (1 where
    nothing is stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, base: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's rotary table [dim / 2] float32 (Peng et al. 2023, as DeepSeek-V2
    uses it): pair i turns at ``(1 - r_i) * base^(-2i/dim) / factor + r_i *
    base^(-2i/dim)`` with ``r_i = 1 - clip((i - lo) / (hi - lo), 0, 1)``,
    ``lo`` / ``hi`` the floored / ceiled pair indices (clipped to [0, dim - 1])
    that make ``beta_fast`` / ``beta_slow`` turns over the ``original``
    context: fast pairs keep their frequency, slow ones are interpolated by
    ``factor``. ``factor`` 1 is plain RoPE."""
    def pair_of(turns: float) -> float:
        return dim * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(base))

    lo = max(math.floor(pair_of(beta_fast)), 0)
    hi = min(math.ceil(pair_of(beta_slow)), dim - 1)
    pairs = np.arange(dim // 2, dtype=np.float32)
    plain = np.float32(base) ** (-2.0 * pairs / dim)
    keep = 1.0 - np.clip((pairs - lo) / ((hi - lo) or 0.001), 0.0, 1.0)
    return ((1.0 - keep) * plain / factor + keep * plain).astype(np.float32)


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    q_offset: jax.Array | int = 0,
    k_offset: jax.Array | int = 0,
) -> jax.Array:
    """Scaled dot-product attention over [B, T, H, D] tensors.

    ``q_offset``/``k_offset`` are the global positions of q[:,0] and
    k[:,0]: with a sharded sequence axis each device passes its shard's
    offset and the causal mask stays globally correct.
    """
    d = q.shape[-1]
    # Scores + softmax in float32 regardless of input dtype (bf16 exp/sum
    # loses mass at long T); the PV contraction runs in the value dtype so
    # the MXU still sees bf16 operands on the bf16 path.
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) / jnp.sqrt(jnp.asarray(d, jnp.float32))
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[1])
        k_pos = k_offset + jnp.arange(k.shape[1])
        mask = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def decode_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, pos: jax.Array
) -> jax.Array:
    """Single-token decode attention: q [B, 1, H, D] over a full cache
    k/v [B, L, H, D] with per-slot current positions ``pos`` [B].

    The mask ``k_pos <= pos[b]`` replaces the causal triangle: each slot
    attends exactly its own written prefix (the current token's K/V are
    written at ``pos`` BEFORE this call), and unwritten cache rows are
    excluded the same way future tokens are in training — NEG_INF before
    the f32 softmax, so they carry exactly zero weight and the valid
    rows produce the same statistics as the training kernel's masked
    row. O(L) per emitted token; the O(T²) training kernels never run."""
    d = q.shape[-1]
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) / jnp.sqrt(jnp.asarray(d, jnp.float32))
    mask = jnp.arange(k.shape[1])[None, :] <= pos[:, None]  # [B, L]
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def decode_attention_grouped(
    q: jax.Array, k: jax.Array, v: jax.Array, pos: jax.Array
) -> jax.Array:
    """:func:`decode_attention` for grouped-query attention without the
    repeat: q [B, 1, H, D] over k/v [B, L, Hkv, D], query head h reading K/V
    head h // (H / Hkv). Repeating the cache to H heads first would
    materialize H / Hkv copies of it every step."""
    b, _, h, d = q.shape
    hkv = k.shape[2]
    qg = q.reshape(b, hkv, h // hkv, d)
    s = jnp.einsum(
        "bkgd,blkd->bkgl", qg, k, preferred_element_type=jnp.float32
    ) / jnp.sqrt(jnp.asarray(d, jnp.float32))
    mask = jnp.arange(k.shape[1])[None, :] <= pos[:, None]  # [B, L]
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgl,blkd->bkgd", p, v).reshape(b, 1, h, d)


def attention_by_position(
    q: jax.Array, k: jax.Array, v: jax.Array, q_pos: jax.Array,
    k_pos: jax.Array, *, window: int | None = None,
    sink: jax.Array | None = None, scale: float | None = None,
) -> jax.Array:
    """Grouped-query attention whose mask is written in GLOBAL positions:
    q [B, Tq, H, Dk] at ``q_pos`` ([Tq] or [B, Tq]) over k [B, Tk, Hkv, Dk]
    and v [B, Tk, Hkv, Dv] at ``k_pos`` ([Tk] or [B, Tk]) -> [B, Tq, H, Dv].
    Query head h reads K/V head h // (H / Hkv), with no repeat of K or V.

    A key is seen where ``0 <= k_pos <= q_pos`` and, with ``window`` W,
    ``k_pos > q_pos - W``: the window counts the query's own position, so a
    query sees itself and the W - 1 keys before it. A negative ``k_pos``
    marks a row that holds nothing (a ring's unwritten rows). ``sink`` [H]
    float32 is a learned logit a head that joins the softmax's denominator
    and gives no value: ``P_ij = exp(S_ij) / (exp(s_h) + sum_j' exp(S_ij'))``.

    One text for every path that is not a kernel: the whole sequence
    (``apply``), a prefill chunk over a ring's rows and its own, and the
    decode step's einsum read. K and V may differ in width; ``scale``
    defaults to ``Dk ** -0.5`` (a caller whose K is stored in more lanes
    than the head has, the rest zero, gives the head's)."""
    b, tq, h, dk = q.shape
    hkv = k.shape[2]
    qg = q.reshape(b, tq, hkv, h // hkv, dk)
    s = jnp.einsum(
        "bqkgd,blkd->bkgql", qg, k, preferred_element_type=jnp.float32
    ) * jnp.asarray(scale or dk ** -0.5, jnp.float32)
    qp = jnp.broadcast_to(q_pos, (b, tq))[:, :, None]
    kp = jnp.broadcast_to(k_pos, (b, k.shape[1]))[:, None, :]
    mask = (kp >= 0) & (kp <= qp)
    if window is not None:
        mask &= kp > qp - window
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    if sink is not None:
        s = jnp.concatenate([s, jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(1, hkv, h // hkv, 1, 1),
            (*s.shape[:-1], 1))], axis=-1)
    p = jax.nn.softmax(s, axis=-1)[..., :k.shape[1]].astype(v.dtype)
    o = jnp.einsum("bkgql,blkd->bqkgd", p, v)
    return o.reshape(b, tq, h, v.shape[-1])


def decode_attention_window(
    q: jax.Array, k: jax.Array, v: jax.Array, pos: jax.Array
) -> jax.Array:
    """Multi-token decode attention: q [B, Q, H, D] — Q consecutive
    tokens per slot, the first at per-slot position ``pos`` [B] — over a
    full cache k/v [B, L, H, D]. The speculative-decoding verify window
    (Q = K+1) and the paged decode step both land here; Q = 1 reduces
    exactly to :func:`decode_attention`.

    Query j (global position pos+j) masks ``k_pos <= pos[b] + j``: its
    own row plus the committed prefix plus the earlier window rows —
    all written before this call — and NOTHING else. Rows the mask
    excludes may hold stale K/V from an evicted request; NEG_INF before
    the f32 softmax gives them exactly zero weight, so they never need
    zeroing."""
    d = q.shape[-1]
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) / jnp.sqrt(jnp.asarray(d, jnp.float32))
    q_pos = pos[:, None] + jnp.arange(q.shape[1])[None, :]  # [B, Q]
    mask = jnp.arange(k.shape[1])[None, None, :] <= q_pos[:, :, None]
    s = jnp.where(mask[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _chunk_flash_window(
    q: jax.Array, k: jax.Array, v: jax.Array, start: int
) -> jax.Array:
    """Prefill-chunk attention on TPU via the flash kernel: q [B, C, H, D]
    at global offset ``start`` over the window k/v [B, start+C, H, D]
    (``start`` static, a multiple of C).

    The flash kernels fold K/V at the QUERY length, so the window runs as
    ``start/C + 1`` equal-length block calls — every block below the
    chunk is fully visible (causal=False), the diagonal block masks
    locally — merged with the same online log-sum-exp combination the
    ring forward uses. Identical work to one causal flash over the
    window; no O(T²) recompute of earlier chunks."""
    from tpudml.ops.attention_kernel import flash_forward_lse

    b, c, h, d = q.shape
    n = start // c + 1
    num = jnp.zeros((b, c, h, d), jnp.float32)
    m = jnp.full((b, h, c), NEG_INF, jnp.float32)
    den = jnp.zeros((b, h, c), jnp.float32)
    for j in range(n):
        kb = k[:, j * c:(j + 1) * c]
        vb = v[:, j * c:(j + 1) * c]
        o_b, lse_b = flash_forward_lse(q, kb, vb, causal=(j == n - 1))
        m_new = jnp.maximum(m, lse_b)
        c_old = jnp.exp(m - m_new)
        c_new = jnp.exp(lse_b - m_new)
        num = (
            num * c_old.transpose(0, 2, 1)[..., None]
            + o_b * c_new.transpose(0, 2, 1)[..., None]
        )
        den = den * c_old + c_new
        m = m_new
    return (num / den.transpose(0, 2, 1)[..., None]).astype(q.dtype)


@dataclass(frozen=True)
class MultiHeadAttention(Module):
    """Self-attention with separate head-aligned q/k/v projections (TP
    shards each kernel's output dim without in-layer resharding).

    ``impl``: "full" (one-device softmax(QKᵀ)V), "flash" (Pallas fused
    kernel on TPU, reference math elsewhere — tpudml.ops), "ring"
    (sequence sharded over ``axis_name``, K/V blocks rotated over the ring
    — must run under shard_map), or "ulysses" (all-to-all head↔sequence
    transpose — heads must divide the axis size).
    """

    embed_dim: int
    num_heads: int
    causal: bool = False
    impl: str = "full"
    axis_name: str = "seq"
    # Accepted for API compatibility; the ring custom-VJP backward always
    # recomputes per-block (flash-style), so rematerialization is implied.
    remat: bool = False
    num_kv_heads: int | None = None  # GQA/MQA: K/V head groups (< num_heads)
    rope: bool = False  # rotary position embeddings on q/k
    rope_base: float = 10000.0
    seq_sharded: bool = False  # rope offsets from axis_name when sharded
    # Sharded-sequence token layout: "contiguous" (device i owns
    # [i·Tl, (i+1)·Tl)) or "striped" (device i owns {t : t mod W == i} —
    # the balanced causal-ring layout; positions become idx + W·j).
    seq_layout: str = "contiguous"
    dtype: Any = jnp.float32
    # Width of one head; None derives it as embed_dim // num_heads. Set, the
    # q and out projections are embed_dim x num_heads·head_dim, which need
    # not equal embed_dim.
    head_dim: int | None = None
    use_bias: bool = True
    # What a layer of a window / full mixture brings (models/hybrid.py).
    # Any of ``v_head_dim``, ``window`` and ``sink`` makes the layer
    # ``_positional``: its attention is `attention_by_position` (or, in the
    # decode step, the kernel), never the flash or the ring form.
    v_head_dim: int | None = None  # a value head's width; None: head_dim
    rotary_dim: int | None = None  # RoPE turns the head's first rotary_dim; None: all
    value_scale: float = 1.0  # v = value_scale * (x @ Wv)
    window: int | None = None  # query i sees keys i - window + 1 .. i
    sink: bool = False  # a learned logit a head in the softmax's denominator

    def __post_init__(self):
        if self.head_dim is None and self.embed_dim % self.num_heads:
            raise ValueError(
                f"embed_dim {self.embed_dim} % num_heads {self.num_heads} != 0"
            )
        kv = self.num_kv_heads
        if kv is not None and (kv < 1 or self.num_heads % kv):
            raise ValueError(
                f"num_kv_heads {kv} must divide num_heads {self.num_heads}"
            )
        if self.seq_layout not in ("contiguous", "striped"):
            raise ValueError(f"unknown seq_layout {self.seq_layout!r}")
        if self.seq_layout == "striped" and self.impl != "ring":
            # Ulysses/full gather shards in device order — under striping
            # that is a PERMUTED sequence, so their causal masks would
            # silently let tokens attend the future. Only the ring fold
            # understands striped positions.
            raise ValueError(
                f"seq_layout='striped' requires impl='ring', got {self.impl!r}"
            )
        if self.rope and self._rotary_dim % 2:
            # RoPE rotates feature PAIRS; an odd width would silently
            # broadcast to the wrong width instead of erroring later.
            raise ValueError(
                f"rope requires an even rotary width, got {self._rotary_dim}"
            )
        if not 0 < self._rotary_dim <= self._head_dim:
            raise ValueError(
                f"rotary_dim {self.rotary_dim} outside (0, {self._head_dim}]")
        if self.window is not None and (self.window < 1 or not self.causal):
            raise ValueError("a window needs window >= 1 and causal=True")
        if self._positional and self.impl not in ("full", "flash"):
            raise ValueError(
                f"impl {self.impl!r} has no window, sink or value-head width "
                "of its own; such a layer takes 'full' or 'flash'")

    @property
    def _kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def _head_dim(self) -> int:
        return self.head_dim or self.embed_dim // self.num_heads

    @property
    def _v_dim(self) -> int:
        return self.v_head_dim or self._head_dim

    @property
    def _rotary_dim(self) -> int:
        return self.rotary_dim or self._head_dim

    @property
    def _positional(self) -> bool:
        return (self.window is not None or self.sink
                or self._v_dim != self._head_dim)

    @property
    def _inner(self) -> int:
        """Width of the concatenated query heads (embed_dim unless head_dim
        is set)."""
        return self.num_heads * self._head_dim

    @property
    def _inner_v(self) -> int:
        """Width of the concatenated value heads: what the out projection
        takes."""
        return self.num_heads * self._v_dim

    def _sink(self, params):
        return params["sink"] if self.sink else None

    def _rope(self, x, positions):
        """RoPE on the head's first ``rotary_dim``; the rest passes through."""
        r = self._rotary_dim
        if r == x.shape[-1]:
            return rotary_embedding(x, positions, self.rope_base)
        return jnp.concatenate(
            [rotary_embedding(x[..., :r], positions, self.rope_base),
             x[..., r:]], axis=-1)

    @staticmethod
    def _dense(p, x):
        y = x @ p["kernel"]
        return y + p["bias"] if "bias" in p else y

    def init(self, key):
        # Separate q/k/v projections (not a fused [d, 3d] kernel): shards of
        # each kernel's output dim stay head-aligned under tensor
        # parallelism, so Megatron-style column sharding needs no in-layer
        # resharding for any mesh size dividing num_heads — for the K/V
        # kernels under GQA that bound is num_kv_heads (a smaller mesh);
        # otherwise apply_rules demotes K/V to replicated, which stays
        # CORRECT (GSPMD inserts the resharding) but costs the
        # one-allreduce-per-sublayer property. With GQA the K/V projections
        # shrink to kv_heads·head_dim — fewer KV parameters and a
        # kv_heads-sized cache at inference.
        kq, kk, kv, ko = jax.random.split(key, 4)
        bias = self.use_bias
        proj = Dense(self.embed_dim, self._inner, bias, dtype=self.dtype)
        kv_proj = Dense(self.embed_dim, self._kv_heads * self._head_dim, bias,
                        dtype=self.dtype)
        v_proj = Dense(self.embed_dim, self._kv_heads * self._v_dim, bias,
                       dtype=self.dtype)
        out = Dense(self._inner_v, self.embed_dim, bias, dtype=self.dtype)
        params = {
            "q": proj.init(kq)[0],
            "k": kv_proj.init(kk)[0],
            "v": v_proj.init(kv)[0],
            "out": out.init(ko)[0],
        }
        if self.sink:
            params["sink"] = jnp.zeros((self.num_heads,), jnp.float32)
        return params, {}

    def _heads(self, x, n_heads):
        b, t, _ = x.shape
        return x.reshape(b, t, n_heads, x.shape[-1] // n_heads)

    def apply(self, params, state, x, *, train=False, rng=None):
        b, t, _ = x.shape
        q, k, v = self._project(params, x)
        if self.rope:
            # Before the GQA repeat: rotating the kv_heads-wide tensor does
            # group× less work and repeating rotated heads is identical.
            positions = sharded_positions(
                self.axis_name, t, self.seq_sharded, self.seq_layout
            )
            q = self._rope(q, positions)
            k = self._rope(k, positions)
        if self._positional:
            # No flash form takes a window, a sink or a narrower value head:
            # the whole sequence by the plain math, whatever ``impl`` says.
            at = jnp.arange(t)
            o = attention_by_position(q, k, v, at, at, window=self.window,
                                      sink=self._sink(params))
            return self._dense(params["out"], o.reshape(b, t, -1)), state
        if self._kv_heads != self.num_heads:
            # Broadcast each KV group across its query heads; the attention
            # ops then see ordinary per-head tensors (GQA's savings are in
            # parameters and the inference KV cache, not this training op).
            group = self.num_heads // self._kv_heads
            k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
        if self.impl == "full":
            o = dot_product_attention(q, k, v, causal=self.causal)
        elif self.impl == "flash":
            from tpudml.ops import flash_attention

            o = flash_attention(q, k, v, causal=self.causal)
        elif self.impl == "ring":
            from tpudml.parallel.cp import ring_attention

            o = ring_attention(
                q, k, v, self.axis_name, causal=self.causal, remat=self.remat,
                layout=self.seq_layout,
            )
        elif self.impl == "ulysses":
            from tpudml.parallel.cp import ulysses_attention

            o = ulysses_attention(q, k, v, self.axis_name, causal=self.causal)
        else:
            raise ValueError(f"unknown attention impl {self.impl!r}")
        o = o.reshape(b, t, self._inner)
        return self._dense(params["out"], o), state

    # ----------------------------------------------------- serving paths
    # Incremental decode + chunked prefill over a tpudml.serve KVCache.
    # Same projections/RoPE/GQA-repeat/softmax math as apply() — the
    # greedy-decode parity tests pin logit-exactness against it — but
    # attention reads K/V from the cache instead of recomputing them, so
    # one emitted token costs O(L) instead of the O(T²) training kernel.

    def _serve_guard(self):
        if self.impl not in ("full", "flash"):
            raise ValueError(
                f"serve decode supports impl='full'/'flash' attention "
                f"configs, not {self.impl!r} (ring/ulysses shard the "
                f"sequence axis, which a per-slot cache does not)"
            )
        if self.seq_sharded:
            raise ValueError("serve decode requires seq_sharded=False")

    def _dense_cache_only(self, what: str):
        if self._positional:
            raise ValueError(
                f"a layer with a window, a sink or its own value-head width "
                f"serves through the dense cache only, not {what}")

    def _project(self, params, x, n_local_heads=None, n_local_kv=None):
        """(q, k, v) head tensors for x [B, T, d]. Local head counts are
        overridable so the TP decode step can run the same code on a
        head-sharded parameter shard."""
        def heads(name, n):
            y = self._dense(params[name], x)
            if y.shape[-1] // n > 128 and (y.shape[-1] // n) % 128:
                # A head of 192 does not split along whole 128-lane tiles,
                # and the chip's compiler then prefers the projection's
                # output with the tokens in the lanes: it transposes the
                # WEIGHT to get it, 100 MB of q kernel copied a layer a
                # step at 4096 x 64 x 192. Behind the barrier the 3 MB of
                # activations are re-laid instead (PERF.md §6, PR 37).
                y = jax.lax.optimization_barrier(y)
            return self._heads(y, n)

        q = heads("q", n_local_heads or self.num_heads)
        k, v = (heads(n, n_local_kv or self._kv_heads) for n in ("k", "v"))
        if self.value_scale != 1.0:
            v = v * jnp.asarray(self.value_scale, v.dtype)
        return q, k, v

    def _gqa_repeat(self, k, v, n_heads):
        group = n_heads // k.shape[2]
        if group > 1:
            k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
        return k, v

    def apply_decode(self, params, cache, x, pos):
        """One decode step: x [B, 1, d] (the current token's features),
        ``pos`` [B] its per-slot position. Writes this token's K/V into
        the cache at ``pos``, attends q over the cached prefix, returns
        (out [B, 1, d], updated cache).

        A window layer treats its cache of L rows as a ring: position p
        lies in row ``p % L`` (`tpudml.serve.cache.ring_positions`). With L
        the window itself, the rows ``<= pos`` are exactly the keys the
        query sees and the kernel's mask is the full layer's; a longer
        cache (``max_len`` rows: nothing wraps) is read by the einsum under
        the window mask, row for row the same values."""
        from tpudml.serve.cache import (decode_kernel, fit_width, read_all,
                                        ring_positions, write_token)

        self._serve_guard()
        b = x.shape[0]
        q, k_new, v_new = self._project(params, x)
        if self.rope:
            q = self._rope(q, pos[:, None])
            k_new = self._rope(k_new, pos[:, None])
        length = cache.max_len
        ring = self.window is not None
        # K may be stored wider than the head (zero lanes: serve/cache.py).
        q, k_new = (fit_width(a, cache.k.shape[-1]) for a in (q, k_new))
        cache = write_token(cache, k_new, v_new, pos % length if ring else pos)
        scale = 1.0 / self._head_dim ** 0.5
        if (decode_kernel(cache.kind, *cache.k.shape[1:3], self.num_heads,
                          cache.k.shape[-1], cache.v.shape[-1])
                and (not ring or length == self.window)):
            # Shared K/V heads on a TPU: the cache is read where it lies.
            from tpudml.ops.decode_attn import decode_attn, kernel_interpret

            o = decode_attn(q, cache.k, cache.v, pos, scale=scale,
                            sink=self._sink(params),
                            name="decode_attn_window" if ring else "decode_attn",
                            interpret=kernel_interpret())
        else:
            k, v = read_all(cache, x.dtype)
            if self._positional:
                k_pos = (ring_positions(pos, length) if ring
                         else jnp.arange(length))
                o = attention_by_position(
                    q, k, v, pos[:, None], k_pos, window=self.window,
                    sink=self._sink(params), scale=scale)
            elif 1 < k.shape[2] < q.shape[2]:
                o = decode_attention_grouped(q, k, v, pos)
            else:  # MHA as it is; one K/V head broadcasts
                k, v = self._gqa_repeat(k, v, self.num_heads)
                o = decode_attention(q, k, v, pos)
        o = o.reshape(b, 1, self._inner_v)
        return self._dense(params["out"], o), cache

    def apply_decode_window(self, params, cache, x, pos):
        """Decode a window of Q consecutive tokens per slot: x [B, Q, d]
        at positions pos..pos+Q-1 (the speculative verify window).
        Writes all Q rows' K/V, attends each window query over prefix +
        earlier window rows, returns (out [B, Q, d], updated cache).
        Rows past the committed count are overwritten by a later window
        before any unmasked read — the same stale-row invariant the
        single-token path relies on."""
        from tpudml.serve.cache import read_all, write_token

        self._serve_guard()
        self._dense_cache_only("the speculative window")
        b, qlen = x.shape[:2]
        q, k_new, v_new = self._project(params, x)
        if self.rope:
            positions = pos[:, None] + jnp.arange(qlen)[None, :]  # [B, Q]
            q = self._rope(q, positions)
            k_new = self._rope(k_new, positions)
        cache = write_token(cache, k_new, v_new, pos)
        k, v = read_all(cache, x.dtype)
        k, v = self._gqa_repeat(k, v, self.num_heads)
        o = decode_attention_window(q, k, v, pos)
        o = o.reshape(b, qlen, self._inner)
        return self._dense(params["out"], o), cache

    def apply_decode_paged(self, params, pool, table, x, pos):
        """Decode step over a paged pool: x [B, Q, d] (Q=1 plain decode,
        Q=K+1 spec verify), ``table`` [B, max_pages] each slot's page
        map, ``pos`` [B]. Same math as apply_decode/apply_decode_window
        — the gathered table window puts identical values at identical
        flat positions, and masked rows carry zero weight — so greedy
        parity vs the dense cache holds bit-for-bit in practice. Returns
        (out [B, Q, d], updated pool)."""
        from tpudml.serve.paged import read_table, write_tokens

        self._serve_guard()
        self._dense_cache_only("the paged pool")
        b, qlen = x.shape[:2]
        q, k_new, v_new = self._project(params, x)
        if self.rope:
            positions = pos[:, None] + jnp.arange(qlen)[None, :]
            q = self._rope(q, positions)
            k_new = self._rope(k_new, positions)
        pool = write_tokens(pool, k_new, v_new, table, pos)
        k, v = read_table(pool, table, x.dtype)
        k, v = self._gqa_repeat(k, v, self.num_heads)
        o = decode_attention_window(q, k, v, pos)
        o = o.reshape(b, qlen, self._inner)
        return self._dense(params["out"], o), pool

    def apply_prefill_paged(self, params, pool, table_row, x, start: int):
        """Prefill one chunk of the slot owning ``table_row``
        [max_pages]: x [1, C, d] at global positions [start, start+C).
        Mirrors apply_prefill over the paged pool; ``start`` static."""
        from tpudml.serve.paged import read_row_prefix, write_chunk

        self._serve_guard()
        self._dense_cache_only("the paged pool")
        c = x.shape[1]
        q, k_new, v_new = self._project(params, x)
        if self.rope:
            positions = start + jnp.arange(c)
            q = self._rope(q, positions)
            k_new = self._rope(k_new, positions)
        pool = write_chunk(pool, k_new, v_new, table_row, start)
        k, v = read_row_prefix(pool, table_row, start + c, x.dtype)
        k, v = self._gqa_repeat(k, v, self.num_heads)
        if jax.default_backend() == "tpu":
            o = _chunk_flash_window(q, k, v, start)
        else:
            o = dot_product_attention(q, k, v, causal=True, q_offset=start)
        o = o.reshape(1, c, self._inner)
        return self._dense(params["out"], o), pool

    def apply_prefill(self, params, cache, x, slot, start: int, n_real=None):
        """Prefill one chunk of one slot: x [1, C, d] are features of
        prompt tokens at global positions [start, start+C). Writes their
        K/V, attends the chunk over the slot's [0, start+C) window with
        the globally-offset causal mask, returns (out [1, C, d], updated
        cache). ``start`` is STATIC — one compiled program per chunk
        index, shared across slots/requests. On TPU the window attention
        reuses the flash kernel (``k_shift`` moves the causal diagonal
        to the chunk's global offset).

        A window layer (its cache a ring) sees, besides the chunk's own
        keys, the ``window`` positions before ``start`` as the ring holds
        them, and then keeps the last rows of the chunk's ``n_real`` real
        tokens (traced; default all): a padded tail written into a ring
        would lie over rows that still count."""
        from tpudml.serve.cache import (fit_width, read_ring_slot,
                                        read_slot_prefix, write_chunk,
                                        write_ring_chunk)

        self._serve_guard()
        c = x.shape[1]
        q, k_new, v_new = self._project(params, x)
        if self.rope:
            positions = start + jnp.arange(c)
            q = self._rope(q, positions)
            k_new = self._rope(k_new, positions)
        # K may be stored wider than the head (zero lanes: serve/cache.py);
        # q takes the same lanes, and the scores the head's own scale.
        q, k_new = (fit_width(a, cache.k.shape[-1]) for a in (q, k_new))
        scale = 1.0 / self._head_dim ** 0.5
        if self.window is not None:
            length = cache.max_len
            k_old, v_old = read_ring_slot(cache, slot, start, x.dtype)
            o = attention_by_position(
                q, jnp.concatenate([k_old, k_new], axis=1),
                jnp.concatenate([v_old, v_new], axis=1),
                start + jnp.arange(c),
                start - length + jnp.arange(length + c), window=self.window,
                sink=self._sink(params), scale=scale)
            cache = write_ring_chunk(cache, k_new, v_new, slot, start,
                                     c if n_real is None else n_real)
            return self._dense(params["out"], o.reshape(1, c, -1)), cache
        cache = write_chunk(cache, k_new, v_new, slot, start)
        k, v = read_slot_prefix(cache, slot, start + c, x.dtype)
        if self._positional:
            o = attention_by_position(
                q, k, v, start + jnp.arange(c), jnp.arange(start + c),
                sink=self._sink(params), scale=scale)
            return self._dense(params["out"], o.reshape(1, c, -1)), cache
        k, v = self._gqa_repeat(k, v, self.num_heads)
        if jax.default_backend() == "tpu":
            o = _chunk_flash_window(q, k, v, start)
        else:
            o = dot_product_attention(q, k, v, causal=True, q_offset=start)
        o = o.reshape(1, c, self._inner)
        return self._dense(params["out"], o), cache


# ------------------------------------------------- differential attention


def differential_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, q_pos: jax.Array,
    k_pos: jax.Array, *, scale: float, window: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """The two softmaxes of differential attention (Ye et al. 2024), by the
    plain math and with the mask of :func:`attention_by_position`: q
    [B, Tq, H, D] at ``q_pos`` over k, v [B, Tk, H/2, D] at ``k_pos`` ([Tk] or
    [B, Tk]), or over the same rows stored a pair a row, in any shape of that
    order ([B, Tk, H/4, 2D], or flat [B, Tk * H/4, 1, 2D]: K heads 2p and
    2p + 1 side by side are one row, V heads likewise).

    Differential head j of H/2 pairs query heads 2j (q1) and 2j + 1 (q2) and
    reads K/V pair p = j // 2: q1 scores K head 2p, q2 K head 2p + 1, and
    both weigh the pair's two V heads side by side (2D wide). Returns (A1,
    A2), each [B, Tq, H/2, 2D]: ``softmax(q_i k_i^T * scale + mask) V_p``."""
    b, tq, h, d = q.shape
    tk, pairs = k_pos.shape[-1], h // 4
    qg = q.reshape(b, tq, pairs, 2, 2, d)  # (pair, head of the pair, q1 | q2)
    kg = k.reshape(b, tk, pairs, 2, d)  # (pair, k1 | k2)
    vg = v.reshape(b, tk, pairs, 2 * d)
    s = jnp.einsum("bqpjid,blpid->bpjiql", qg, kg,
                   preferred_element_type=jnp.float32) * jnp.asarray(scale, jnp.float32)
    qp = jnp.broadcast_to(q_pos, (b, tq))[:, :, None]
    kp = jnp.broadcast_to(k_pos, (b, tk))[:, None, :]
    mask = (kp >= 0) & (kp <= qp)
    if window is not None:
        mask &= kp > qp - window
    s = jnp.where(mask[:, None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bpjiql,blpe->bqpjie", p, vg)  # [B, Tq, pair, head, q1 | q2, 2D]
    return (o[..., 0, :].reshape(b, tq, h // 2, 2 * d),
            o[..., 1, :].reshape(b, tq, h // 2, 2 * d))


@dataclass(frozen=True)
class DifferentialAttention(Module):
    """Causal differential attention without positions, with its serving
    paths over a `tpudml.serve.cache.KVCache`:

        o_j = RMSNorm_2D(A1_j - lambda * A2_j) * (1 - lambda_init)
        lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init
        out = concat_j(o_j) @ W_o + b_o

    with A1, A2 as :func:`differential_attention` gives them, ``num_heads``
    query heads, half as many K/V heads, four learned D-vectors and the
    norm's gain a layer. ``window`` W: a query sees the last W positions, its
    own among them, and the cache is a ring of W rows. ``cross``: the layer
    has a query projection only and reads the rows another layer cached
    (its call is handed that layer's cache and writes nothing).

    The cache decides how a K/V row is stored, and every path follows the
    cache it is handed: [B, L, H/2, D], a head a row, or a pair a row, FLAT:
    [B, L * H/4, 1, 2D], a token's H/4 pair-rows one after the other (every
    cache function then takes positions times H/4: `_per`). At D = 64 a pair is
    a 128-lane row, both fast paths of ``serve/cache.py`` hold, and the decode
    step reads the cache with ``ops/decode_attn.py`` as it is: its H query
    rows are ``[q1 | 0]`` and ``[0 | q2]`` (a zero lane adds nothing to
    ``q . k``), four to a K/V row, each weighing the whole 2D-wide V row; the
    subtraction, the norm and the scale follow the kernel. Flat, because ten
    pairs are no whole sublane tile: [B, L, 10, 128] the chip stores L-minor
    or pads to sixteen, and the kernel's layout then costs four copies of the
    cache a step (PERF.md §6, PR 39). The kernel's name in a trace says whose
    read it is: ``decode_attn`` (a full layer's own cache),
    ``decode_attn_window`` (a ring), ``decode_attn_shared`` (a cross layer's
    read of another's)."""

    embed_dim: int
    num_heads: int
    head_dim: int
    lambda_init: float
    window: int | None = None
    cross: bool = False
    use_bias: bool = True
    eps: float = 1e-5
    dtype: Any = jnp.float32

    def __post_init__(self):
        if self.num_heads % 4:
            raise ValueError(
                f"num_heads {self.num_heads}: differential heads read K/V in "
                "pairs, so the query heads come in fours")
        if self.cross and self.window is not None:
            raise ValueError("a cross layer has no ring of its own")

    @property
    def _scale(self) -> float:
        return self.head_dim ** -0.5

    def init(self, key):
        kq, kk, kv, ko, kl = jax.random.split(key, 5)
        d, inner = self.embed_dim, self.num_heads * self.head_dim
        params = {"q": Dense(d, inner, self.use_bias, dtype=self.dtype).init(kq)[0],
                  "out": Dense(inner, d, self.use_bias, dtype=self.dtype).init(ko)[0]}
        if not self.cross:
            kv_proj = Dense(d, inner // 2, self.use_bias, dtype=self.dtype)
            params.update(k=kv_proj.init(kk)[0], v=kv_proj.init(kv)[0])
        lam = 0.1 * jax.random.normal(kl, (4, self.head_dim), jnp.float32)
        params.update(lambda_q1=lam[0], lambda_k1=lam[1], lambda_q2=lam[2],
                      lambda_k2=lam[3],
                      subln={"scale": jnp.ones((2 * self.head_dim,), self.dtype)})
        return params, {}

    # ------------------------------------------------------------ pieces

    _dense = staticmethod(MultiHeadAttention._dense)

    def _queries(self, params, x):
        b, t, _ = x.shape
        return self._dense(params["q"], x).reshape(b, t, self.num_heads, self.head_dim)

    def _subln(self, params, x):
        y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps)
        return y * params["subln"]["scale"].astype(jnp.float32)

    def _finish(self, params, a1, a2, dtype):
        """A1, A2 [B, T, H/2, 2D] -> out [B, T, d]; float32 between."""
        lam = (jnp.exp(jnp.sum(params["lambda_q1"] * params["lambda_k1"]))
               - jnp.exp(jnp.sum(params["lambda_q2"] * params["lambda_k2"]))
               + self.lambda_init)
        o = self._subln(params, a1.astype(jnp.float32) - lam * a2.astype(jnp.float32))
        o = (o * (1.0 - self.lambda_init)).astype(dtype)
        return self._dense(params["out"], o.reshape(*o.shape[:2], -1))

    def _attend(self, q, k, v, q_pos, k_pos):
        return differential_attention(q, k, v, q_pos, k_pos, scale=self._scale,
                                      window=self.window)

    # ------------------------------------------------------------- paths

    def apply(self, params, state, x, *, train=False, rng=None):
        if self.cross:
            raise ValueError("a cross layer reads another layer's rows: `forward`")
        return self.forward(params, x), state

    def forward(self, params, x, kv=None):
        """The whole sequences x [B, T, d], no cache, over ``kv`` (default:
        the layer's own `kv_rows`; a cross layer is given those it reads)."""
        k, v = kv if kv is not None else self.kv_rows(params, x)
        at = jnp.arange(x.shape[1])
        return self._finish(params, *self._attend(self._queries(params, x), k, v, at, at),
                            x.dtype)

    def kv_rows(self, params, x, like=None):
        """(k, v) of x [B, T, d], a head a row ([B, T, H/2, D]) or as the
        cache buffer ``like`` stores rows (`_per`)."""
        shape = (x.shape[1], self.num_heads // 2, self.head_dim) if like is None else (
            x.shape[1] * self._per(like), *like.shape[2:])
        return tuple(self._dense(params[n], x).reshape(x.shape[0], *shape) for n in "kv")

    def _per(self, buffer) -> int:
        """Cache rows a token: H/4 where a row is a pair (flat), else 1."""
        return self.num_heads // 4 if buffer.shape[-1] == 2 * self.head_dim else 1

    def apply_decode(self, params, cache, x, pos):
        """One decode step: x [B, 1, d] at per-slot positions ``pos`` [B].
        Writes the token's K/V rows (a ring: at ``pos % L``; a cross layer:
        nothing), reads the cache, returns (out [B, 1, d], the cache)."""
        from tpudml.serve.cache import (kernel_block, read_all, ring_positions,
                                        write_token)

        b = x.shape[0]
        per = self._per(cache.k)
        length = cache.max_len // per
        ring = self.window is not None
        q = self._queries(params, x)
        if not self.cross:
            k_new, v_new = self.kv_rows(params, x, cache.k)
            cache = write_token(cache, k_new, v_new, (pos % length if ring else pos) * per)
        block = per > 1 and (not ring or length == self.window) and kernel_block(
            cache.kind, length, per, self.num_heads, cache.k.shape[-1], cache.v.shape[-1])
        if block:
            from tpudml.ops.decode_attn import decode_attn, kernel_interpret

            zero = jnp.zeros_like(q[..., 0::2, :])
            rows = jnp.stack([jnp.concatenate([q[..., 0::2, :], zero], axis=-1),
                              jnp.concatenate([zero, q[..., 1::2, :]], axis=-1)],
                             axis=3)  # [B, 1, H/2, q1 | q2, 2D]
            name = ("decode_attn_shared" if self.cross
                    else "decode_attn_window" if ring else "decode_attn")
            o = decode_attn(rows.reshape(b, 1, self.num_heads, 2 * self.head_dim),
                            cache.k, cache.v, pos, scale=self._scale, block=block,
                            name=name, interpret=kernel_interpret(), kv_heads=per)
            o = o.reshape(b, 1, self.num_heads // 2, 2, 2 * self.head_dim)
            a1, a2 = o[..., 0, :], o[..., 1, :]
        else:
            k, v = read_all(cache, x.dtype)
            k_pos = ring_positions(pos, length) if ring else jnp.arange(length)
            a1, a2 = self._attend(q, k, v, pos[:, None], k_pos)
        return self._finish(params, a1, a2, x.dtype), cache

    def apply_prefill(self, params, cache, x, slot, start: int, n_real=None):
        """Prefill one chunk of one slot: x [1, C, d] at positions
        [start, start + C) (``start`` static), of which the first ``n_real``
        are real (what a ring must know: `MultiHeadAttention.apply_prefill`).
        Writes the chunk's K/V rows and attends over the slot's rows before
        them and its own; a cross layer reads rows [0, start + C) of the
        cache it is handed. Returns (out [1, C, d], the cache)."""
        from tpudml.serve.cache import (read_ring_slot, read_slot_prefix,
                                        write_chunk, write_ring_chunk)

        c = x.shape[1]
        per = self._per(cache.k)  # cache rows a token: positions times it
        q = self._queries(params, x)
        q_pos = start + jnp.arange(c)
        if self.window is not None:
            length = cache.max_len // per
            k_new, v_new = self.kv_rows(params, x, cache.k)
            k_old, v_old = read_ring_slot(cache, slot, start * per, x.dtype)
            a1, a2 = self._attend(
                q, jnp.concatenate([k_old, k_new], axis=1),
                jnp.concatenate([v_old, v_new], axis=1), q_pos,
                start - length + jnp.arange(length + c))
            cache = write_ring_chunk(cache, k_new, v_new, slot, start * per,
                                     (c if n_real is None else n_real) * per)
            return self._finish(params, a1, a2, x.dtype), cache
        if not self.cross:
            cache = write_chunk(cache, *self.kv_rows(params, x, cache.k), slot, start * per)
        k, v = read_slot_prefix(cache, slot, (start + c) * per, x.dtype)
        a1, a2 = self._attend(q, k, v, q_pos, jnp.arange(start + c))
        return self._finish(params, a1, a2, x.dtype), cache


# ------------------------------------------------------- latent attention


@dataclass(frozen=True)
class LatentAttention(Module):
    """Causal multi-head latent attention (MLA; DeepSeek-V2, arXiv:2405.04434)
    with its serving paths over a `tpudml.serve.cache.LatentCache`. On the
    normed stream x:

        c_q = RMSNorm(x W_DQ);  [q_nope | q_rope]_h = c_q W_UQ   (H heads)
        [c_kv | k_r] = x W_DKV; c_kv = RMSNorm(c_kv); k_r = RoPE(k_r)
        [k_nope | v]_h = c_kv W_UKV;  k_h = [k_nope_h | k_r]
        out = concat_h softmax(q_h . k_h * s) v_h  W_O

    ``k_r`` is ONE rotary key that all heads share, so what a token leaves
    behind is ``[c_kv | k_r]``, ``kv_rank + rope_dim`` values a layer (576
    against 128 heads x (192 + 128)), and that row is what the cache holds:
    ``c_kv`` after its norm, ``k_r`` after RoPE. RoPE turns ``rope_dim`` lanes by
    a table (:func:`rotary_by_table`), YaRN's where ``yarn = (factor, original
    context, beta_fast, beta_slow, mscale, mscale_all_dim)`` is given; the
    softmax scale is ``(nope_dim + rope_dim)^-0.5 * m^2`` with ``m =
    yarn_mscale(factor, mscale_all_dim)`` (`_scale`), handed to every path as
    the caller's ``scale``.

    Three paths. ``apply`` (the whole sequence) and ``apply_prefill`` (a chunk
    over the slot's cached prefix and itself) run the published form: the rows
    are expanded through ``W_UKV`` to per-head keys and values and attended
    with :func:`attention_by_position` at 192 / 128. A chunk of C queries makes
    the absorbed form cost ``C * H * (kv_rank + rope_dim + kv_rank)`` a cached
    row against the expansion's ``kv_rank * H * (nope_dim + v_dim)`` plus ``C *
    H * (nope_dim + rope_dim + v_dim)``: the expansion is fewer operations
    from C > 170 at the published sizes (1.9x at the engine's 512), and the
    only form here. ``apply_decode`` is ABSORBED: ``q~_h = q_nope_h W_UK,h^T``
    [kv_rank], ``score = q~_h . c_kv + q_rope_h . k_r``, ``o_h = (P c_kv)
    W_UV,h``: H query heads over one cached row a token whose first
    ``kv_rank`` lanes are also the value, read once where it lies by
    `tpudml.ops.decode_attn.decode_attn_latent` (an einsum off the TPU).
    ``W_UK`` and ``W_UV`` are the two halves of the stored ``kv_up`` [H,
    kv_rank, nope_dim + v_dim]; there is no second copy of it."""

    embed_dim: int
    num_heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    rope_base: float = 10000.0
    yarn: tuple | None = None
    eps: float = 1e-6
    dtype: Any = jnp.float32

    def __post_init__(self):
        if self.rope_dim % 2:
            raise ValueError(f"rope requires an even rotary width, got {self.rope_dim}")
        if self.yarn is not None and len(self.yarn) != 6:
            raise ValueError("yarn = (factor, original, beta_fast, beta_slow, mscale, "
                             "mscale_all_dim)")

    @property
    def row_width(self) -> int:
        """Values a token leaves in the cache: ``[c_kv | k_r]``."""
        return self.kv_rank + self.rope_dim

    @property
    def _scale(self) -> float:
        m = yarn_mscale(self.yarn[0], self.yarn[5]) if self.yarn else 1.0
        return (self.nope_dim + self.rope_dim) ** -0.5 * m * m

    def _rope(self, x, positions):
        """RoPE over the whole of x [B, T, H, rope_dim]."""
        if self.yarn is None:
            return rotary_embedding(x, positions, self.rope_base)
        factor, original, fast, slow, mscale, mscale_all = self.yarn
        table = yarn_inv_freq(self.rope_dim, self.rope_base, factor, original, fast, slow)
        return rotary_by_table(x, positions, table,
                               yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all))

    def init(self, key):
        keys = jax.random.split(key, 5)
        h, n = self.num_heads, self.nope_dim + self.v_dim

        def kernel(k, rows, cols):
            return Dense(rows, cols, False, dtype=self.dtype).init(k)[0]

        kv_up = kernel(keys[3], self.kv_rank, h * n)["kernel"]
        ones = lambda width: RMSNorm(width, self.eps, self.dtype).init(key)[0]  # noqa: E731
        return {
            "q_down": kernel(keys[0], self.embed_dim, self.q_rank),
            "q_norm": ones(self.q_rank),
            "q_up": kernel(keys[1], self.q_rank, h * (self.nope_dim + self.rope_dim)),
            "kv_down": kernel(keys[2], self.embed_dim, self.row_width),
            "kv_norm": ones(self.kv_rank),
            "kv_up": {"kernel": kv_up.reshape(self.kv_rank, h, n).transpose(1, 0, 2)},
            "out": kernel(keys[4], h * self.v_dim, self.embed_dim),
        }, {}

    # ------------------------------------------------------------ pieces

    def _norm(self, p, x):
        return RMSNorm(x.shape[-1], self.eps, self.dtype).apply(p, {}, x)[0]

    def _queries(self, params, x, positions):
        """(q_nope [B, T, H, nope_dim], q_rope [B, T, H, rope_dim] turned)."""
        b, t, _ = x.shape
        q = self._norm(params["q_norm"], x @ params["q_down"]["kernel"]) @ params["q_up"]["kernel"]
        if (self.nope_dim + self.rope_dim) % 128:
            # A 192-wide head: keep the chip's compiler from transposing the
            # weight for it (`MultiHeadAttention._project`; PERF.md §6, PR 37).
            q = jax.lax.optimization_barrier(q)
        q = q.reshape(b, t, self.num_heads, self.nope_dim + self.rope_dim)
        return q[..., :self.nope_dim], self._rope(q[..., self.nope_dim:], positions)

    def latent_rows(self, params, x, positions):
        """[B, T, row_width]: what x [B, T, d] at ``positions`` leaves in the
        cache, ``c_kv`` after its norm beside ``k_r`` after RoPE."""
        down = x @ params["kv_down"]["kernel"]
        k_r = self._rope(down[..., None, self.kv_rank:], positions)[..., 0, :]
        return jnp.concatenate(
            [self._norm(params["kv_norm"], down[..., :self.kv_rank]), k_r], axis=-1)

    def _attend_expanded(self, params, q_nope, q_rope, rows, q_pos, k_pos):
        """The published form: q [B, Tq, H, .] at ``q_pos`` over latent
        ``rows`` [B, Tk, >= row_width] at ``k_pos`` -> out [B, Tq, d]."""
        b, tk = rows.shape[:2]
        kv = jnp.einsum("btr,hrn->bthn", rows[..., :self.kv_rank], params["kv_up"]["kernel"])
        k_r = jnp.broadcast_to(rows[:, :, None, self.kv_rank:self.row_width],
                               (b, tk, self.num_heads, self.rope_dim))
        o = attention_by_position(
            jnp.concatenate([q_nope, q_rope], axis=-1),
            jnp.concatenate([kv[..., :self.nope_dim], k_r], axis=-1),
            kv[..., self.nope_dim:], q_pos, k_pos, scale=self._scale)
        return o.reshape(b, q_nope.shape[1], -1) @ params["out"]["kernel"]

    # ------------------------------------------------------------- paths

    def apply(self, params, state, x, *, train=False, rng=None):
        at = jnp.arange(x.shape[1])
        return self._attend_expanded(params, *self._queries(params, x, at),
                                     self.latent_rows(params, x, at), at, at), state

    def apply_prefill(self, params, cache, x, slot, start: int, n_real=None):
        """Prefill one chunk of one slot: x [1, C, d] at positions [start,
        start + C) (``start`` static). Writes the chunk's latent rows, then
        attends over the slot's rows [0, start + C) as the cache holds them
        (its own among them, rounded as stored: what decode will read).
        Returns (out [1, C, d], the cache). A padded tail lands in rows the
        mask hides until decode overwrites them, as in a K/V cache."""
        from tpudml.serve.cache import read_latent_prefix, write_latent_chunk

        c = x.shape[1]
        at = start + jnp.arange(c)
        cache = write_latent_chunk(cache, self.latent_rows(params, x, at), slot, start)
        rows = read_latent_prefix(cache, slot, start + c, x.dtype)
        return self._attend_expanded(params, *self._queries(params, x, at), rows, at,
                                     jnp.arange(start + c)), cache

    def apply_decode(self, params, cache, x, pos):
        """One decode step, absorbed: x [B, 1, d] at per-slot positions
        ``pos`` [B]. Writes the token's latent row at ``pos``, reads the cache
        once, returns (out [B, 1, d], the cache)."""
        from tpudml.serve.cache import decode_kernel, fit_width, write_latent_token

        b, r = x.shape[0], self.kv_rank
        q_nope, q_rope = self._queries(params, x, pos[:, None])
        cache = write_latent_token(cache, self.latent_rows(params, x, pos[:, None]), pos)
        kv_up = params["kv_up"]["kernel"]  # [H, r, nope | v]: W_UK and W_UV where they lie
        q = jnp.concatenate(
            [jnp.einsum("hrn,bqhn->bqhr", kv_up[..., :self.nope_dim], q_nope), q_rope], axis=-1)
        q = fit_width(q, cache.rows.shape[-1])  # the stored row's zero lanes
        if decode_kernel(cache.kind, cache.max_len, 1, self.num_heads, cache.rows.shape[-1], r):
            from tpudml.ops.decode_attn import decode_attn_latent, kernel_interpret

            o = decode_attn_latent(q, cache.rows, pos, v_dim=r, scale=self._scale,
                                   interpret=kernel_interpret())
        else:
            rows = cache.rows.astype(x.dtype)[:, :, None, :]
            o = attention_by_position(q, rows, rows[..., :r], pos[:, None],
                                      jnp.arange(cache.max_len), scale=self._scale)
        o = jnp.einsum("bqhr,hrv->bqhv", o, kv_up[..., self.nope_dim:])
        return o.reshape(b, 1, -1) @ params["out"]["kernel"], cache
