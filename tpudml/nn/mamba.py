"""State-space mixers with their serving paths: Mamba-2 (state-space duality;
Dao & Gu 2024) and, at the end of the file, Mamba-1 (Gu & Dao 2023; `Mamba1`).

A Mamba-2 layer maps u [B, T, d] to [B, T, d]:

    [z | xBC | dt] = u @ W_in          widths inner | inner + 2·G·N | H
    xBC  <- silu(causal depthwise conv_K(xBC) + b_conv)
    x [H, P], B [G, N], C [G, N] = split(xBC)     (H/G heads share a group)
    dt   <- softplus(dt + dt_bias);   A = -exp(A_log)   (one a head)
    S_t  = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t       [H, P, N] a sequence
    y_t  = S_t C_t + D x_t
    out  = GroupRMSNorm_G(y * silu(z)) @ W_out

``inner = H·P`` is given by the head count and size, not by an expansion
factor. Three ways through the same mathematics:

- ``apply``: the whole sequence from a zero state, as the CHUNKED scan
  (`ssd_chunked`): inside a chunk of ``chunk_size`` tokens the recurrence is
  two matmuls against a masked decay matrix; between chunks only the
  [H, P, N] state is carried.
- ``apply_prefill``: one prefill chunk of one slot, continuing from the
  slot's stored window and state (`tpudml.serve.cache.RecurrentState`). A
  recurrent state has no mask to hide a padded tail behind, so the call is
  told how many tokens are real: the rest get ``dt = 0`` (decay 1, nothing
  added) and the stored window ends at the last real token.
- ``apply_decode``: the one-token recurrence for all slots; slots that are
  not active keep their window and state.

The convolution, the recurrence and the norm compute in float32 whatever
the stream's dtype; the state is stored as ``state_dtype`` (float32 unless a
control lowers it), the window in the stream's dtype.

`Mamba1` has the same three paths over a different recurrence: a step size
a CHANNEL (not a head) and an ``[inner, N]`` decay matrix, so no two tokens
share a decay and `ssd_chunked`'s two matmuls do not express it; its scan
walks the tokens in order (`selective_scan`). It can hand on its scan output
``m`` (before the gate) for a later layer's gated memory unit
(`tpudml.nn.layers.GatedMemoryUnit`).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from tpudml.nn.layers import GatedGroupRMSNorm, Module

_HI = lax.Precision.HIGHEST


def causal_conv_silu(conv, window, x):
    """silu(causal depthwise conv + bias) of x [B, T, C] after ``window``
    [B, K-1, C] (the inputs before it), in float32, ``conv`` the layer's
    ``kernel`` [K, C] and ``bias``; also the two joined, [B, K-1+T, C]."""
    t = x.shape[1]
    joined = jnp.concatenate([window.astype(x.dtype), x], axis=1)
    w = conv["kernel"].astype(jnp.float32)
    out = sum(joined[:, j:j + t].astype(jnp.float32) * w[j] for j in range(w.shape[0]))
    return jax.nn.silu(out + conv["bias"].astype(jnp.float32)), joined


def ssd_chunked(x, dt, a, b, c, s0, chunk: int):
    """The recurrence above over T = n·chunk tokens, chunk by chunk.

    x [B, T, G, R, P] (head h = g·R + r), dt [B, T, G, R], a [G, R],
    b, c [B, T, G, N], s0 [B, G, R, P, N]; all float32. Returns
    (y [B, T, G, R, P] without the D term, the state after token T - 1).
    With cum_t the running sum of dt·a inside a chunk:
    y_t = exp(cum_t) S_0 C_t + sum_{s<=t} exp(cum_t - cum_s) dt_s (C_t·B_s) x_s."""
    bsz, t = x.shape[:2]
    n = t // chunk

    def blocks(v):  # [B, T, ...] -> [n, B, chunk, ...]
        return jnp.moveaxis(v.reshape(bsz, n, chunk, *v.shape[2:]), 1, 0)

    causal = jnp.tril(jnp.ones((chunk, chunk), bool))[None, :, :, None, None]

    def one(s, blk):
        xc, dtc, bc, cc = blk
        cum = jnp.cumsum(dtc * a, axis=1)  # [B, L, G, R], <= 0 and falling
        seg = cum[:, :, None] - cum[:, None, :]  # [B, L(t), L(s), G, R]
        decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
        cb = jnp.einsum("blgn,bsgn->blsg", cc, bc, precision=_HI)
        m = cb[..., None] * decay * dtc[:, None]
        y = jnp.einsum("blsgr,bsgrp->blgrp", m, xc, precision=_HI)
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "blgn,bgrpn->blgrp", cc, s, precision=_HI)
        tail = jnp.exp(cum[:, -1:] - cum) * dtc  # decay from token s to the chunk's end
        s = jnp.exp(cum[:, -1])[..., None, None] * s + jnp.einsum(
            "bsgr,bsgrp,bsgn->bgrpn", tail, xc, bc, precision=_HI)
        return s, y

    s, ys = lax.scan(one, s0, (blocks(x), blocks(dt), blocks(b), blocks(c)))
    return jnp.moveaxis(ys, 0, 1).reshape(x.shape), s


@dataclass(frozen=True)
class Mamba2(Module):
    embed_dim: int
    num_heads: int = 8
    head_dim: int = 16
    n_groups: int = 1
    state_size: int = 16
    conv_kernel: int = 4
    chunk_size: int = 128
    eps: float = 1e-5
    dtype: Any = jnp.float32
    state_dtype: Any = jnp.float32

    def __post_init__(self):
        if self.num_heads % self.n_groups:
            raise ValueError(
                f"num_heads {self.num_heads} % n_groups {self.n_groups} != 0")

    @property
    def inner(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.inner + 2 * self.n_groups * self.state_size

    def _norm(self) -> GatedGroupRMSNorm:
        return GatedGroupRMSNorm(self.inner, self.n_groups, self.eps, self.dtype)

    def init(self, key):
        k_in, k_conv, k_dt, k_a, k_out = jax.random.split(key, 5)
        d, h = self.embed_dim, self.num_heads
        normal = lambda k, shape, fan: (  # noqa: E731
            jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan)).astype(self.dtype)
        # softplus(dt_bias) log-uniform in [1e-3, 1e-1], A uniform in [1, 16]
        # (the published initialisation); both and D stay float32.
        dt = jnp.exp(jax.random.uniform(k_dt, (h,), jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return {
            "in_proj": {"kernel": normal(k_in, (d, 2 * self.inner + 2 * self.n_groups
                                                * self.state_size + h), d)},
            "conv": {"kernel": normal(k_conv, (self.conv_kernel, self.conv_dim),
                                      self.conv_kernel),
                     "bias": jnp.zeros((self.conv_dim,), self.dtype)},
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jax.random.uniform(k_a, (h,), jnp.float32, 1.0, 16.0)),
            "D": jnp.ones((h,), jnp.float32),
            "norm": self._norm().init(key)[0],
            "out_proj": {"kernel": normal(k_out, (self.inner, d), self.inner)},
        }, {}

    # ------------------------------------------------------------ pieces

    def _split_proj(self, params, u):
        zxd = u @ params["in_proj"]["kernel"]
        return jnp.split(zxd, [self.inner, self.inner + self.conv_dim], axis=-1)

    def _conv(self, params, window, xbc):
        return causal_conv_silu(params["conv"], window, xbc)

    def _split_xbc(self, xbc):
        """x [..., G, R, P], b and c [..., G, N] of the float32 xbc [..., cd]."""
        g, n = self.n_groups, self.state_size
        x, b, c = jnp.split(xbc, [self.inner, self.inner + g * n], axis=-1)
        lead = xbc.shape[:-1]
        return (x.reshape(*lead, g, self.num_heads // g, self.head_dim),
                b.reshape(*lead, g, n), c.reshape(*lead, g, n))

    def _per_head(self, v):
        return v.reshape(*v.shape[:-1], self.n_groups, self.num_heads // self.n_groups)

    def _dt(self, params, dt):
        return self._per_head(jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"]))

    def _finish(self, params, y, x, z, out_dtype):
        """D term, gated group norm, output projection; y, x [..., G, R, P]."""
        y = y + self._per_head(params["D"])[..., None] * x
        y = y.reshape(*y.shape[:-3], self.inner).astype(out_dtype)
        y, _ = self._norm().apply(params["norm"], {}, y, gate=z)
        return y @ params["out_proj"]["kernel"]

    def _scan(self, params, xbc, dt, s0, n_real=None):
        """Chunked scan over the float32 conv output xbc [B, T, cd] from
        state s0; tokens at or past ``n_real`` leave the state as it is."""
        t = xbc.shape[1]
        x, b, c = self._split_xbc(xbc)
        dt = self._dt(params, dt)
        if n_real is not None:
            dt = jnp.where((jnp.arange(t) < n_real)[None, :, None, None], dt, 0.0)
        pad = -t % self.chunk_size
        if pad:  # dt = 0 rows: decay 1, nothing added
            x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                           for v in (x, dt, b, c))
        a = self._per_head(-jnp.exp(params["A_log"]))
        y, s = ssd_chunked(x, dt, a, b, c, s0, self.chunk_size)
        return y[:, :t], x[:, :t], s

    def _state_shape(self, batch: int) -> tuple:
        """The state as the scan sees it: heads split into (group, head)."""
        g = self.n_groups
        return (batch, g, self.num_heads // g, self.head_dim, self.state_size)

    # ------------------------------------------------------------- paths

    def apply(self, params, state, u, *, train=False, rng=None):
        z, xbc, dt = self._split_proj(params, u)
        window = jnp.zeros((u.shape[0], self.conv_kernel - 1, self.conv_dim), u.dtype)
        xbc, _ = self._conv(params, window, xbc)
        y, x, _ = self._scan(params, xbc, dt,
                              jnp.zeros(self._state_shape(u.shape[0]), jnp.float32))
        return self._finish(params, y, x, z, u.dtype), state

    def apply_prefill(self, params, cache, u, slot, n_real):
        """One prefill chunk u [1, C, d] of slot ``slot`` (traced), of which
        the first ``n_real`` (traced) tokens are real: continues from the
        slot's window and state and leaves both as after its last real
        token. Returns (out [1, C, d], updated cache)."""
        from tpudml.serve.cache import read_slot_state, write_slot_state

        k = self.conv_kernel
        window, s0 = read_slot_state(cache, slot)
        z, xbc, dt = self._split_proj(params, u)
        conv, joined = self._conv(params, window, xbc)
        y, x, s = self._scan(params, conv, dt, s0.astype(jnp.float32).reshape(
            self._state_shape(1)), n_real)
        window = lax.dynamic_slice_in_dim(joined, n_real, k - 1, axis=1)
        cache = write_slot_state(cache, slot, window, s.reshape(s0.shape))
        return self._finish(params, y, x, z, u.dtype), cache

    def apply_decode(self, params, cache, u, active):
        """One token for every slot: u [B, 1, d]; ``active`` [B] bool, slots
        that hold no request keep their window and state."""
        from tpudml.serve.cache import RecurrentState

        z, xbc, dt = self._split_proj(params, u)
        conv, joined = self._conv(params, cache.conv, xbc)
        x, b, c = self._split_xbc(conv[:, 0])
        dt = self._dt(params, dt[:, 0])  # [B, G, R]
        a = self._per_head(-jnp.exp(params["A_log"]))
        s_old = cache.ssm.astype(jnp.float32).reshape(self._state_shape(u.shape[0]))
        s = jnp.exp(dt * a)[..., None, None] * s_old \
            + (dt[..., None] * x)[..., None] * b[:, :, None, None, :]
        y = jnp.sum(s * c[:, :, None, None, :], axis=-1)
        keep = active[:, None, None, None, None]
        new = RecurrentState(
            conv=jnp.where(active[:, None, None], joined[:, 1:], cache.conv
                           ).astype(cache.conv.dtype),
            ssm=jnp.where(keep, s, s_old).reshape(cache.ssm.shape).astype(cache.ssm.dtype))
        out = self._finish(params, y[:, None], x[:, None], z, u.dtype)
        return out, new


# ------------------------------------------------------------------ Mamba-1


def selective_scan(x, dt, a, b, c, s0):
    """The Mamba-1 recurrence over T tokens in order, one sequence:

        S_t = exp(dt_t * A) * S_{t-1} + (dt_t * x_t) (x) B_t ;  y_t = S_t C_t

    x, dt [T, E]; a [N, E]; b, c [T, N]; s0 [N, E]; all float32. The state
    keeps its channels in the lanes ([N, E], as it is stored). Returns
    (y [T, E] without the D term, the state after token T - 1). A token with
    ``dt = 0`` leaves the state as it was."""
    def one(s, row):
        xt, dtt, bt, ct = row
        s = jnp.exp(dtt[None, :] * a) * s + (dtt * xt)[None, :] * bt[:, None]
        return s, jnp.sum(s * ct[:, None], axis=0)

    s, y = lax.scan(one, s0, (x, dt, b, c), unroll=8)
    return y, s


@dataclass(frozen=True)
class Mamba1(Module):
    """u [B, T, d] -> [B, T, d] (``inner`` E channels, ``state_size`` N,
    ``dt_rank`` R, a causal depthwise convolution of ``conv_kernel`` K):

        [x | z] = u @ W_in
        x  <- silu(conv_K(x) + b_conv)
        [delta | B | C] = x @ W_x                 widths R | N | N
        dt = softplus(delta @ W_dt + b_dt)        [T, E], one a channel
        A  = -exp(A_log)                          [E, N]
        S_t = exp(dt_t * A) * S_{t-1} + (dt_t * x_t) (x) B_t ;  y_t = S_t C_t + D * x_t
        out = (y * silu(z)) @ W_out

    Only the convolution and ``dt`` have a bias. Every path also returns
    ``m = y`` (with the D term, before the gate), in the stream's dtype. The
    per-slot state is a `tpudml.serve.cache.RecurrentState` whose ``ssm`` is
    [B, 1, N, E]: the channels lie in the lanes."""

    embed_dim: int
    inner: int = 128
    state_size: int = 16
    dt_rank: int = 4
    conv_kernel: int = 4
    dtype: Any = jnp.float32
    state_dtype: Any = jnp.float32

    def init(self, key):
        k_in, k_conv, k_x, k_dt, k_b, k_out = jax.random.split(key, 6)
        d, e, n, r = self.embed_dim, self.inner, self.state_size, self.dt_rank
        normal = lambda k, shape, fan: (  # noqa: E731
            jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan)).astype(self.dtype)
        # The published initialisation: softplus(dt bias) log-uniform in
        # [1e-3, 1e-1], A = 1..N in every channel, D = 1; all float32.
        dt = jnp.exp(jax.random.uniform(k_b, (e,), jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return {
            "in_proj": {"kernel": normal(k_in, (d, 2 * e), d)},
            "conv": {"kernel": normal(k_conv, (self.conv_kernel, e), self.conv_kernel),
                     "bias": jnp.zeros((e,), self.dtype)},
            "x_proj": {"kernel": normal(k_x, (e, r + 2 * n), e)},
            "dt_proj": {"kernel": normal(k_dt, (r, e), r),
                        "bias": dt + jnp.log(-jnp.expm1(-dt))},
            "A_log": jnp.broadcast_to(jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)), (e, n)),
            "D": jnp.ones((e,), jnp.float32),
            "out_proj": {"kernel": normal(k_out, (e, d), e)},
        }, {}

    # ------------------------------------------------------------ pieces

    def _conv(self, params, window, x):
        return causal_conv_silu(params["conv"], window, x)

    def _inputs(self, params, x, dtype):
        """(dt [..., E], B, C [..., N], A [N, E]) of the float32 conv output
        x [..., E]; the two projections in the stream's dtype."""
        r, n = self.dt_rank, self.state_size
        dbc = x.astype(dtype) @ params["x_proj"]["kernel"]
        delta, b, c = jnp.split(dbc, [r, r + n], axis=-1)
        dt = jax.nn.softplus((delta @ params["dt_proj"]["kernel"]).astype(jnp.float32)
                             + params["dt_proj"]["bias"])
        return (dt, b.astype(jnp.float32), c.astype(jnp.float32),
                -jnp.exp(params["A_log"]).T)

    def _finish(self, params, y, x, z):
        """D term, gate, output projection -> (out, m); y, x float32."""
        m = (y + params["D"] * x).astype(z.dtype)
        return (m * jax.nn.silu(z)) @ params["out_proj"]["kernel"], m

    def _sequence(self, params, u, window, s0, n_real=None):
        """One sequence u [1, T, d] from ``window`` and state s0 [N, E]:
        (out, m, the window and x joined, the last state)."""
        x, z = jnp.split(u @ params["in_proj"]["kernel"], 2, axis=-1)
        conv, joined = self._conv(params, window, x)
        dt, b, c, a = self._inputs(params, conv, u.dtype)
        if n_real is not None:  # a padded tail: decay 1, nothing added
            dt = jnp.where((jnp.arange(u.shape[1]) < n_real)[None, :, None], dt, 0.0)
        y, s = selective_scan(conv[0], dt[0], a, b[0], c[0], s0)
        out, m = self._finish(params, y[None], conv, z)
        return out, m, joined, s

    # ------------------------------------------------------------- paths

    def forward(self, params, u):
        """The whole sequences u [B, T, d] from a zero state -> (out, m)."""
        def one(row):
            window = jnp.zeros((1, self.conv_kernel - 1, self.inner), u.dtype)
            out, m, _, _ = self._sequence(
                params, row[None], window,
                jnp.zeros((self.state_size, self.inner), jnp.float32))
            return out[0], m[0]

        return lax.map(one, u)

    def apply(self, params, state, u, *, train=False, rng=None):
        return self.forward(params, u)[0], state

    def apply_prefill(self, params, cache, u, slot, n_real):
        """One prefill chunk u [1, C, d] of slot ``slot`` (traced), of which
        the first ``n_real`` (traced) tokens are real: continues from the
        slot's window and state and leaves both as after its last real
        token. Returns (out [1, C, d], m [1, C, E], updated cache)."""
        from tpudml.serve.cache import read_slot_state, write_slot_state

        window, s0 = read_slot_state(cache, slot)
        out, m, joined, s = self._sequence(
            params, u, window, s0[0, 0].astype(jnp.float32), n_real)
        window = lax.dynamic_slice_in_dim(joined, n_real, self.conv_kernel - 1, axis=1)
        return out, m, write_slot_state(cache, slot, window, s[None, None])

    def apply_decode(self, params, cache, u, active):
        """One token for every slot: u [B, 1, d]; ``active`` [B] bool, slots
        that hold no request keep their window and state. Returns (out
        [B, 1, d], m [B, 1, E], updated cache)."""
        from tpudml.serve.cache import RecurrentState

        x, z = jnp.split(u @ params["in_proj"]["kernel"], 2, axis=-1)
        conv, joined = self._conv(params, cache.conv, x)
        dt, b, c, a = self._inputs(params, conv[:, 0], u.dtype)  # [B, E], [B, N]
        s_old = cache.ssm[:, 0].astype(jnp.float32)  # [B, N, E]
        s = jnp.exp(dt[:, None, :] * a) * s_old + (dt * conv[:, 0])[:, None, :] * b[:, :, None]
        y = jnp.sum(s * c[:, :, None], axis=1)
        new = RecurrentState(
            conv=jnp.where(active[:, None, None], joined[:, 1:], cache.conv
                           ).astype(cache.conv.dtype),
            ssm=jnp.where(active[:, None, None], s, s_old)[:, None].astype(cache.ssm.dtype))
        out, m = self._finish(params, y[:, None], conv, z)
        return out, m, new
