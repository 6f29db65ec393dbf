"""Mamba-2 mixer (state-space duality; Dao & Gu 2024) with its serving paths.

One layer maps u [B, T, d] to [B, T, d]:

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
        """silu(conv + bias) of xbc [B, T, cd] after ``window`` [B, K-1, cd]
        (the inputs before it); also the two joined, [B, K-1+T, cd]."""
        t = xbc.shape[1]
        joined = jnp.concatenate([window.astype(xbc.dtype), xbc], axis=1)
        w = params["conv"]["kernel"].astype(jnp.float32)
        out = sum(joined[:, j:j + t].astype(jnp.float32) * w[j]
                  for j in range(self.conv_kernel))
        return jax.nn.silu(out + params["conv"]["bias"].astype(jnp.float32)), joined

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
