"""Functional neural-net module system.

A minimal init/apply layer framework in the JAX idiom: a ``Module`` is an
immutable description; ``init(key)`` returns a parameter pytree and a state
pytree (e.g. batch-norm running stats); ``apply(params, state, x, train=...)``
is a pure function returning ``(y, new_state)``. Parameters are plain nested
dicts, so the hand-written optimizers in ``tpudml.optim`` (reference:
codes/task1/pytorch/MyOptimizer.py) operate on them directly as pytrees, and
GSPMD sharding annotations attach to them without framework cooperation.

Data layout is NHWC (channels-last), the layout XLA:TPU prefers for
convolutions; the reference's NCHW torch models (codes/task1/pytorch/
model.py:16-35) map onto this with identical math.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax

Params = Any
State = Any


class Module:
    """Base class: immutable layer description with pure init/apply."""

    def init(self, key: jax.Array) -> tuple[Params, State]:
        return {}, {}

    def apply(
        self,
        params: Params,
        state: State,
        x: jax.Array,
        *,
        train: bool = False,
        rng: jax.Array | None = None,
    ) -> tuple[jax.Array, State]:
        raise NotImplementedError

    # Convenience call; pass ``state`` for models with stateful layers
    # (e.g. BatchNorm running stats), whose apply would KeyError on {}.
    def __call__(self, params, x, state=None, **kw):
        y, _ = self.apply(params, state if state is not None else {}, x, **kw)
        return y


def _uniform_fan_in(key, shape, fan_in, dtype):
    """Kaiming-uniform à la torch's default Linear/Conv init: U(-b, b) with
    b = 1/sqrt(fan_in). Keeps initial loss scale close to the reference's
    torch models so loss curves are comparable."""
    bound = 1.0 / jnp.sqrt(jnp.maximum(fan_in, 1.0))
    return jax.random.uniform(key, shape, dtype, -bound, bound)


@dataclass(frozen=True)
class Dense(Module):
    in_features: int
    out_features: int
    use_bias: bool = True
    dtype: Any = jnp.float32

    def init(self, key):
        kw, kb = jax.random.split(key)
        params = {
            "kernel": _uniform_fan_in(
                kw, (self.in_features, self.out_features), self.in_features, self.dtype
            )
        }
        if self.use_bias:
            params["bias"] = _uniform_fan_in(
                kb, (self.out_features,), self.in_features, self.dtype
            )
        return params, {}

    def apply(self, params, state, x, *, train=False, rng=None):
        y = x @ params["kernel"]
        if self.use_bias:
            y = y + params["bias"]
        return y, state


@dataclass(frozen=True)
class Conv2D(Module):
    """2-D convolution, NHWC x HWIO -> NHWC."""

    in_channels: int
    out_channels: int
    kernel_size: int | tuple[int, int] = 3
    stride: int | tuple[int, int] = 1
    padding: str | int = "SAME"
    use_bias: bool = True
    dtype: Any = jnp.float32

    def _ksize(self):
        k = self.kernel_size
        return (k, k) if isinstance(k, int) else tuple(k)

    def init(self, key):
        kh, kw_ = self._ksize()
        fan_in = kh * kw_ * self.in_channels
        kw, kb = jax.random.split(key)
        params = {
            "kernel": _uniform_fan_in(
                kw, (kh, kw_, self.in_channels, self.out_channels), fan_in, self.dtype
            )
        }
        if self.use_bias:
            params["bias"] = _uniform_fan_in(kb, (self.out_channels,), fan_in, self.dtype)
        return params, {}

    def apply(self, params, state, x, *, train=False, rng=None):
        s = self.stride
        strides = (s, s) if isinstance(s, int) else tuple(s)
        if isinstance(self.padding, int):
            p = self.padding
            padding = [(p, p), (p, p)]
        else:
            padding = self.padding
        y = lax.conv_general_dilated(
            x,
            params["kernel"],
            window_strides=strides,
            padding=padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        if self.use_bias:
            y = y + params["bias"]
        return y, state


@dataclass(frozen=True)
class MaxPool(Module):
    window: int = 2
    stride: int | None = None

    def apply(self, params, state, x, *, train=False, rng=None):
        w, s = self.window, self.stride or self.window
        y = lax.reduce_window(
            x, -jnp.inf, lax.max, (1, w, w, 1), (1, s, s, 1), "VALID"
        )
        return y, state


@dataclass(frozen=True)
class AvgPool(Module):
    window: int = 2
    stride: int | None = None

    def apply(self, params, state, x, *, train=False, rng=None):
        w, s = self.window, self.stride or self.window
        y = lax.reduce_window(x, 0.0, lax.add, (1, w, w, 1), (1, s, s, 1), "VALID")
        return y / (w * w), state


@dataclass(frozen=True)
class Flatten(Module):
    def apply(self, params, state, x, *, train=False, rng=None):
        return x.reshape(x.shape[0], -1), state


@dataclass(frozen=True)
class Activation(Module):
    fn: Callable[[jax.Array], jax.Array] = jax.nn.relu

    def apply(self, params, state, x, *, train=False, rng=None):
        return self.fn(x), state


@dataclass(frozen=True)
class Dropout(Module):
    rate: float = 0.5

    def apply(self, params, state, x, *, train=False, rng=None):
        if not train or self.rate == 0.0:
            return x, state
        if rng is None:
            raise ValueError("Dropout in train mode requires an rng")
        keep = 1.0 - self.rate
        mask = jax.random.bernoulli(rng, keep, x.shape)
        return jnp.where(mask, x / keep, 0.0), state


@dataclass(frozen=True)
class BatchNorm(Module):
    """Batch normalization with running-average inference statistics."""

    num_features: int
    momentum: float = 0.9
    eps: float = 1e-5
    dtype: Any = jnp.float32

    def init(self, key):
        params = {
            "scale": jnp.ones((self.num_features,), self.dtype),
            "bias": jnp.zeros((self.num_features,), self.dtype),
        }
        state = {
            "mean": jnp.zeros((self.num_features,), self.dtype),
            "var": jnp.ones((self.num_features,), self.dtype),
        }
        return params, state

    def apply(self, params, state, x, *, train=False, rng=None):
        axes = tuple(range(x.ndim - 1))
        if train:
            mean = jnp.mean(x, axes, dtype=jnp.float32)
            if x.dtype == jnp.bfloat16:
                # Single-pass moments in f32 accumulated straight off the bf16
                # stream: sum and sum-of-squares reduce in ONE fused read of
                # x instead of jnp.var's mean-then-deviations second pass, and
                # the stream is never materialized as an f32 copy. Clamped
                # E[x²] − m² (cancellation can go slightly negative in f32;
                # rsqrt(negative + eps) would NaN-poison the step). The bf16
                # input already bounds the stats' accuracy, so the single-pass
                # cancellation is below the quantization floor.
                var = jnp.maximum(
                    jnp.mean(jnp.square(x.astype(jnp.float32)), axes)
                    - jnp.square(mean),
                    0.0,
                )
            else:
                # Two-pass E[(x−m)²] for f32 inputs: at large activation
                # means (m² ≫ var) the single-pass form loses ALL variance
                # bits to f32 cancellation and the clamp silently returns
                # var=0 — normalization then amplifies by rsqrt(eps).
                var = jnp.mean(jnp.square(x.astype(jnp.float32) - mean), axes)
            m = self.momentum
            new_state = {
                "mean": m * state["mean"] + (1 - m) * mean.astype(state["mean"].dtype),
                "var": m * state["var"] + (1 - m) * var.astype(state["var"].dtype),
            }
        else:
            mean, var = state["mean"], state["var"]
            new_state = state
        # Normalization in f32 (scale/bias params are f32), back in x's dtype —
        # pure elementwise, so XLA fuses the cast/normalize/cast chain into the
        # neighbouring ops; a bf16 compute path stays bf16 end to end.
        y = (x.astype(jnp.float32) - mean) * lax.rsqrt(
            var.astype(jnp.float32) + self.eps
        )
        y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
        return y.astype(x.dtype), new_state


@dataclass(frozen=True)
class LayerNorm(Module):
    """Layer normalization over the trailing feature axis (the transformer
    norm; batch-size independent, so it needs no cross-replica state sync
    under data or sequence sharding)."""

    num_features: int
    eps: float = 1e-5
    dtype: Any = jnp.float32

    def init(self, key):
        return {
            "scale": jnp.ones((self.num_features,), self.dtype),
            "bias": jnp.zeros((self.num_features,), self.dtype),
        }, {}

    def apply(self, params, state, x, *, train=False, rng=None):
        # Statistics always in float32 (bf16 mean/var is numerically weak
        # at transformer widths); result back in the input dtype so the
        # bf16 compute path stays bf16 end to end. Single-pass moments
        # (E[x²] − m² instead of jnp.var's second mean pass) — one fewer
        # reduction over the row for XLA to schedule; fine in f32 at
        # activation magnitudes.
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        # Clamped at 0: E[x²] − m² can go slightly NEGATIVE from f32
        # cancellation when m² >> var (large-mean rows), and
        # rsqrt(negative + eps) would NaN-poison the step.
        var = jnp.maximum(
            jnp.mean(jnp.square(xf), axis=-1, keepdims=True) - jnp.square(mean),
            0.0,
        )
        y = (xf - mean) * lax.rsqrt(var + self.eps)
        y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(
            jnp.float32
        )
        return y.astype(x.dtype), state


@dataclass(frozen=True)
class RMSNorm(Module):
    """Root-mean-square normalization over the trailing feature axis:
    ``x * rsqrt(mean(x^2) + eps) * scale``, no centring and no bias.
    Statistics in float32, result in the input dtype (as LayerNorm)."""

    num_features: int
    eps: float = 1e-5
    dtype: Any = jnp.float32

    def init(self, key):
        return {"scale": jnp.ones((self.num_features,), self.dtype)}, {}

    def apply(self, params, state, x, *, train=False, rng=None):
        xf = x.astype(jnp.float32)
        y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + self.eps)
        return (y * params["scale"].astype(jnp.float32)).astype(x.dtype), state


@dataclass(frozen=True)
class GatedGroupRMSNorm(Module):
    """Mamba-2's output norm: ``RMSNorm_groups(x * silu(gate)) * scale``,
    the gate applied BEFORE the norm and the statistics taken over each of
    ``num_groups`` equal slices of the feature axis."""

    num_features: int
    num_groups: int = 1
    eps: float = 1e-5
    dtype: Any = jnp.float32

    def __post_init__(self):
        if self.num_features % self.num_groups:
            raise ValueError(
                f"num_features {self.num_features} % num_groups "
                f"{self.num_groups} != 0"
            )

    def init(self, key):
        return {"scale": jnp.ones((self.num_features,), self.dtype)}, {}

    def apply(self, params, state, x, *, gate, train=False, rng=None):
        xf = x.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
        g = xf.reshape(*xf.shape[:-1], self.num_groups, -1)
        g = g * lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True) + self.eps)
        y = g.reshape(xf.shape) * params["scale"].astype(jnp.float32)
        return y.astype(x.dtype), state


@dataclass(frozen=True)
class GatedMLP(Module):
    """A gated feed-forward (SwiGLU): ``(silu(x @ gate) * (x @ up)) @ down``,
    no bias."""

    embed_dim: int
    hidden_dim: int
    dtype: Any = jnp.float32

    def init(self, key):
        d, h = self.embed_dim, self.hidden_dim
        kg, ku, kd = jax.random.split(key, 3)
        return {"gate": _uniform_fan_in(kg, (d, h), d, self.dtype),
                "up": _uniform_fan_in(ku, (d, h), d, self.dtype),
                "down": _uniform_fan_in(kd, (h, d), h, self.dtype)}, {}

    def apply(self, params, state, x, *, train=False, rng=None):
        y = jax.nn.silu(x @ params["gate"]) * (x @ params["up"])
        return y @ params["down"], state


@dataclass(frozen=True)
class GatedMemoryUnit(Module):
    """A gated memory unit: ``(m * silu(x @ W_1)) @ W_2``, no bias, where
    ``m`` [..., memory_dim] is what an earlier layer of the model made for the
    same token (a state-space layer's scan output; `tpudml.nn.mamba.Mamba1`).
    The layer keeps nothing between tokens."""

    embed_dim: int
    memory_dim: int
    dtype: Any = jnp.float32

    def init(self, key):
        d, e = self.embed_dim, self.memory_dim
        k1, k2 = jax.random.split(key)
        return {"in_proj": {"kernel": _uniform_fan_in(k1, (d, e), d, self.dtype)},
                "out_proj": {"kernel": _uniform_fan_in(k2, (e, d), e, self.dtype)}}, {}

    def forward(self, params, x, m):
        gate = jax.nn.silu(x @ params["in_proj"]["kernel"])
        return (m.astype(x.dtype) * gate) @ params["out_proj"]["kernel"]


@dataclass(frozen=True)
class Sequential(Module):
    """Chain of modules; params/state are dicts keyed ``layer{i}``."""

    layers: Sequence[Module] = field(default_factory=tuple)

    def init(self, key):
        params, state = {}, {}
        keys = jax.random.split(key, max(len(self.layers), 1))
        for i, (layer, k) in enumerate(zip(self.layers, keys)):
            p, s = layer.init(k)
            if p:
                params[f"layer{i}"] = p
            if s:
                state[f"layer{i}"] = s
        return params, state

    def apply(self, params, state, x, *, train=False, rng=None):
        new_state = {}
        rngs = (
            jax.random.split(rng, max(len(self.layers), 1)) if rng is not None else None
        )
        for i, layer in enumerate(self.layers):
            p = params.get(f"layer{i}", {})
            s = state.get(f"layer{i}", {})
            x, s2 = layer.apply(
                p, s, x, train=train, rng=rngs[i] if rngs is not None else None
            )
            if s2:
                new_state[f"layer{i}"] = s2
        return x, new_state


def iter_module_tree(obj):
    """Yield ``obj`` and every nested candidate module: dataclass fields,
    tuple/list items, and dict values. The ONE walker behind structural
    model inspection (dropout detection in the pipeline engines, MoE
    detection in the training engine) — containers added here propagate
    to every detector at once instead of drifting per copy (ADVICE r2 +
    review r3)."""
    import dataclasses

    yield obj
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        for f in dataclasses.fields(obj):
            yield from iter_module_tree(getattr(obj, f.name))
    elif isinstance(obj, (tuple, list)):
        for o in obj:
            yield from iter_module_tree(o)
    elif isinstance(obj, dict):
        for o in obj.values():
            yield from iter_module_tree(o)
