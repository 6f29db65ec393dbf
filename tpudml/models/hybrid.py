"""A decoder-only language model whose layers are listed by a pattern.

``pattern`` is one character a layer: ``M`` a Mamba-2 mixer
(`tpudml.nn.mamba.Mamba2`), ``S`` a Mamba-1 mixer (`tpudml.nn.mamba.Mamba1`),
``E`` a sigmoid-routed mixture of experts (`tpudml.nn.moe.SigmoidMoE`: relu^2
or, ``gated_experts``, SwiGLU experts; a shared expert where ``shared_dim`` is
not 0), ``D`` a dense gated feed-forward (`tpudml.nn.layers.GatedMLP`), ``G`` a
gated memory unit (`tpudml.nn.layers.GatedMemoryUnit`), and causal attention
with an explicit head size: ``*`` without positional encoding, ``F`` full and
``W`` windowed, ``X`` cross attention (a query projection only), and ``L``
latent attention (`tpudml.nn.attention.LatentAttention`: low-rank queries, one
compressed K/V row a token beside a rotary key all heads share, YaRN RoPE).

``F`` and ``W`` come in two forms. By default they are grouped-query attention
(`tpudml.nn.attention.MultiHeadAttention`, as ``*``) with RoPE on the head's
first ``rotary_dim``, a value head ``v_head_dim`` wide scaled by
``value_scale``, and each with its own K/V head count, RoPE base and sink
flag (a ``W`` query sees the last ``window`` positions, its own among them,
and by default a learned sink a head joins its softmax's denominator). With
``differential`` they are `tpudml.nn.attention.DifferentialAttention`: no
positions, no sink, ``num_heads / 2`` K/V heads, and a ``lambda_init`` that
follows the layer's depth (`lambda_init`). ``X`` exists in that form only.

Two kinds read what an earlier layer made, and which one follows from the
pattern: ``G`` gates the scan output ``m`` of the last ``S`` before it (made
inside the step for the same token, never cached), and ``X`` attends over the
K/V cache of the last ``F`` before it, so that several layers read one cache:
an ``X`` owns none and writes none.

Every layer is ``h <- h + mixer(norm(h))``, so a published
attention-then-feed-forward layer is two pattern entries; ``norm`` is an
RMSNorm or (``norm="layer"``) a LayerNorm with a bias. After the last,
``logits = norm_f(h) @ W_head``, no bias; ``tied``: ``W_head`` is the
embedding, contracted where it lies. There is no position table: the
state-space layers or RoPE carry order.

The model serves through the unmodified ``ServingEngine`` entry point with
the dense cache layout. Its per-layer cache tuple holds each layer's own
per-slot state (`tpudml.serve.cache`): for ``*`` and ``F`` a ``KVCache`` of
``max_len`` rows, for ``W`` a ring of ``window`` rows, with the layer's K/V
head count and K and V at their stored widths (``stored_width``: a 192-wide
key in 256 lanes; differential layers with ``pair_rows``: two 64-wide heads a
128-lane row, a token's rows one after the other); for ``L`` a ``LatentCache``
of ``max_len`` rows, ``kv_rank + rope_dim`` values each as stored; a
``RecurrentState`` for ``M`` and ``S``; ``None`` for ``E``, ``D``, ``G`` and ``X``. ``cache_forms``,
``cache_bytes`` and ``live_rows`` tell the engine what its
``serve/dispatch`` span says of them. Because a
recurrent state or a ring has no mask to hide stale or padded tokens behind,
the model is ``stateful`` and the engine then (1) zeroes a slot's state when a
request takes the slot (``reset_slot``), (2) tells prefill how many tokens of
a padded chunk are real, and (3) tells decode which slots are active — the
others keep their state and reach no expert. The decode step also returns
the expert layers' counters for active slots, and both steps return every
token's expert choices (``routes``: ``route_width`` int32 a token, the
``E`` layers in order, ``top_k`` each), which the engine keeps a request in
``RequestStats.routes`` — what a reference needs to follow the program's
routing, and what an expert-placement study reads.

**A prefill chunk stops where the last per-slot state is written**
(``prefill_entries``): the engine never prefills a prompt's last token, so
nothing past the last entry that writes a cache or a state, or (``E``) reports
routes, is kept of a chunk. A pattern that ends in layers which only read
(``G``, ``X``, ``D``) prefills a shorter trunk than it decodes.

``held = (first, count)`` gives every ``E`` layer one chip's share of the
experts (`SigmoidMoE`): it routes over all ``num_experts`` and computes its
own experts' part. ``moe_scoring`` and ``moe_groups`` are its ``scoring`` and
``groups``; with groups the decode step's counters gain ``moe_group_hit``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import cached_property
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from tpudml.capabilities import reject
from tpudml.nn.attention import (DifferentialAttention, LatentAttention,
                                 MultiHeadAttention)
from tpudml.nn.layers import GatedMemoryUnit, GatedMLP, LayerNorm, Module, RMSNorm
from tpudml.nn.mamba import Mamba1, Mamba2
from tpudml.nn.moe import SigmoidMoE

KINDS = "ME*FWDSGXL"
ATTENTION = "*FW"  # the kinds that own a K/V cache
LATENT = "L"  # the kind that owns a latent cache
RECURRENT = "MS"  # the kinds that own a recurrent state


def lambda_init(depth: int) -> float:
    """A differential layer's ``lambda_init`` by its depth among the
    model's mixers (the published schedule: 0.2 at the first, towards 0.8)."""
    return 0.8 - 0.6 * math.exp(-0.3 * depth)


@dataclass(frozen=True)
class HybridLM(Module):
    vocab_size: int
    pattern: str = "ME*M"
    embed_dim: int = 64
    # attention (`*`)
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 16
    impl: str = "full"  # "flash": the Pallas kernel in `apply` and prefill on TPU (`*`)
    attn_bias: bool = False
    # differential attention without positions (`F`, `W`, `X`): num_heads / 2
    # K/V heads; pair_rows: two of them a cache row
    differential: bool = False
    pair_rows: bool = True
    # attention with positions (`F` full, `W` window); heads and head_dim as `*`
    v_head_dim: int | None = None  # None: head_dim
    rotary_dim: int | None = None  # None: the whole head
    value_scale: float = 1.0
    full_kv_heads: int = 2
    full_rope_base: float = 1e7
    full_sink: bool = False
    window: int = 128
    window_kv_heads: int = 2
    window_rope_base: float = 1e4
    window_sink: bool = True
    # latent attention (`L`): heads as `*`, the value head `v_head_dim` (None:
    # nope_dim); yarn = (factor, original context, beta_fast, beta_slow,
    # mscale, mscale_all_dim), None: plain RoPE
    q_rank: int = 48
    kv_rank: int = 32
    nope_dim: int = 16
    rope_dim: int = 8
    latent_rope_base: float = 1e4
    yarn: tuple | None = None
    # dense gated feed-forward (`D`)
    dense_dim: int = 128
    # Mamba-1 (`S`; state_size and conv_kernel as `M`) and the memory units (`G`)
    ssm_inner: int = 128
    dt_rank: int = 4
    # Mamba-2 (`M`)
    mamba_heads: int = 8
    mamba_head_dim: int = 16
    n_groups: int = 2
    state_size: int = 16
    conv_kernel: int = 4
    chunk_size: int = 128
    # mixture of experts (`E`)
    num_experts: int = 8
    top_k: int = 2
    expert_dim: int = 32
    shared_dim: int = 64  # 0: no shared expert
    gated_experts: bool = False  # SwiGLU experts (three matrices), not relu^2 (two)
    routed_scale: float = 1.0
    norm_topk: bool = True
    held: tuple[int, int] | None = None
    moe_scoring: str = "sigmoid"  # "softmax": no selection bias
    moe_groups: tuple[int, int] | None = None  # (n_group, topk_group): `SigmoidMoE.groups`
    norm: str = "rms"  # "layer": LayerNorm with a bias
    tied: bool = False  # the head is the embedding
    eps: float = 1e-5
    dtype: Any = jnp.float32  # parameters and the residual stream
    state_dtype: Any = jnp.float32  # the recurrence's stored state

    # What ServingEngine reads (see the module docstring).
    stateful = True
    max_positions = None  # no position table bounds the cache

    @property
    def counter_names(self) -> tuple[str, ...]:
        names = ("moe_routed", "moe_held", "experts_touched", "expert_load_max")
        return names + ("moe_group_hit",) if self.moe_groups else names

    def __post_init__(self):
        bad = set(self.pattern) - set(KINDS)
        if bad or not self.pattern:
            raise ValueError(f"pattern {self.pattern!r}: layer kinds are {KINDS!r}")
        if self.impl not in ("full", "flash"):
            raise ValueError(f"impl must be 'full' or 'flash', got {self.impl!r}")
        if self.norm not in ("rms", "layer"):
            raise ValueError(f"norm must be 'rms' or 'layer', got {self.norm!r}")
        for kind, source in (("G", "S"), ("X", "F")):
            at = self.pattern.find(kind)
            if at >= 0 and source not in self.pattern[:at]:
                raise ValueError(f"pattern {self.pattern!r}: {kind!r} reads what an "
                                 f"{source!r} before it makes")
        if "X" in self.pattern and not self.differential:
            raise ValueError("cross attention (`X`) exists in the differential form only")

    @property
    def num_layers(self) -> int:
        return len(self.pattern)

    @property
    def route_width(self) -> int:
        """int32 values a token in ``routes``: ``top_k`` an ``E`` layer."""
        return self.pattern.count("E") * self.top_k

    @property
    def prefill_entries(self) -> int:
        """Pattern entries a prefill chunk runs: up to the last that writes
        per-slot state or reports routes (the module docstring)."""
        return 1 + max((i for i, k in enumerate(self.pattern)
                        if k in ATTENTION + LATENT + RECURRENT + "E"), default=-1)

    def _source(self, i: int) -> int:
        """The entry whose K/V cache the ``X`` at entry i reads."""
        return self.pattern.rindex("F", 0, i)

    def _norm(self) -> Module:
        kind = LayerNorm if self.norm == "layer" else RMSNorm
        return kind(self.embed_dim, self.eps, self.dtype)

    def _mixer(self, kind: str, i: int = 0) -> Module:
        """The mixer of entry i (its place counts for differential layers)."""
        if kind == "M":
            return Mamba2(self.embed_dim, self.mamba_heads, self.mamba_head_dim,
                          self.n_groups, self.state_size, self.conv_kernel,
                          self.chunk_size, self.eps, self.dtype, self.state_dtype)
        if kind == "S":
            return Mamba1(self.embed_dim, self.ssm_inner, self.state_size, self.dt_rank,
                          self.conv_kernel, self.dtype, self.state_dtype)
        if kind == "G":
            return GatedMemoryUnit(self.embed_dim, self.ssm_inner, self.dtype)
        if kind == "E":
            return SigmoidMoE(self.embed_dim, self.num_experts, self.top_k,
                              self.expert_dim, self.shared_dim, self.routed_scale,
                              self.norm_topk, self.held, self.dtype,
                              self.gated_experts, self.moe_scoring, self.moe_groups)
        if kind == "D":
            return GatedMLP(self.embed_dim, self.dense_dim, self.dtype)
        if kind == "L":
            return LatentAttention(
                self.embed_dim, self.num_heads, self.q_rank, self.kv_rank, self.nope_dim,
                self.rope_dim, self.v_head_dim or self.nope_dim, self.latent_rope_base,
                self.yarn, self.eps, self.dtype)
        if self.differential and kind in "FWX":
            depth = sum(k not in "DE" for k in self.pattern[:i])
            return DifferentialAttention(
                self.embed_dim, self.num_heads, self.head_dim, lambda_init(depth),
                window=self.window if kind == "W" else None, cross=kind == "X",
                use_bias=self.attn_bias, eps=self.eps, dtype=self.dtype)
        attention = dict(causal=True, impl=self.impl, head_dim=self.head_dim,
                         use_bias=self.attn_bias, dtype=self.dtype)
        if kind == "*":
            return MultiHeadAttention(self.embed_dim, self.num_heads,
                                      num_kv_heads=self.num_kv_heads, **attention)
        kv_heads, base, sink, window = {
            "F": (self.full_kv_heads, self.full_rope_base, self.full_sink, None),
            "W": (self.window_kv_heads, self.window_rope_base, self.window_sink,
                  self.window)}[kind]
        return MultiHeadAttention(
            self.embed_dim, self.num_heads, num_kv_heads=kv_heads, rope=True,
            rope_base=base, v_head_dim=self.v_head_dim, rotary_dim=self.rotary_dim,
            value_scale=self.value_scale, window=window, sink=sink, **attention)

    def init(self, key):
        keys = jax.random.split(key, self.num_layers + 2)
        params = {
            "embed": (0.02 * jax.random.normal(
                keys[0], (self.vocab_size, self.embed_dim), jnp.float32)).astype(self.dtype),
            "norm_f": self._norm().init(key)[0],
        }
        if not self.tied:
            params["head"] = {"kernel": (0.02 * jax.random.normal(
                keys[1], (self.embed_dim, self.vocab_size), jnp.float32)).astype(self.dtype)}
        for i, kind in enumerate(self.pattern):
            params[f"layer{i}"] = {"norm": self._norm().init(key)[0],
                                   "mixer": self._mixer(kind, i).init(keys[i + 2])[0]}
        return params, {}

    def _layers(self, params, h, mix, entries: int | None = None):
        """The residual trunk, or its first ``entries``: ``mix(i, kind,
        mixer, p, u)`` gives layer i's mixer output on the normed stream u."""
        for i, kind in enumerate(self.pattern[:entries]):
            p = params[f"layer{i}"]
            u, _ = self._norm().apply(p["norm"], {}, h)
            h = h + mix(i, kind, self._mixer(kind, i), p["mixer"], u)
        return h

    def _logits(self, params, h):
        y, _ = self._norm().apply(params["norm_f"], {}, h)
        if self.tied:  # the table where it lies: no transposed copy of it a step
            return jnp.einsum("...d,vd->...v", y, params["embed"])
        return y @ params["head"]["kernel"]

    def apply(self, params, state, tokens, *, train=False, rng=None):
        """tokens [B, T] -> logits [B, T, V]: the whole sequence, no cache."""
        made = {}  # what later layers read: the last S's ``m``, the last F's K/V rows

        def mix(i, kind, mixer, p, u):
            if kind == "S":
                out, made["m"] = mixer.forward(p, u)
            elif kind == "G":
                out = mixer.forward(p, u, made["m"])
            elif kind in "FX" and self.differential:
                if kind == "F":
                    made["kv"] = mixer.kv_rows(p, u)
                out = mixer.forward(p, u, made["kv"])
            else:
                out = mixer.apply(p, {}, u)[0]
            return out

        return self._logits(params, self._layers(params, params["embed"][tokens], mix)), state

    # ------------------------------------------------------------ serving

    def init_decode_cache(self, batch: int, max_len: int, kind: str = "f32"):
        """The per-layer cache tuple for ``batch`` slots. ``kind`` governs
        the K/V caches only; a recurrent state is ``state_dtype`` and its
        convolution window the stream's dtype."""
        from tpudml.serve.cache import (init_cache, init_latent_cache,
                                        init_recurrent_state)

        if kind.startswith("int8") and "W" in self.pattern:
            reject("serve_pattern_ring_int8")
        if kind.startswith("int8") and "L" in self.pattern:
            reject("serve_pattern_latent_int8")

        def make(layer: str):
            if layer in ATTENTION:
                rows, kv_heads, k_dim, v_dim = self._cache_shape(layer, max_len)
                if self.differential and self.pair_rows:  # flat: `DifferentialAttention`
                    rows, kv_heads = rows * kv_heads, 1
                return init_cache(batch, rows, kv_heads, k_dim, kind, v_dim)
            if layer == "L":
                return init_latent_cache(batch, max_len, self.kv_rank + self.rope_dim, kind)
            if layer == "M":
                m = self._mixer("M")
                return init_recurrent_state(
                    batch, self.conv_kernel - 1, m.conv_dim, self.mamba_heads,
                    self.mamba_head_dim, self.state_size, self.dtype, self.state_dtype)
            if layer == "S":  # one "head" of N rows, the channels in the lanes
                return init_recurrent_state(
                    batch, self.conv_kernel - 1, self.ssm_inner, 1, self.state_size,
                    self.ssm_inner, self.dtype, self.state_dtype)
            return None

        return tuple(make(k) for k in self.pattern)

    def _cache_shape(self, layer: str, max_len: int) -> tuple[int, int, int, int]:
        """(rows, K/V heads, K width, V width as stored) of one attention
        layer's cache: a ``W`` layer keeps a ring of its window."""
        from tpudml.serve.cache import stored_width

        if layer == "*":
            return max_len, self.num_kv_heads, self.head_dim, self.head_dim
        if self.differential:  # a pair of K/V heads a row, or a head
            pair = 2 if self.pair_rows else 1
            return (max_len if layer == "F" else min(self.window, max_len),
                    self.num_heads // 2 // pair, pair * self.head_dim, pair * self.head_dim)
        widths = stored_width(self.head_dim), stored_width(self.v_head_dim or self.head_dim)
        if layer == "F":
            return max_len, self.full_kv_heads, *widths
        return min(self.window, max_len), self.window_kv_heads, *widths

    def cache_forms(self, max_len: int, kind: str) -> tuple[bool, bool]:
        """(row_scatter, decode_kernel): whether EVERY attention layer's
        decode step writes its rows by one scatter, and reads them with the
        kernel (`tpudml.serve.cache`); what ``serve/dispatch`` reports. A
        latent cache answers as one K/V head ``stored_width`` wide whose value
        is its first ``kv_rank`` lanes."""
        from tpudml.serve.cache import (decode_kernel, kernel_block, row_scatter,
                                        stored_width)

        shapes = [self._cache_shape(k, max_len) for k in self.pattern if k in ATTENTION]
        if "L" in self.pattern:
            shapes.append((max_len, 1, stored_width(self.kv_rank + self.rope_dim),
                           self.kv_rank))
        reads = kernel_block if self.differential else decode_kernel
        return (all(row_scatter(k) and row_scatter(v) for _, _, k, v in shapes),
                all(bool(reads(kind, rows, kv_heads, self.num_heads, k, v))
                    for rows, kv_heads, k, v in shapes))

    def cache_bytes(self, caches) -> dict:
        """Allocated bytes of the ``max_len``-row K/V caches, of the rings and
        (a model with ``L`` layers) of the latent caches."""
        from tpudml.serve.cache import cache_bytes

        by = {"cache_bytes_full": 0, "cache_bytes_window": 0}
        if "L" in self.pattern:
            by["cache_bytes_latent"] = 0
        for layer, c in zip(self.pattern, caches):
            if layer in ATTENTION + LATENT:
                by["cache_bytes_" + {"W": "window", "L": "latent"}.get(layer, "full")] += (
                    cache_bytes(c))
        return by

    def live_rows(self, pos, max_len: int) -> dict:
        """What a decode step over the active slots at positions ``pos``
        (numpy) must touch, over the layers. Cache rows that hold a token:
        ``rows_full`` of the ``max_len``-row caches, ``rows_window`` of the
        rings, and ``rows_read_full``, the full caches' live rows times the
        layers that read them (an ``X`` reads its ``F``'s; equal to
        ``rows_full`` without one). ``state_bytes``: the recurrent state of
        those slots, which the step reads and writes back. A model with ``L``
        layers adds ``rows_latent``, the latent caches' live rows."""
        ring = min(self.window, max_len)
        full = int((pos + 1).sum())
        rows = {"rows_full": full * sum(k in "*F" for k in self.pattern),
                "rows_window": int(np.minimum(pos + 1, ring).sum()) * self.pattern.count("W"),
                "rows_read_full": full * sum(k in "*FX" for k in self.pattern),
                "state_bytes": len(pos) * self._state_bytes_slot}
        if "L" in self.pattern:
            rows["rows_latent"] = full * self.pattern.count("L")
        return rows

    @cached_property
    def _state_bytes_slot(self) -> int:
        """Bytes of one slot's recurrent state (window and state) over the
        ``M`` and ``S`` layers."""
        caches = jax.eval_shape(lambda: self.init_decode_cache(1, 1))
        return sum(a.size * a.dtype.itemsize for layer, c in zip(self.pattern, caches)
                   if layer in RECURRENT for a in (c.conv, c.ssm))

    def reset_slot(self, caches, slot):
        from tpudml.serve.cache import reset_slot_state

        return reset_slot_state(caches, slot)

    def apply_prefill(self, params, caches, chunk, slot, start: int, n_real):
        """Prefill one chunk of one slot's prompt: ``chunk`` [1, C] tokens at
        positions [start, start + C) of which the first ``n_real`` (traced)
        are real -> (updated caches, routes [C, route_width]). ``start`` is
        static, as for every model the engine serves; the recurrent layers
        continue from the slot's stored state, which admission zeroed before
        the first chunk. Only the first ``prefill_entries`` of the pattern
        run."""
        new = list(caches)
        routes, made = [], {}
        real = (jnp.arange(chunk.shape[1]) < n_real)[None]

        def mix(i, kind, mixer, p, u):
            if kind == "M":
                out, new[i] = mixer.apply_prefill(p, caches[i], u, slot, n_real)
            elif kind == "S":
                out, made["m"], new[i] = mixer.apply_prefill(p, caches[i], u, slot, n_real)
            elif kind == "G":
                out = mixer.forward(p, u, made["m"])
            elif kind == "X":
                out, _ = mixer.apply_prefill(p, new[self._source(i)], u, slot, start)
            elif kind in ATTENTION + LATENT:
                out, new[i] = mixer.apply_prefill(p, caches[i], u, slot, start, n_real)
            elif kind == "D":
                out = mixer.apply(p, {}, u)[0]
            else:
                out, counts = mixer.forward(p, u, real)
                routes.append(counts["choices"])
            return out

        # The last entry's own output is kept by nobody either: of an
        # attention layer there, the compiler keeps the K and V projections
        # and the cache write, and drops the queries, the scores and the rest.
        self._layers(params, params["embed"][chunk], mix, self.prefill_entries)
        return tuple(new), self._routes(routes, chunk.shape[1])

    def apply_decode(self, params, caches, tokens, pos, active):
        """One decode step: ``tokens`` [B] at per-slot positions ``pos`` [B],
        ``active`` [B] bool -> (logits [B, V], updated caches, counters,
        routes [B, route_width]).
        The counters are int32 scalars over the expert layers, for active
        slots only: ``moe_routed`` (token, choice) pairs, ``moe_held`` of
        them on held experts, ``experts_touched`` held experts with at
        least one token (summed over layers), ``expert_load_max`` the most
        tokens on one expert of one layer; with ``moe_groups`` also
        ``moe_group_hit``, (token, layer) pairs whose kept groups include a
        held expert's."""
        new = list(caches)
        seen, made = [], {}

        def mix(i, kind, mixer, p, u):
            if kind == "M":
                out, new[i] = mixer.apply_decode(p, caches[i], u, active)
            elif kind == "S":
                out, made["m"], new[i] = mixer.apply_decode(p, caches[i], u, active)
            elif kind == "G":
                out = mixer.forward(p, u, made["m"])
            elif kind == "X":  # the F's cache as this step's F left it; unwritten
                at = self._source(i)
                out, new[at] = mixer.apply_decode(p, new[at], u, pos)
            elif kind in ATTENTION + LATENT:
                out, new[i] = mixer.apply_decode(p, caches[i], u, pos)
            elif kind == "D":
                out = mixer.apply(p, {}, u)[0]
            else:
                out, counts = mixer.forward(p, u, active[:, None])
                seen.append(counts)
            return out

        h = self._layers(params, params["embed"][tokens][:, None, :], mix)
        zero = jnp.zeros((), jnp.int32)
        counters = {
            "moe_routed": sum((c["routed"] for c in seen), zero),
            "moe_held": sum((c["held"] for c in seen), zero),
            "experts_touched": sum((c["touched"] for c in seen), zero),
            "expert_load_max": jnp.max(jnp.stack([c["load_max"] for c in seen] or [zero])),
            "moe_group_hit": sum((c["group_hit"] for c in seen), zero),
        }
        routes = self._routes([c["choices"] for c in seen], tokens.shape[0])
        return self._logits(params, h)[:, 0, :], tuple(new), counters, routes

    def _routes(self, choices: list, n: int):
        """The ``E`` layers' choices [n, top_k] side by side: [n, route_width]."""
        return jnp.concatenate(choices or [jnp.zeros((n, 0), jnp.int32)], axis=-1)
