"""Transformer blocks and a decoder-only LM.

No analogue exists in the reference (its models are a LeNet CNN and an MLP
— SURVEY.md §5.7 records the absence of any sequence model), but
long-context capability is first-class here, so the transformer is the
framework's flagship sequence model:

- ``TransformerBlock`` is stateless and shape-preserving — exactly the
  homogeneous-stage contract of the GPipe engine (``tpudml.parallel.pp``),
  so depth scales by pipeline stages;
- attention ``impl`` ("full" | "ring" | "ulysses") selects single-chip or
  sequence-sharded execution (``tpudml.parallel.cp``) from one model
  definition;
- position embeddings are computed from *global* offsets when the sequence
  axis is sharded, so the same weights give identical math either way.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from tpudml.comm.collectives import axis_size
from tpudml.nn.attention import MultiHeadAttention, sharded_positions
from tpudml.nn.layers import Dense, LayerNorm, Module


# Bound on the one-hot transient the matmul backward materializes
# (elements of [N, V] in dy.dtype). 512M elements (~1 GiB bf16) keeps
# the flagship (8k×32k = 2^28) and chip-filling (16k×32k = 2^29) configs
# on the single-matmul fast path — chunking them was measured to cost
# ~3 ms/step at the flagship (23.3 vs 20.3 ms, fori A/B on v5e: 128
# sequential [2k, 32k] scan steps lose the big matmul's pipelining).
# Past the cap the backward chunks the token axis so memory stays
# O(cap + V·d) instead of O(N·V) — the 131k-token × 32k-vocab regime
# (2^32 elements, ~8.6 GB unchunked) runs as 8 × 1 GiB chunks, exactly
# the O(N·V) blow-up this bound exists to stop (ADVICE r4).
_ONEHOT_ELEM_CAP = 512 * 1024 * 1024


@jax.custom_vjp
def embed_lookup(table: jax.Array, tokens: jax.Array) -> jax.Array:
    """Token-embedding gather with a matmul backward.

    Forward is the plain gather ``table[tokens]``. The backward computes
    dTable = one_hot(tokens)ᵀ @ dy as an MXU matmul instead of autodiff's
    scatter-add: on v5e at [8·1024 tokens, 32k vocab, d=512] the
    scatter-add path measured 3.6 ms vs 1.0 ms for the one-hot matmul
    (round 5, 2026-07-31, older than this code) — TPU scatter serializes
    per-index updates while the matmul is dense MXU work. Same math (each
    table row sums the cotangents of its occurrences); f32 accumulation,
    cast to the table dtype. Above ``_ONEHOT_ELEM_CAP`` one-hot elements the token
    axis is chunked under ``lax.scan`` so the transient stays bounded at
    any sequence length."""
    return table[tokens]


def _embed_lookup_fwd(table, tokens):
    # The table rides along for its static shape/dtype only (a reference,
    # not a copy — it is a live parameter either way).
    return table[tokens], (tokens, table)


def _embed_lookup_bwd(res, dy):
    import numpy as np

    tokens, table = res
    v = table.shape[0]
    d = dy.shape[-1]
    toks = tokens.reshape(-1)
    dyf = dy.reshape(-1, d)
    n = toks.shape[0]
    if n * v <= _ONEHOT_ELEM_CAP:
        oh = jax.nn.one_hot(toks, v, dtype=dy.dtype)
        dtable = lax.dot_general(
            oh, dyf, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    else:
        # Chunk the token axis: each scan step materializes one
        # [chunk, V] one-hot tile and accumulates its matmul into the
        # f32 dTable. Padded rows carry dy = 0, so their (token 0)
        # one-hot contributes nothing.
        chunk = max(_ONEHOT_ELEM_CAP // v, 8)
        pad = (-n) % chunk
        if pad:
            toks = jnp.pad(toks, (0, pad))
            dyf = jnp.pad(dyf, ((0, pad), (0, 0)))
        toks_c = toks.reshape(-1, chunk)
        dy_c = dyf.reshape(-1, chunk, d)

        def body(acc, args):
            t, g = args
            oh = jax.nn.one_hot(t, v, dtype=g.dtype)
            return acc + lax.dot_general(
                oh, g, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ), None

        dtable, _ = lax.scan(body, jnp.zeros((v, d), jnp.float32), (toks_c, dy_c))
    return (
        dtable.astype(table.dtype),
        np.zeros(tokens.shape, dtype=jax.dtypes.float0),
    )


embed_lookup.defvjp(_embed_lookup_fwd, _embed_lookup_bwd)


@dataclass(frozen=True)
class TransformerBlock(Module):
    """Pre-LN decoder block: x + MHA(LN(x)); x + FFN(LN(x)).

    ``moe_experts > 0`` swaps the dense FFN for a Switch-style
    mixture-of-experts layer (``tpudml.nn.moe``); set ``moe_axis`` to run
    the experts sharded under the ExpertParallel engine.
    """

    embed_dim: int
    num_heads: int
    causal: bool = True
    impl: str = "full"
    axis_name: str = "seq"
    remat: bool = False
    num_kv_heads: int | None = None
    rope: bool = False
    rope_base: float = 10000.0
    seq_sharded: bool = False
    seq_layout: str = "contiguous"
    dropout: float = 0.0  # on attention + FFN outputs (train mode, needs rng)
    mlp_ratio: int = 4
    moe_experts: int = 0
    moe_axis: str | None = None
    moe_capacity_factor: float = 2.0
    moe_top_k: int = 1
    moe_dispatch: str = "gather"
    moe_ragged_dw: str = "grouped"  # ragged backward: grouped-dW kernel / stock transpose
    # Fuse the block's ln2 junction (x + attn_out → LayerNorm) into one
    # add+LN Pallas kernel per direction. This is the PIPELINE-stage form
    # of the LM's deferred trunk: the block keeps its shape-preserving
    # x → x contract (the closing residual add stays unfused, so the
    # stage payload is still one tensor), fusing 1 of its 2 junctions —
    # the LM's ``fused_ln`` trunk fuses 2L of 2L+1 by deferring adds
    # across block boundaries, which a pipeline cut cannot do. The FFN
    # branch may be the dense MLP or the MoE layer — the junction kernel
    # fuses the residual ADD, not the branch.
    fused_ln: bool = False
    dtype: Any = jnp.float32

    def _parts(self):
        d = self.embed_dim
        parts = {
            "ln1": LayerNorm(d, dtype=self.dtype),
            "attn": MultiHeadAttention(
                d,
                self.num_heads,
                causal=self.causal,
                impl=self.impl,
                axis_name=self.axis_name,
                remat=self.remat,
                num_kv_heads=self.num_kv_heads,
                rope=self.rope,
                rope_base=self.rope_base,
                seq_sharded=self.seq_sharded,
                seq_layout=self.seq_layout,
                dtype=self.dtype,
            ),
            "ln2": LayerNorm(d, dtype=self.dtype),
        }
        if self.moe_experts:
            from tpudml.nn.moe import MoELayer

            parts["moe"] = MoELayer(
                d,
                self.moe_experts,
                mlp_ratio=self.mlp_ratio,
                capacity_factor=self.moe_capacity_factor,
                top_k=self.moe_top_k,
                axis_name=self.moe_axis,
                dispatch=self.moe_dispatch,
                ragged_dw=self.moe_ragged_dw,
                dtype=self.dtype,
            )
        else:
            parts["fc1"] = Dense(d, self.mlp_ratio * d, dtype=self.dtype)
            parts["fc2"] = Dense(self.mlp_ratio * d, d, dtype=self.dtype)
        return parts

    def init(self, key):
        parts = self._parts()
        keys = jax.random.split(key, len(parts))
        params, states = {}, {}
        for (n, m), k in zip(parts.items(), keys):
            p, s = m.init(k)
            params[n] = p
            if s:
                states[n] = s  # e.g. the MoE aux-loss slot
        return params, states

    def _drop(self, h, train, rng, salt):
        """Inverted dropout via the shared nn.Dropout module; the salt
        fold keeps the attention/FFN masks independent."""
        if not train or self.dropout == 0.0:
            return h
        if rng is None:
            raise ValueError("TransformerBlock dropout requires an rng in train mode")
        from tpudml.nn.layers import Dropout

        return Dropout(self.dropout)(
            {}, h, train=True, rng=jax.random.fold_in(rng, salt)
        )

    def _ffn_branch(self, parts, params, state, y, train):
        """Post-norm FFN branch — dense MLP or MoE. The ONE site that
        encodes the branch contract for every trunk form (block fused/
        unfused, LM deferred); returns (h, per-block state update)."""
        if self.moe_experts:
            h, moe_state = parts["moe"].apply(
                params["moe"], state.get("moe", {}), y, train=train
            )
            return h, {"moe": moe_state}
        h = jax.nn.gelu(parts["fc1"](params["fc1"], y))
        return parts["fc2"](params["fc2"], h), {}

    def apply(self, params, state, x, *, train=False, rng=None):
        parts = self._parts()
        h = parts["ln1"](params["ln1"], x)
        h = parts["attn"](params["attn"], h)
        if self.fused_ln:
            from tpudml.ops.layernorm_kernel import fused_add_layernorm

            s, y2 = fused_add_layernorm(
                x,
                self._drop(h, train, rng, 1),
                params["ln2"]["scale"],
                params["ln2"]["bias"],
            )
            h, new_state = self._ffn_branch(parts, params, state, y2, train)
            return s + self._drop(h, train, rng, 2), new_state
        x = x + self._drop(h, train, rng, 1)
        h = parts["ln2"](params["ln2"], x)
        h, new_state = self._ffn_branch(parts, params, state, h, train)
        return x + self._drop(h, train, rng, 2), new_state


@dataclass(frozen=True)
class TransformerEmbed(Module):
    """Token + learned position embedding. Doubles as the pipeline
    prologue (GPipe runs it replicated ahead of the staged trunk) and as
    TransformerLM's embedding stage; with ``seq_sharded=True`` position
    lookup uses the device's global offset along ``axis_name`` (run under
    shard_map with the time axis sharded)."""

    vocab_size: int
    embed_dim: int
    max_len: int = 1024
    axis_name: str = "seq"
    seq_sharded: bool = False
    seq_layout: str = "contiguous"  # "striped" = balanced causal-ring layout
    use_pos_embed: bool = True  # False when positions come from RoPE
    dtype: Any = jnp.float32

    def init(self, key):
        ke, kp = jax.random.split(key)
        params = {
            "tok_embed": 0.02
            * jax.random.normal(ke, (self.vocab_size, self.embed_dim), self.dtype),
        }
        if self.use_pos_embed:
            params["pos_embed"] = 0.02 * jax.random.normal(
                kp, (self.max_len, self.embed_dim), self.dtype
            )
        return params, {}

    def apply(self, params, state, tokens, *, train=False, rng=None):
        t_local = tokens.shape[1]
        t_global = (
            axis_size(self.axis_name) * t_local if self.seq_sharded else t_local
        )
        if self.use_pos_embed and t_global > self.max_len:
            # Trace-time guard: out-of-range gathers clamp silently under
            # jit, which would reuse pos_embed[max_len-1] for the overflow
            # and corrupt position information without any signal. RoPE
            # (use_pos_embed=False) has no table to overflow — lengths
            # beyond max_len are legitimate extrapolation.
            raise ValueError(
                f"sequence length {t_global} exceeds max_len {self.max_len}"
            )
        h = embed_lookup(params["tok_embed"], tokens)
        if self.use_pos_embed:
            positions = sharded_positions(
                self.axis_name, t_local, self.seq_sharded, self.seq_layout
            )
            h = h + params["pos_embed"][positions]
        return h, state


@dataclass(frozen=True)
class TransformerHead(Module):
    """Final LayerNorm + vocab projection — the pipeline epilogue."""

    embed_dim: int
    vocab_size: int
    dtype: Any = jnp.float32

    def init(self, key):
        kl, kh = jax.random.split(key)
        return {
            "ln_f": LayerNorm(self.embed_dim, dtype=self.dtype).init(kl)[0],
            "head": Dense(self.embed_dim, self.vocab_size, dtype=self.dtype).init(kh)[0],
        }, {}

    def apply(self, params, state, x, *, train=False, rng=None):
        h = LayerNorm(self.embed_dim, dtype=self.dtype)(params["ln_f"], x)
        head = Dense(self.embed_dim, self.vocab_size, dtype=self.dtype)
        return head(params["head"], h), state


@dataclass(frozen=True)
class TransformerLM(Module):
    """Decoder-only language model: token + learned position embeddings,
    N pre-LN blocks, final LayerNorm, vocab projection.

    ``seq_sharded=True`` makes position lookup use the device's global
    offset along ``axis_name`` (the model then must run under shard_map
    with the time axis sharded — the ContextParallel engine's regime).
    """

    vocab_size: int
    embed_dim: int = 128
    num_heads: int = 4
    num_layers: int = 2
    max_len: int = 1024
    impl: str = "full"
    axis_name: str = "seq"
    seq_sharded: bool = False
    seq_layout: str = "contiguous"
    remat: bool = False
    num_kv_heads: int | None = None
    rope: bool = False
    rope_base: float = 10000.0
    dropout: float = 0.0
    moe_experts: int = 0
    moe_axis: str | None = None
    moe_capacity_factor: float = 2.0
    moe_top_k: int = 1
    moe_dispatch: str = "gather"
    moe_ragged_dw: str = "grouped"  # ragged backward: grouped-dW kernel / stock transpose
    dtype: Any = jnp.float32
    # Fused residual-add + LayerNorm junctions (tpudml.ops.layernorm_kernel
    # .fused_add_layernorm): the trunk defers each block's closing residual
    # add into the NEXT norm's kernel, so all 2L adds and 2L of the 2L+1
    # norms run as one Pallas kernel per direction with the backward's
    # residual-gradient merge folded in (round-3 ablation: the in-situ LN
    # cost is fusion structure, not arithmetic — BASELINE.md). Identical
    # math to the unfused path (the sum rounds to the stream dtype before
    # the f32 statistics); the FFN branch may be dense or MoE (the kernel
    # fuses the residual ADD, not the branch — MoE aux state threads
    # through the deferred trunk). On non-TPU backends the op dispatches
    # to reference math, so the flag is safe everywhere.
    fused_ln: bool = False
    # Mixed precision, ResNet-style: parameters stay in ``dtype`` (the f32
    # master copy the optimizer updates) and are cast per-apply to
    # ``compute_dtype`` so the matmuls hit the MXU at bf16 throughput.
    # Norm scales/biases and the router stay f32 (LayerNorm statistics and
    # routing softmax are computed in f32 regardless); logits stay in the
    # compute dtype (softmax_cross_entropy computes its statistics in f32
    # from bf16 logits without materializing an f32 copy).
    # None means "compute in the parameter dtype" — NOT the same as
    # jnp.float32: the legacy all-bf16 mode (dtype=bf16, compute_dtype
    # unset) must keep computing in bf16, not get upcast.
    compute_dtype: Any = None

    @property
    def head_dim(self) -> int:
        return self.embed_dim // self.num_heads

    @property
    def max_positions(self) -> int | None:
        """Positions the model can embed: the learned table's length, or
        None under RoPE (which extrapolates)."""
        return None if self.rope else self.max_len

    def _block(self) -> TransformerBlock:
        return TransformerBlock(
            self.embed_dim,
            self.num_heads,
            causal=True,
            impl=self.impl,
            axis_name=self.axis_name,
            remat=self.remat,
            num_kv_heads=self.num_kv_heads,
            rope=self.rope,
            rope_base=self.rope_base,
            seq_sharded=self.seq_sharded,
            seq_layout=self.seq_layout,
            dropout=self.dropout,
            moe_experts=self.moe_experts,
            moe_axis=self.moe_axis,
            moe_capacity_factor=self.moe_capacity_factor,
            moe_top_k=self.moe_top_k,
            moe_dispatch=self.moe_dispatch,
            moe_ragged_dw=self.moe_ragged_dw,
            dtype=self.dtype,
        )

    # Composition: the LM IS embed → blocks → head, with the param tree
    # kept FLAT (tok_embed/pos_embed/block{i}/ln_f/head) so checkpoints,
    # TP sharding rules, and pipeline prologue/epilogue trees stay in one
    # format regardless of which engine runs the model.

    def _embed(self) -> TransformerEmbed:
        return TransformerEmbed(
            self.vocab_size,
            self.embed_dim,
            self.max_len,
            axis_name=self.axis_name,
            seq_sharded=self.seq_sharded,
            seq_layout=self.seq_layout,
            use_pos_embed=not self.rope,
            dtype=self.dtype,
        )

    def _head(self) -> "TransformerHead":
        return TransformerHead(self.embed_dim, self.vocab_size, dtype=self.dtype)

    def init(self, key):
        ke, kb, kh = jax.random.split(key, 3)
        params = dict(self._embed().init(ke)[0])
        params.update(self._head().init(kh)[0])
        block = self._block()
        states = {}
        for i, k in enumerate(jax.random.split(kb, self.num_layers)):
            p, s = block.init(k)
            params[f"block{i}"] = p
            if s:
                states[f"block{i}"] = s  # MoE aux-loss slots
        return params, states

    def _cast_params(self, params):
        if self.compute_dtype is None:
            return params
        keep_f32 = {"ln1", "ln2", "ln_f", "router"}

        from tpudml.core.pytree import path_names

        def cast(path, p):
            names = set(path_names(path))
            return p if names & keep_f32 else p.astype(self.compute_dtype)

        return jax.tree_util.tree_map_with_path(cast, params)

    def _trunk(self, params, state, tokens, train, rng):
        """embed → blocks (params already cast); no final norm/head."""
        embed_keys = ("tok_embed",) + (() if self.rope else ("pos_embed",))
        h = self._embed()({k: params[k] for k in embed_keys}, tokens)
        block = self._block()
        new_state = {}
        for i in range(self.num_layers):
            h, s = block.apply(
                params[f"block{i}"], state.get(f"block{i}", {}), h,
                train=train,
                rng=None if rng is None else jax.random.fold_in(rng, i),
            )
            if s:
                new_state[f"block{i}"] = s
        return h, new_state

    def _trunk_deferred(self, params, state, tokens, train, rng):
        """Fused-junction trunk (``fused_ln=True``): embed → blocks with
        each residual add deferred into the next norm's fused add+LN
        kernel. The FFN branch is the dense MLP or the MoE layer — the
        junction kernel is FFN-agnostic (it fuses the residual ADD, not
        the branch). Returns ``(s, pend, new_state)`` — the residual
        stream, the still-unadded final FFN branch (so the caller can
        close the last junction inside the final-norm fusion too), and
        the threaded model state (MoE aux-loss slots)."""
        from tpudml.ops.layernorm_kernel import fused_add_layernorm

        embed_keys = ("tok_embed",) + (() if self.rope else ("pos_embed",))
        s = self._embed()({k: params[k] for k in embed_keys}, tokens)
        block = self._block()
        parts = block._parts()
        pend = None
        new_state = {}
        for i in range(self.num_layers):
            p = params[f"block{i}"]
            brng = None if rng is None else jax.random.fold_in(rng, i)
            if pend is None:
                y = parts["ln1"](p["ln1"], s)
            else:
                s, y = fused_add_layernorm(
                    s, pend, p["ln1"]["scale"], p["ln1"]["bias"]
                )
            a = parts["attn"](p["attn"], y)
            s, y2 = fused_add_layernorm(
                s,
                block._drop(a, train, brng, 1),
                p["ln2"]["scale"],
                p["ln2"]["bias"],
            )
            h, st = block._ffn_branch(
                parts, p, state.get(f"block{i}", {}), y2, train
            )
            if st:
                new_state[f"block{i}"] = st
            pend = block._drop(h, train, brng, 2)
        return s, pend, new_state

    def _features_deferred(self, params, state, tokens, train, rng):
        """Deferred trunk closed through the final norm: the last block's
        residual add fuses into ln_f."""
        from tpudml.ops.layernorm_kernel import fused_add_layernorm

        s, pend, new_state = self._trunk_deferred(params, state, tokens, train, rng)
        _, y = fused_add_layernorm(
            s, pend, params["ln_f"]["scale"], params["ln_f"]["bias"]
        )
        return y, new_state

    def _use_fused_ln(self):
        # num_layers=0 leaves no junction to fuse (pend would stay None).
        return self.fused_ln and self.num_layers > 0

    def apply(self, params, state, tokens, *, train=False, rng=None):
        params = self._cast_params(params)
        if self._use_fused_ln():
            y, new_state = self._features_deferred(params, state, tokens, train, rng)
            head = Dense(self.embed_dim, self.vocab_size, dtype=self.dtype)
            return head(params["head"], y), new_state
        h, new_state = self._trunk(params, state, tokens, train, rng)
        logits = self._head()({k: params[k] for k in ("ln_f", "head")}, h)
        # Logits stay in compute dtype: softmax_cross_entropy computes its
        # statistics in f32 from bf16 logits without materializing an f32
        # copy (a [B·T, 32k] cast is ~1 GB of HBM traffic at LM scale),
        # and argmax/accuracy are dtype-insensitive.
        return logits, new_state

    # ----------------------------------------------------- serving paths
    # KV-cached incremental decode + chunked prefill (tpudml.serve). Both
    # run the UNFUSED pre-LN math with train=False — exactly _trunk's
    # composition — so greedy decode is logit-exact against apply() (the
    # tests/test_serve.py parity contract). MoE is rejected: routing a
    # single token re-runs the full dispatch machinery for no cache
    # reuse; PP likewise has no serve composition (docs/API.md).

    def _serve_guard(self):
        if self.moe_experts:
            raise NotImplementedError(
                "serve decode does not compose with MoE blocks yet"
            )
        if self._use_fused_ln():
            # fused_add_layernorm is a throughput fusion for [B, T≫1, d]
            # streams; a one-token decode step gains nothing and the
            # unfused math is the parity reference. Reject rather than
            # silently diverge from the training-time configuration.
            raise NotImplementedError(
                "serve decode runs the unfused-LN math; build the serving "
                "model with fused_ln=False"
            )
        if self.seq_sharded:
            raise ValueError("serve decode requires seq_sharded=False")

    def init_decode_cache(self, batch: int, max_len: int | None = None,
                          kind: str = "f32"):
        """Per-layer KV caches for ``batch`` decode slots: a tuple of
        ``num_layers`` ``serve.cache.KVCache`` pytrees, each
        [batch, max_len, kv_heads, head_dim] (GQA shrinks the head axis;
        TP shards it). ``kind`` selects f32/bf16/int8 storage."""
        from tpudml.serve.cache import init_cache

        self._serve_guard()
        max_len = self.max_len if max_len is None else max_len
        if not self.rope and max_len > self.max_len:
            raise ValueError(
                f"cache max_len {max_len} exceeds the position table "
                f"({self.max_len}); only RoPE models extrapolate"
            )
        head_dim = self.embed_dim // self.num_heads
        kv_heads = self.num_kv_heads or self.num_heads
        return tuple(
            init_cache(batch, max_len, kv_heads, head_dim, kind)
            for _ in range(self.num_layers)
        )

    def init_paged_cache(self, num_pages: int, page_size: int,
                         kind: str = "f32"):
        """Per-layer page pools: a tuple of ``num_layers``
        ``serve.paged.PagedKVCache`` pytrees, each
        [num_pages, page_size, kv_heads, head_dim]. The slot→page table
        lives with the engine, not the pool — every slot reads through
        its table rows, so pool size is an HBM budget, not a sequence
        bound (per-slot capacity is the table width × page_size)."""
        from tpudml.serve.paged import init_pool

        self._serve_guard()
        head_dim = self.embed_dim // self.num_heads
        kv_heads = self.num_kv_heads or self.num_heads
        return tuple(
            init_pool(num_pages, page_size, kv_heads, head_dim, kind)
            for _ in range(self.num_layers)
        )

    def _decode_embed(self, params, tokens, pos):
        """[B] tokens at per-slot positions ``pos`` [B] → [B, 1, d]."""
        h = params["tok_embed"][tokens][:, None, :]
        if not self.rope:
            h = h + params["pos_embed"][pos][:, None, :]
        return h

    def _decode_embed_window(self, params, tokens, pos):
        """[B, Q] window tokens, first at per-slot positions ``pos`` [B]
        → [B, Q, d]."""
        h = params["tok_embed"][tokens]
        if not self.rope:
            positions = pos[:, None] + jnp.arange(tokens.shape[1])[None, :]
            h = h + params["pos_embed"][positions]
        return h

    def _serve_blocks(self, params, caches, h, attend):
        """Shared block loop of both serving paths: pre-LN attention (via
        ``attend(attn_module, block_params, cache, y)``) and the dense
        FFN, threading per-layer caches."""
        block = self._block()
        parts = block._parts()
        new_caches = []
        for i, cache in enumerate(caches):
            p = params[f"block{i}"]
            y = parts["ln1"](p["ln1"], h)
            a, cache = attend(parts["attn"], p["attn"], cache, y)
            h = h + a
            y2 = parts["ln2"](p["ln2"], h)
            f = parts["fc2"](p["fc2"], jax.nn.gelu(parts["fc1"](p["fc1"], y2)))
            h = h + f
            new_caches.append(cache)
        return h, tuple(new_caches)

    def apply_decode(self, params, caches, tokens, pos):
        """One incremental decode step: ``tokens`` [B] at per-slot
        positions ``pos`` [B] → (logits [B, V], updated caches). Each
        slot's K/V land in its cache row at ``pos``; attention covers
        the slot's written prefix only. Cost per emitted token is O(L·d)
        — never the O(T²) training kernel."""
        self._serve_guard()
        params = self._cast_params(params)
        h = self._decode_embed(params, tokens, pos)
        h, new_caches = self._serve_blocks(
            params, caches, h,
            lambda attn, p, cache, y: attn.apply_decode(p, cache, y, pos),
        )
        logits = self._head()({k: params[k] for k in ("ln_f", "head")}, h)
        return logits[:, 0, :], new_caches

    def apply_decode_features(self, params, caches, tokens, pos):
        """One incremental decode step STOPPING AT THE FEATURES: embed →
        cached blocks → final LayerNorm, without the vocab projection —
        (features [B, d], updated caches). The input contract of the
        fused decode head (``tpudml.ops.decode_head``), which consumes
        features + head weights and never materializes the [B, V]
        logits; the serving twin of ``apply_features``."""
        self._serve_guard()
        params = self._cast_params(params)
        h = self._decode_embed(params, tokens, pos)
        h, new_caches = self._serve_blocks(
            params, caches, h,
            lambda attn, p, cache, y: attn.apply_decode(p, cache, y, pos),
        )
        h = LayerNorm(self.embed_dim, dtype=self.dtype)(params["ln_f"], h)
        return h[:, 0, :], new_caches

    def apply_decode_window(self, params, caches, tokens, pos):
        """Decode a window of Q consecutive tokens per slot over the
        dense cache: ``tokens`` [B, Q], first token at ``pos`` [B] →
        (logits [B, Q, V], updated caches). The speculative verify step:
        one model pass scores all Q positions; greedy acceptance then
        commits a prefix of them. Q=1 matches apply_decode exactly."""
        self._serve_guard()
        params = self._cast_params(params)
        h = self._decode_embed_window(params, tokens, pos)
        h, new_caches = self._serve_blocks(
            params, caches, h,
            lambda attn, p, cache, y: attn.apply_decode_window(p, cache, y, pos),
        )
        logits = self._head()({k: params[k] for k in ("ln_f", "head")}, h)
        return logits, new_caches

    def apply_decode_paged(self, params, caches, table, tokens, pos):
        """Decode over paged pools: ``table`` [B, max_pages] maps each
        slot to its pages, ``tokens`` [B, Q] (Q=1 plain decode, Q=K+1
        spec verify), ``pos`` [B] → (logits [B, Q, V], updated pools)."""
        self._serve_guard()
        params = self._cast_params(params)
        h = self._decode_embed_window(params, tokens, pos)
        h, new_caches = self._serve_blocks(
            params, caches, h,
            lambda attn, p, pool, y: attn.apply_decode_paged(p, pool, table, y, pos),
        )
        logits = self._head()({k: params[k] for k in ("ln_f", "head")}, h)
        return logits, new_caches

    def apply_prefill_paged(self, params, caches, table_row, chunk, start: int):
        """Paged prefill of one chunk: ``table_row`` [max_pages] is the
        admitted slot's page map, ``chunk`` [1, C] tokens at positions
        [start, start+C) → updated pools. ``start`` static, like the
        dense path."""
        self._serve_guard()
        params = self._cast_params(params)
        c = chunk.shape[1]
        h = params["tok_embed"][chunk]
        if not self.rope:
            if start + c > self.max_len:
                raise ValueError(
                    f"prefill window {start + c} exceeds max_len {self.max_len}"
                )
            h = h + params["pos_embed"][start:start + c][None]
        _, new_caches = self._serve_blocks(
            params, caches, h,
            lambda attn, p, pool, y: attn.apply_prefill_paged(
                p, pool, table_row, y, start
            ),
        )
        return new_caches

    def apply_prefill(self, params, caches, chunk, slot, start: int):
        """Prefill one chunk of one slot's prompt: ``chunk`` [1, C]
        tokens at global positions [start, start+C) → updated caches.
        ``start`` is static (one compiled program per chunk index); no
        logits — the engine feeds the prompt's LAST token through
        ``apply_decode`` to emit the first generated token."""
        self._serve_guard()
        params = self._cast_params(params)
        c = chunk.shape[1]
        h = params["tok_embed"][chunk]
        if not self.rope:
            if start + c > self.max_len:
                raise ValueError(
                    f"prefill window {start + c} exceeds max_len {self.max_len}"
                )
            h = h + params["pos_embed"][start:start + c][None]
        _, new_caches = self._serve_blocks(
            params, caches, h,
            lambda attn, p, cache, y: attn.apply_prefill(p, cache, y, slot, start),
        )
        return new_caches

    def apply_features(self, params, state, tokens, *, train=False, rng=None):
        """Pre-head features: embed → blocks → final LayerNorm, WITHOUT
        the vocab projection — the input contract of the fused
        linear-cross-entropy kernel (``tpudml.ops.xent_kernel``), which
        consumes features + head weights and never materializes the
        [B·T, V] logits."""
        params = self._cast_params(params)
        if self._use_fused_ln():
            y, new_state = self._features_deferred(params, state, tokens, train, rng)
            return y, new_state
        h, new_state = self._trunk(params, state, tokens, train, rng)
        h = LayerNorm(self.embed_dim, dtype=self.dtype)(params["ln_f"], h)
        return h, new_state
