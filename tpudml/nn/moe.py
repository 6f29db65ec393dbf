"""Mixture-of-Experts layer with expert parallelism.

Absent from the reference (SURVEY.md §2.3 lists EP/MoE as out of parity
scope), built here to complete the parallelism matrix. TPU-first design:

- top-k routing (k=1 Switch, k>1 GShard) with a static per-shard expert
  capacity C, so every shape is fixed under jit;
- dispatch/combine are static-shape ROW GATHERS over a flat slot index
  (default ``dispatch="gather"``): the choice-priority cumsum assigns each
  (token, choice) a flat slot in [0, E·C) (sentinel when capacity-dropped),
  dispatch gathers token rows into [E, C, d], combine gathers each token's
  k expert outputs back, gate-weighted. The slot map is injective, so both
  backwards are the INVERSE gather (custom VJPs — no row scatter-adds, no
  [G, E, C] one-hot buffers, no O(G·E·C·d) einsum FLOPs). The GShard
  one-hot einsum formulation survives as ``dispatch="einsum"``, the parity
  oracle: both paths consume the identical slot assignment. Measured on a
  v5e (round 5, 2026-07-31, older than this code): the einsum dispatch
  cost ~1.9-2.5× dense at matched active FLOPs; gather removes that
  overhead. Tokens past capacity are dropped (combine weight 0), the
  standard Switch trade;
- under expert parallelism (``axis_name`` set, run inside shard_map),
  tokens AND experts are sharded over the same mesh axis: each shard
  routes its local tokens, one ``all_to_all`` ships the [E, C, d] dispatch
  to the owning experts, the local expert FFNs run, and the inverse
  ``all_to_all`` returns outputs to the token owners. Communication is two
  all_to_alls of C·d per expert — never the full activations.

Routing gradients flow through the combine gate (straight-through on the
argmax path); an auxiliary load-balancing loss is exposed via
:func:`load_balancing_loss` for callers that want Switch-style balance
pressure in their objective.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from tpudml.nn.layers import Module, _uniform_fan_in
from tpudml.ops.moe_kernel import ragged_ffn


def _pad0(rows):
    """Append one zero row — the landing pad for sentinel indices."""
    return jnp.concatenate([rows, jnp.zeros((1, rows.shape[-1]), rows.dtype)], 0)


def _switch_aux(frac, probs, num_experts):
    """Switch/GShard load-balance loss E · Σ_e frac_e · p̄_e (=1 uniform).
    ``frac`` is the per-expert dispatch fraction averaged over all k
    choices — shared by every dispatch branch so the formulation cannot
    silently diverge between them."""
    return num_experts * jnp.sum(frac * jnp.mean(probs, axis=0))


@jax.custom_vjp
def _permute_rows(tokens_pad, token_src, flat_dst):
    """Dispatch gather: out[s] = tokens_pad[token_src[s]] for every expert
    slot s (``token_src`` sentinel = G hits the appended zero row).

    The slot assignment is INJECTIVE — each slot holds at most one
    (token, choice) and each (token, choice) owns at most one slot — so
    the backward is the inverse gather over ``flat_dst`` [G, k] (sentinel
    = S), never a scatter-add of [*, d] rows (the op autodiff would emit
    for ``take``, which serializes on TPU — the same finding that moved
    the embedding backward to an MXU matmul in round 4)."""
    return jnp.take(tokens_pad, token_src, axis=0)


def _permute_rows_fwd(tokens_pad, token_src, flat_dst):
    return _permute_rows(tokens_pad, token_src, flat_dst), (
        flat_dst,
        tokens_pad.shape[0],
    )


def _permute_rows_bwd(res, dy):
    flat_dst, n_pad = res
    # dTokens[g] = Σ_j dy[flat_dst[g, j]]; sentinel rides the zero row.
    d_tok = jnp.sum(jnp.take(_pad0(dy), flat_dst, axis=0), axis=1)
    d_pad = jnp.zeros((n_pad - d_tok.shape[0], dy.shape[-1]), dy.dtype)
    return jnp.concatenate([d_tok, d_pad], 0), None, None


_permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


@jax.custom_vjp
def _combine_rows(expert_flat, w, flat_dst, token_src):
    """Combine gather: y[g] = Σ_j w[g, j] · expert_flat[flat_dst[g, j]]
    (gate-weighted return of each token's k expert outputs; dropped
    choices carry w = 0 and a sentinel index onto the zero row).

    Backward wrt ``expert_flat`` is again the inverse gather — slot s's
    cotangent is w_at_slot[s] · dy[token_src[s]] — computed via a [S]
    scalar scatter of the gate values (tiny) plus one row gather."""
    rows = jnp.take(_pad0(expert_flat), flat_dst, axis=0)  # [G, k, d]
    return jnp.einsum("gk,gkd->gd", w, rows.astype(w.dtype))


def _combine_rows_fwd(expert_flat, w, flat_dst, token_src):
    return _combine_rows(expert_flat, w, flat_dst, token_src), (
        expert_flat,
        w,
        flat_dst,
        token_src,
    )


def _combine_rows_bwd(res, dy):
    expert_flat, w, flat_dst, token_src = res
    s_total = expert_flat.shape[0]
    # Re-gather the rows (cheaper than holding [G, k, d] as a residual).
    rows = jnp.take(_pad0(expert_flat), flat_dst, axis=0)
    dw = jnp.einsum("gd,gkd->gk", dy, rows.astype(dy.dtype)).astype(w.dtype)
    # Gate value seen by each slot: a [S]-scalar scatter (collisions only
    # on the sliced-off sentinel row).
    w_src = (
        jnp.zeros((s_total + 1,), w.dtype)
        .at[flat_dst.reshape(-1)]
        .set(w.reshape(-1))[:s_total]
    )
    dy_tok = jnp.take(_pad0(dy), token_src, axis=0)  # [S, d]
    d_expert = (w_src[:, None] * dy_tok).astype(expert_flat.dtype)
    return d_expert, dw, None, None


_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


@dataclass(frozen=True)
class MoELayer(Module):
    """Top-k mixture-of-experts FFN over [..., embed_dim] inputs.

    ``top_k=1`` is the Switch formulation (raw top-1 probability as the
    gate); ``top_k>1`` is GShard-style — each token dispatches to its k
    best experts with gates renormalized over the chosen k, capacity
    scaled by k, and choice 0 taking buffer priority over choice 1 (a
    token's secondary pick is dropped first under overflow).

    ``axis_name=None``: single-shard dense routing. ``axis_name="expert"``:
    expert-parallel — must run under shard_map with tokens sharded over the
    axis and ``num_experts`` divisible by the axis size.
    """

    embed_dim: int
    num_experts: int
    mlp_ratio: int = 4
    capacity_factor: float = 1.25
    top_k: int = 1
    axis_name: str | None = None
    dtype: Any = jnp.float32
    # "gather": slot-index dispatch/combine via row gathers with
    # inverse-gather backwards — O(S·d) data movement, no O(G·E·C·d)
    # FLOPs and no [G, E, C] buffers. "einsum": the GShard one-hot
    # formulation, kept as the parity oracle (identical routing by
    # construction — both consume the same flat_dst slot assignment).
    # "ragged": DROPLESS — tokens sorted by expert feed lax.ragged_dot
    # grouped matmuls; no capacity, no drops, no padded slots (single-
    # shard only: EP's all_to_all needs the static capacity buffers).
    dispatch: str = "gather"
    # Backward for the ragged FFN's weight gradients. "grouped" routes
    # dW1/dW2 through ops.moe_kernel.ragged_ffn (Pallas grouped-dW on
    # TPU, reference segment-einsum elsewhere — cost ∝ tokens).
    # "stock" keeps lax.ragged_dot's own transpose (an E-scaled masked
    # matmul — the 3.4× backward of BASELINE round 5) for A/B runs;
    # the analyzer flags it as J109.
    ragged_dw: str = "grouped"

    def __post_init__(self):
        if not 1 <= self.top_k <= self.num_experts:
            raise ValueError(
                f"top_k {self.top_k} must be in [1, num_experts={self.num_experts}]"
            )
        if self.dispatch not in ("gather", "einsum", "ragged"):
            raise ValueError(
                f"dispatch must be 'gather', 'einsum', or 'ragged', got {self.dispatch!r}"
            )
        if self.ragged_dw not in ("grouped", "stock"):
            raise ValueError(
                f"ragged_dw must be 'grouped' or 'stock', got {self.ragged_dw!r}"
            )
        if self.dispatch == "ragged" and self.axis_name is not None:
            raise ValueError(
                "dispatch='ragged' is single-shard only — expert parallelism "
                "ships static [E, C, d] capacity buffers over all_to_all, "
                "which the dropless path deliberately does not build; use "
                "dispatch='gather' under EP"
            )

    def init(self, key):
        d, e, h = self.embed_dim, self.num_experts, self.mlp_ratio * self.embed_dim
        kr, k1, kb1, k2, kb2 = jax.random.split(key, 5)
        params = {
            "router": {"kernel": _uniform_fan_in(kr, (d, e), d, self.dtype)},
            "experts": {
                "w1": _uniform_fan_in(k1, (e, d, h), d, self.dtype),
                "b1": _uniform_fan_in(kb1, (e, h), d, self.dtype),
                "w2": _uniform_fan_in(k2, (e, h, d), h, self.dtype),
                "b2": _uniform_fan_in(kb2, (e, d), h, self.dtype),
            },
        }
        # aux_loss lives in state from init so the TrainState pytree
        # structure is stable across steps; make_loss_fn(aux_loss_weight=α)
        # folds it into the objective (gradients flow to the router).
        return params, {"aux_loss": jnp.zeros((), jnp.float32)}

    def _capacity(self, n_tokens: int) -> int:
        return max(
            1,
            int(n_tokens * self.top_k * self.capacity_factor / self.num_experts + 0.5),
        )

    def apply(self, params, state, x, *, train=False, rng=None):
        shape = x.shape
        d, e = self.embed_dim, self.num_experts
        g = 1
        for s in shape[:-1]:
            g *= s
        tokens = x.reshape(g, d)
        cap = self._capacity(g)

        logits = tokens @ params["router"]["kernel"]  # [G, E]
        probs = jax.nn.softmax(logits, axis=-1)
        topv, topi = lax.top_k(probs, self.top_k)  # [G, k]
        if self.top_k == 1:
            gates = topv  # Switch: the raw top-1 probability
        else:
            gates = topv / (jnp.sum(topv, axis=-1, keepdims=True) + 1e-9)

        if self.dispatch == "ragged":
            y = self._ragged_ffn(params["experts"], tokens, topi, gates)
            frac = jnp.mean(
                jnp.sum(jax.nn.one_hot(topi, e, dtype=jnp.float32), axis=1), axis=0
            ) / self.top_k
            return y.reshape(shape), {"aux_loss": _switch_aux(frac, probs, e)}

        # Choice-priority slot assignment: choice 0 claims buffer slots for
        # ALL tokens before choice 1 sees the remaining capacity (k static
        # and small, so the Python loop unrolls). Bookkeeping stays float32
        # regardless of the token dtype — bf16 represents integers exactly
        # only to 256, so a bf16 cumsum would corrupt capacity positions on
        # any real batch. Output: flat_dst [G, k] — each (token, choice)'s
        # flat slot id e·cap + slot, sentinel S = E·cap when dropped.
        s_total = e * cap
        counts = jnp.zeros((e,), jnp.float32)  # slots used per expert
        choice_sum = jnp.zeros((g, e), jnp.float32)  # Σ_j onehot_j per token
        flat_dst = []
        kept_flags = []
        for j in range(self.top_k):
            onehot = jax.nn.one_hot(topi[:, j], e, dtype=jnp.float32)  # [G, E]
            choice_sum = choice_sum + onehot
            pos = counts[None, :] + jnp.cumsum(onehot, axis=0) - onehot  # [G, E]
            kept = onehot * (pos < cap)
            slot = jnp.sum(pos * onehot, axis=-1).astype(jnp.int32)
            kept_g = jnp.sum(kept, axis=-1)  # [G] ∈ {0, 1}
            flat_dst.append(
                jnp.where(kept_g > 0, topi[:, j] * cap + slot, s_total).astype(
                    jnp.int32
                )
            )
            kept_flags.append(kept_g)
            counts = counts + jnp.sum(kept, axis=0)
        flat_dst = jnp.stack(flat_dst, axis=1)  # [G, k]
        w_eff = gates * jnp.stack(kept_flags, axis=1).astype(gates.dtype)  # [G, k]

        if self.dispatch == "gather":
            # Invert the injective (token, choice) → slot map with a [G·k]
            # int32 scatter (tiny; collisions land only on the sentinel
            # row, which the slice drops), then dispatch = one row gather.
            token_src = (
                jnp.full((s_total + 1,), g, jnp.int32)
                .at[flat_dst.reshape(-1)]
                .set(jnp.repeat(jnp.arange(g, dtype=jnp.int32), self.top_k))[:s_total]
            )
            expert_in = _permute_rows(_pad0(tokens), token_src, flat_dst).reshape(
                e, cap, d
            )
        else:
            # GShard one-hot materialization of the SAME slot assignment:
            # [G, k, S] one-hots reduce to the classic [G, E, C] dispatch /
            # combine tensors (O(G·E·C·d) einsum FLOPs — the parity oracle).
            oh = jax.nn.one_hot(flat_dst, s_total + 1, dtype=jnp.float32)[
                :, :, :s_total
            ]
            disp = jnp.sum(oh, axis=1).reshape(g, e, cap)
            combine = jnp.einsum("gks,gk->gs", oh, w_eff).reshape(g, e, cap)
            expert_in = jnp.einsum(
                "gec,gd->ecd", disp.astype(tokens.dtype), tokens
            )  # [E, C, d]
        ep = self.axis_name is not None
        if ep:
            # Ship each expert's buffer to its owning shard: [E, C, d] →
            # [E/W, W·C, d] (and back after the FFN).
            expert_in = lax.all_to_all(
                expert_in, self.axis_name, split_axis=0, concat_axis=1, tiled=True
            )
        w = params["experts"]
        hidden = jax.nn.relu(
            jnp.einsum("ecd,edh->ech", expert_in, w["w1"]) + w["b1"][:, None, :]
        )
        expert_out = (
            jnp.einsum("ech,ehd->ecd", hidden, w["w2"]) + w["b2"][:, None, :]
        )
        if ep:
            expert_out = lax.all_to_all(
                expert_out, self.axis_name, split_axis=1, concat_axis=0, tiled=True
            )
        if self.dispatch == "gather":
            y = _combine_rows(
                expert_out.reshape(s_total, d), w_eff, flat_dst, token_src
            ).astype(tokens.dtype)
        else:
            y = jnp.einsum(
                "gec,ecd->gd", combine.astype(expert_out.dtype), expert_out
            )
        # Aux loss over this shard's tokens, frac averaged over ALL k
        # choices (first-choice-only frac — ADVICE r2 — would leave
        # secondary-choice expert collapse invisible); differentiable
        # through probs.
        frac = jnp.mean(choice_sum, axis=0) / self.top_k
        return y.reshape(shape), {"aux_loss": _switch_aux(frac, probs, e)}

    def _ragged_ffn(self, w, tokens, topi, gates):
        """Dropless grouped-matmul expert FFN (``dispatch="ragged"``).

        (token, choice) pairs are sorted by expert id; ``lax.ragged_dot``
        runs each expert's contiguous row block through its weights — no
        capacity buffers, no dropped tokens, no padded slots computing on
        zeros. The sort permutation is injective and total, so both the
        dispatch and the un-sort are `_permute_rows` gathers (backwards are
        the inverse gathers). Biases ride a [P, E] one-hot MATMUL rather
        than a row gather, so their backward is an MXU matmul instead of a
        scatter-add onto [E, ·] rows.
        """
        g, d = tokens.shape
        e, k = self.num_experts, self.top_k
        p = g * k  # (token, choice) pairs
        eids = topi.reshape(p)  # pair -> expert, pair id = g·k + j
        # Stable argsort keeps same-expert pairs in token order.
        order = jnp.argsort(eids)  # [P] sorted position -> pair id
        inv = (
            jnp.zeros((p,), jnp.int32)
            .at[order]
            .set(jnp.arange(p, dtype=jnp.int32))
        )  # pair id -> sorted position
        group_sizes = jnp.bincount(eids, length=e).astype(jnp.int32)

        token_src = (order // k).astype(jnp.int32)  # sorted position -> token
        flat_dst = inv.reshape(g, k)  # token -> its k sorted positions
        x_sorted = _permute_rows(_pad0(tokens), token_src, flat_dst)  # [P, d]

        # ragged_dot wants matching operand dtypes; promote like einsum would.
        ct = jnp.promote_types(x_sorted.dtype, w["w1"].dtype)
        onehot = jax.nn.one_hot(eids[order], e, dtype=ct)  # [P, E]
        if self.ragged_dw == "grouped":
            # custom_vjp FFN: dW1/dW2 via the grouped-dW kernel (one row
            # walk, f32 accumulation) instead of ragged_dot's E-scaled
            # masked-matmul transpose; dx/dh stay ragged_dot forward-form.
            out_sorted = ragged_ffn(
                x_sorted.astype(ct),
                w["w1"].astype(ct),
                w["b1"].astype(ct),
                w["w2"].astype(ct),
                w["b2"].astype(ct),
                onehot,
                group_sizes,
            )
        else:  # "stock": lax.ragged_dot's own transpose, kept for A/B.
            hidden = jax.nn.relu(
                lax.ragged_dot(x_sorted.astype(ct), w["w1"].astype(ct), group_sizes)
                + onehot @ w["b1"].astype(ct)
            )
            out_sorted = lax.ragged_dot(
                hidden, w["w2"].astype(ct), group_sizes
            ) + onehot @ w["b2"].astype(ct)
        # Gate-weighted un-sort: the same injective-map combine as the
        # gather dispatch, with every choice kept (w_eff = gates).
        return _combine_rows(out_sorted, gates, flat_dst, token_src).astype(
            tokens.dtype
        )


@dataclass(frozen=True)
class SigmoidMoE(Module):
    """Sigmoid-routed mixture of experts with a shared expert, told which
    experts it HOLDS.

    In float32, ``s = sigmoid(u @ W_r)`` over the router's whole width
    ``num_experts``; a token's experts are the top-k of ``s + bias`` (the
    bias only chooses); their weights are ``routed_scale * s_e / (sum of
    the k chosen s + 1e-20)`` (``norm_topk``; without it, ``routed_scale *
    s_e``). ``scoring="softmax"``: ``s = softmax(u @ W_r)`` and no bias.
    ``groups = (n_group, topk_group)`` limits the choice (DeepSeek-V2's
    ``group_limited_greedy``): the experts are ``n_group`` equal groups in
    order, a group's score is its largest choosing score, the best
    ``topk_group`` groups stay (ties: the first), every other group's scores
    are set to 0 before the top-k. An expert is two matrices,
    ``relu(u @ up)^2 @ down``, or with ``gated`` three, ``(silu(u @ gate) *
    (u @ up)) @ down`` (SwiGLU); the shared expert, of width ``shared_dim``
    and of the same form, sees every token (``shared_dim`` 0: there is none).

    ``held = (first, count)``: the contiguous experts whose weights live
    here (default: all of them). The layer routes over all ``num_experts``
    and computes only its own experts' part of the sum, for the tokens
    routed to them, plus the shared expert — one chip's share of an
    expert-parallel layer, without the exchange. What the other experts
    would add is left out, not approximated.

    Dropless and dense over the held experts: every held expert runs over
    every token and a [tokens, held] matrix of weights (zero where a token
    was not routed to the expert, or is not ``active``) combines them, both
    matmuls contracting over (expert, width) at once. On the v5e, at the
    published widths with 64 experts held, this reads the experts' weights
    at 90 % of the memory roof for 128 tokens (1.74 ms a layer) where
    ``lax.ragged_dot`` over the sorted pairs took 15.3 ms (its kernel walks
    64 groups of ~6 rows) and, the width 1856 not filling whole 128-lane
    tiles, a 640 MB transposing copy of the weights a step on top (PERF.md
    §6, PR 30). The cost is FLOPs on tokens an expert was not given: 4x at a
    512-token prefill chunk, which a grouped kernel would win back.
    """

    embed_dim: int
    num_experts: int
    top_k: int
    expert_dim: int
    shared_dim: int
    routed_scale: float = 1.0
    norm_topk: bool = True
    held: tuple[int, int] | None = None
    dtype: Any = jnp.float32
    gated: bool = False
    scoring: str = "sigmoid"
    groups: tuple[int, int] | None = None

    def __post_init__(self):
        if self.scoring not in ("sigmoid", "softmax"):
            raise ValueError(f"scoring must be 'sigmoid' or 'softmax', got {self.scoring!r}")
        if self.groups is not None:
            n_group, keep = self.groups
            if self.num_experts % n_group or not 1 <= keep <= n_group:
                raise ValueError(f"groups {self.groups}: {self.num_experts} experts in "
                                 "n_group equal groups, of which 1..n_group stay")
        first, count = self._held
        if not (0 <= first and count >= 1 and first + count <= self.num_experts):
            raise ValueError(
                f"held experts {self.held} outside [0, {self.num_experts})")
        if not 1 <= self.top_k <= self.num_experts:
            raise ValueError(
                f"top_k {self.top_k} must be in [1, num_experts={self.num_experts}]")

    @property
    def _held(self) -> tuple[int, int]:
        return self.held or (0, self.num_experts)

    def init(self, key):
        d, h, hs = self.embed_dim, self.expert_dim, self.shared_dim
        n = self._held[1]
        kr, ku, kd, ksu, ksd = jax.random.split(key, 5)
        params = {
            "router": {"kernel": _uniform_fan_in(kr, (d, self.num_experts), d,
                                                 jnp.float32)},
            "experts": {"up": _uniform_fan_in(ku, (n, d, h), d, self.dtype),
                        "down": _uniform_fan_in(kd, (n, h, d), h, self.dtype)},
        }
        if self.scoring == "sigmoid":  # the selection bias of an aux-free balancer
            params["router"]["bias"] = jnp.zeros((self.num_experts,), jnp.float32)
        if hs:
            params["shared"] = {
                "up": _uniform_fan_in(ksu, (d, hs), d, self.dtype),
                "down": _uniform_fan_in(ksd, (hs, d), hs, self.dtype)}
        if self.gated:
            kg, ksg = jax.random.split(jax.random.fold_in(key, 1))
            params["experts"]["gate"] = _uniform_fan_in(kg, (n, d, h), d, self.dtype)
            if hs:
                params["shared"]["gate"] = _uniform_fan_in(ksg, (d, hs), d, self.dtype)
        return params, {}

    def _hidden(self, p, tokens, spec: str):
        """An expert's hidden activations: ``tokens`` times ``p``'s ``up``
        (and ``gate``) by the einsum ``spec``."""
        up = jnp.einsum(spec, tokens, p["up"])
        if self.gated:
            return jax.nn.silu(jnp.einsum(spec, tokens, p["gate"])) * up
        return jnp.square(jax.nn.relu(up))

    def scores(self, params, tokens):
        """s [G, num_experts] float32 of tokens [G, d], computed in float32."""
        logits = jnp.dot(tokens.astype(jnp.float32), params["router"]["kernel"],
                         precision=lax.Precision.HIGHEST)
        if self.scoring == "softmax":
            return jax.nn.softmax(logits, axis=-1)
        return jax.nn.sigmoid(logits)

    def kept_groups(self, select):
        """[G, n_group] bool: the ``topk_group`` groups whose largest of
        ``select`` [G, num_experts] is largest."""
        n_group, keep = self.groups
        best = jnp.max(select.reshape(select.shape[0], n_group, -1), axis=-1)
        _, top = lax.top_k(best, keep)
        return jnp.zeros(best.shape, bool).at[jnp.arange(best.shape[0])[:, None], top].set(True)

    def _route(self, params, tokens):
        """`route`, and the kept groups [G, n_group] (None without groups)."""
        s = self.scores(params, tokens)
        select = s + params["router"]["bias"] if "bias" in params["router"] else s
        kept = None
        if self.groups is not None:
            kept = self.kept_groups(select)
            select = jnp.where(jnp.repeat(kept, self.num_experts // self.groups[0], axis=1),
                               select, 0.0)
        _, topi = lax.top_k(select, self.top_k)
        w = jnp.take_along_axis(s, topi, axis=-1)
        if self.norm_topk:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return topi.astype(jnp.int32), self.routed_scale * w, kept

    def route(self, params, tokens):
        """(experts [G, k] int32, weights [G, k] float32) of tokens [G, d]."""
        return self._route(params, tokens)[:2]

    def forward(self, params, x, active=None):
        """x [..., d] -> (y [..., d], counts). ``active`` [...] bool marks
        the tokens that count (default all): the others reach no expert.
        ``counts`` are int32 scalars over active tokens: ``routed`` (token,
        choice) pairs, ``held`` of them on held experts, ``touched`` held
        experts with at least one token, ``load_max`` tokens on the busiest,
        ``group_hit`` the tokens whose kept groups include a held expert's
        (0 without ``groups``); and ``choices`` [G, k] int32, every token's experts as the router
        chose them (of all ``num_experts``, held or not, active or not).

        ``touched`` counts what a step would have to read if it read only
        the experts that have a token. This dense form reads and multiplies
        all the held experts whatever ``touched`` says: the count is a lower
        bound the program does not follow yet."""
        shape = x.shape
        tokens = x.reshape(-1, self.embed_dim)
        g, k = tokens.shape[0], self.top_k
        first, count = self._held
        topi, w, kept = self._route(params, tokens)
        live = jnp.ones((g,), bool) if active is None else active.reshape(g)
        local = topi - first
        mine = (local >= 0) & (local < count) & live[:, None]  # [G, k]
        # [G, k, count]: one-hot of each pair's held expert (none: all zero)
        onehot = jax.nn.one_hot(jnp.where(mine, local, -1), count, dtype=jnp.float32)
        comb = jnp.einsum("gk,gke->ge", w, onehot)  # weights, zero elsewhere
        load = jnp.sum(onehot, axis=(0, 1)).astype(jnp.int32)  # tokens an expert
        ex = params["experts"]
        hidden = self._hidden(ex, tokens, "gd,edh->geh")
        hidden = (hidden.astype(jnp.float32) * comb[..., None]).astype(tokens.dtype)
        y = jnp.einsum("geh,ehd->gd", hidden, ex["down"])
        if self.shared_dim:
            sh = params["shared"]
            y = y + self._hidden(sh, tokens, "gd,dh->gh") @ sh["down"]
        counts = {"routed": jnp.sum(live).astype(jnp.int32) * k,
                  "held": jnp.sum(load), "touched": jnp.sum(load > 0).astype(jnp.int32),
                  "load_max": jnp.max(load), "choices": topi,
                  "group_hit": jnp.zeros((), jnp.int32)}
        if kept is not None:
            size = self.num_experts // self.groups[0]
            mine_groups = kept[:, first // size:(first + count - 1) // size + 1]
            counts["group_hit"] = jnp.sum(live & jnp.any(mine_groups, axis=1)).astype(jnp.int32)
        return y.reshape(shape), counts

    def apply(self, params, state, x, *, train=False, rng=None):
        return self.forward(params, x)[0], state


def load_balancing_loss(params: dict, x: jax.Array, num_experts: int) -> jax.Array:
    """Switch-style auxiliary loss: E · Σ_e fraction_e · mean_prob_e —
    minimized (→1) when routing is uniform. Add ``α·aux`` to the training
    objective (α ≈ 0.01) to keep experts load-balanced."""
    tokens = x.reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(tokens @ params["router"]["kernel"], axis=-1)
    frac = jnp.mean(
        jax.nn.one_hot(jnp.argmax(probs, -1), num_experts, dtype=probs.dtype), axis=0
    )
    return _switch_aux(frac, probs, num_experts)
