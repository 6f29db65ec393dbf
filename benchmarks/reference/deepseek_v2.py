"""Plain reference of the `deepseek_v2` decoder: the yardstick's, and the one the
tier-1 tests import (`tests/test_deepseek_v2.py`), so there is one text."""
# Plain reference of the `deepseek_v2` decoder (DeepSeek-V2: `modeling_deepseek.py`
# of the source named in the configuration file; arXiv:2405.04434), in float32
# `jax.numpy` at `highest` matmul precision. No kernels, no cache, no absorbed
# form, no batching: one sequence `tokens` [T] at a time, every attention layer in
# the published NON-absorbed form as a [T, T] score matrix a head, the experts as a
# loop over those held. It imports nothing of the program under test and makes its
# own weights from the seed.
#
# A layer is `h <- h + MLA(RMSNorm(h))`, then `h <- h + FFN(RMSNorm(h))`; after the
# last, `RMSNorm(h) @ W_head` (untied, no bias anywhere).
#
# MLA, with `x` the normed stream: `c_q = RMSNorm(x W_DQ)` [q_lora_rank];
# `q = c_q W_UQ` -> H x (nope + rope), split `[q_nope | q_rope]`;
# `[c_kv | k_r] = x W_DKV` [kv_lora_rank + rope]; `c_kv = RMSNorm(c_kv)`;
# `k_r = RoPE(k_r)`, ONE rotary key shared by all heads; `[k_nope | v]_h = c_kv
# W_UKV` -> H x (nope + v); `q_rope = RoPE(q_rope)`; `k_h = [k_nope_h | k_r]`;
# causal `softmax(q_h . k_h * s) v_h` with `s = (nope + rope)^-0.5 * m^2`; the
# heads' values side by side times `W_O`.
#
# RoPE is YaRN's (`yarn_inv_freq`): pair i of the rotary part turns at
# `(1 - r_i) * theta^(-2i/dim) / factor + r_i * theta^(-2i/dim)`, `r_i = 1 -
# clip((i - lo) / (hi - lo), 0, 1)`, `lo` / `hi` the floored / ceiled pair indices
# that make `beta_fast` / `beta_slow` turns over the original context; cos and
# sin are scaled by `yarn_mscale(factor, mscale) / yarn_mscale(factor,
# mscale_all_dim)` and `m = yarn_mscale(factor, mscale_all_dim)`. Lanes are paired
# rotate-half over the rotary part (lane i with lane i + dim/2): the published
# code interleaves (2i with 2i + 1) and permutes q_rope's and k_r's lanes to this
# form before it rotates, which with seeded weights is a fixed permutation of
# W_UQ's and W_DKV's rotary columns.
#
# The first `first_k_dense_replace` layers' feed-forward is a SwiGLU of
# `intermediate_size`; every other's is `p = softmax(x W_r)` in float32 over the
# router's whole width, `group_limited_greedy`: the experts are `n_group` groups,
# a group's score is its largest `p`, the best `topk_group` groups stay, every
# other group's `p` is set to 0, the token's experts are the `num_experts_per_tok`
# best of what is left; their weights are those `p` as they are (`norm_topk_prob`
# false) times `routed_scaling_factor`; SwiGLU experts of
# `moe_intermediate_size`; plus one SwiGLU of `n_shared_experts x
# moe_intermediate_size` on every token.
#
# A configuration may hold one chip's share of each layer, as
# `reference/nemotron_h.py` sets out: `n_routed_experts` counts the experts held
# and `vocab_size` the rows held; `deployment` states the router's published
# width (`n_routed_experts`) and the first expert held (`held_first`).
# `held=(first, count)` narrows the share further (tests: the shares add up).
# `chosen` / `routes` make the expert layers follow a routing they are given (the
# program's, as it exported it) and `route_regret` says how far each of those
# choices lies from the reference's own; `nemotron_h.py` says why.
#
# Departures and assumptions are listed in the configuration file.

from __future__ import annotations

import json
import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp

HIGHEST = "highest"
INIT_STD = 0.02
F32_LEAVES = ("router.w",)  # kept in float32
ATTENTION_LEAVES = ("attn_norm.w", "q_a.w", "q_a_norm.w", "q_b.w", "kv_a.w", "kv_a_norm.w",
                    "kv_b.w", "o.w")


def router_width(cfg: dict) -> int:
    return cfg.get("deployment", {}).get("n_routed_experts", cfg["n_routed_experts"])


def held_experts(cfg: dict) -> tuple[int, int]:
    """(first, count) of the experts whose weights the configuration holds."""
    return cfg.get("deployment", {}).get("held_first", 0), cfg["n_routed_experts"]


def is_moe(cfg: dict, i: int) -> bool:
    return i >= cfg["first_k_dense_replace"] and i % cfg["moe_layer_freq"] == 0


def leaf_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Every parameter leaf by name, in a fixed order."""
    d, v, nh = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    qr, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope_d, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    e, h = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    shapes = {"embed": (v, d)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"layers.{i}."
        shapes.update({
            p + "attn_norm.w": (d,), p + "q_a.w": (d, qr), p + "q_a_norm.w": (qr,),
            p + "q_b.w": (qr, nh * (nope + rope_d)), p + "kv_a.w": (d, r + rope_d),
            p + "kv_a_norm.w": (r,), p + "kv_b.w": (r, nh * (nope + dv)),
            p + "o.w": (nh * dv, d), p + "ffn_norm.w": (d,)})
        if is_moe(cfg, i):
            s = cfg["n_shared_experts"] * h
            shapes.update({
                p + "router.w": (d, router_width(cfg)),
                p + "experts.gate": (e, d, h), p + "experts.up": (e, d, h),
                p + "experts.down": (e, h, d),
                p + "shared.gate": (d, s), p + "shared.up": (d, s), p + "shared.down": (s, d)})
        else:
            f = cfg["intermediate_size"]
            shapes.update({p + "mlp.gate": (d, f), p + "mlp.up": (d, f), p + "mlp.down": (f, d)})
    shapes.update({"norm_f.w": (d,), "lm_head.w": (d, v)})
    return shapes


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, also one wider than 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def init_weights(cfg: dict, key: jax.Array, dtype=jnp.float32) -> dict:
    """Random weights from ``key`` (``seed_key(seed)``): matrices N(0, 0.02), norm
    gains 1 + N(0, 0.02). Drawn in float32 and rounded once to ``dtype``, except
    the router, which stays float32. Traceable: under ``jax.jit`` one program for
    all seeds."""
    f32 = jnp.float32
    out = {}
    for i, (name, shape) in enumerate(leaf_shapes(cfg).items()):
        leaf = name.split(".", 2)[-1] if name.startswith("layers.") else name
        w = INIT_STD * jax.random.normal(jax.random.fold_in(key, i), shape, f32)
        if leaf.endswith("norm.w") or leaf == "norm_f.w":
            w = 1.0 + w
        out[name] = w.astype(f32 if leaf in F32_LEAVES else dtype)
    return out


# ------------------------------------------------------------------ positions


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, base: float, factor: float, original: int, beta_fast: float,
                  beta_slow: float):
    """[dim / 2] float32: the turns a position of each lane pair of a rotary part
    ``dim`` wide (the header)."""
    def pair_of(turns: float) -> float:  # the pair that makes ``turns`` over ``original``
        return dim * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(base))

    lo = max(math.floor(pair_of(beta_fast)), 0)
    hi = min(math.ceil(pair_of(beta_slow)), dim - 1)
    pairs = jnp.arange(dim // 2, dtype=jnp.float32)
    plain = base ** (-2.0 * pairs / dim)
    keep = 1.0 - jnp.clip((pairs - lo) / ((hi - lo) or 0.001), 0.0, 1.0)
    return (1.0 - keep) * plain / factor + keep * plain


def rope_table(cfg: dict):
    """(inv_freq [rope / 2], the factor on cos and sin, m) of the configuration."""
    dim, theta, rs = cfg["qk_rope_head_dim"], float(cfg["rope_theta"]), cfg.get("rope_scaling")
    if not rs:
        return theta ** (-2.0 * jnp.arange(dim // 2, dtype=jnp.float32) / dim), 1.0, 1.0
    inv = yarn_inv_freq(dim, theta, rs["factor"], rs["original_max_position_embeddings"],
                        rs["beta_fast"], rs["beta_slow"])
    m = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    return inv, yarn_mscale(rs["factor"], rs["mscale"]) / m, m


def softmax_scale(cfg: dict) -> float:
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * rope_table(cfg)[2] ** 2


def rope(x, inv_freq, factor: float):
    """Rotate-half RoPE over the whole of x [T, ..., D] at positions 0 .. T - 1."""
    half = x.shape[-1] // 2
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq  # [T, half]
    angles = angles.reshape(x.shape[0], *([1] * (x.ndim - 2)), half)
    cos, sin = factor * jnp.cos(angles), factor * jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


# ------------------------------------------------------------------ forward


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def latent_attention(cfg: dict, lw: dict, u):
    """One MLA layer on u [T, d], non-absorbed. A query head at a time, so that a
    [T, T] score matrix is all that is held."""
    mm = partial(jnp.matmul, precision=HIGHEST)
    t, nh, eps = u.shape[0], cfg["num_attention_heads"], cfg["rms_norm_eps"]
    r, nope, dv = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    inv_freq, factor, _ = rope_table(cfg)
    q = mm(rms_norm(mm(u, lw["q_a.w"]), lw["q_a_norm.w"], eps), lw["q_b.w"]).reshape(t, nh, -1)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], inv_freq, factor)
    down = mm(u, lw["kv_a.w"])
    c_kv, k_r = rms_norm(down[:, :r], lw["kv_a_norm.w"], eps), rope(down[:, r:], inv_freq, factor)
    kv = mm(c_kv, lw["kv_b.w"]).reshape(t, nh, nope + dv)
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    scale = softmax_scale(cfg)

    def head(h):
        s = (mm(q_nope[:, h], kv[:, h, :nope].T) + mm(q_rope[:, h], k_r.T)) * scale
        return mm(jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1), kv[:, h, nope:])

    a = jax.lax.map(head, jnp.arange(nh))  # [H, T, dv]
    return mm(a.transpose(1, 0, 2).reshape(t, nh * dv), lw["o.w"])


def swiglu(u, gate, up, down):
    mm = partial(jnp.matmul, precision=HIGHEST)
    return mm(jax.nn.silu(mm(u, gate)) * mm(u, up), down)


def route_scores(cfg: dict, lw: dict, u):
    """(p [T, E], the scores that choose): softmax over the router's whole width,
    and the same with every group but the token's best `topk_group` set to 0."""
    p = jax.nn.softmax(jnp.matmul(u, lw["router.w"], precision=HIGHEST), axis=-1)
    n_group, keep = cfg["n_group"], cfg["topk_group"]
    if n_group <= 1:
        return p, p
    groups = p.reshape(p.shape[0], n_group, -1)
    best = jnp.argsort(-jnp.max(groups, axis=-1), axis=-1)[:, :keep]  # ties: the first
    kept = jnp.zeros(groups.shape[:2], bool).at[jnp.arange(p.shape[0])[:, None], best].set(True)
    return p, jnp.where(kept[:, :, None], groups, 0.0).reshape(p.shape)


def route_regret(cfg: dict, lw: dict, u, chosen):
    """[T]: how far the worst of a token's ``chosen`` [T, k] experts lies under the
    reference's own k-th best, in the score that chooses (an expert of a group
    that was not kept scores 0); 0 where the choices are the reference's."""
    _, select = route_scores(cfg, lw, u)
    kth = -jnp.sort(-select, axis=-1)[:, cfg["num_experts_per_tok"] - 1]
    worst = jnp.min(jnp.take_along_axis(select, chosen, axis=-1), axis=-1)
    return jnp.maximum(kth - worst, 0.0)


def routed_experts(cfg: dict, lw: dict, u, held: tuple[int, int] | None = None, chosen=None):
    """The routed experts' part of the layer on u [T, d]: route over the router's
    whole width, add up what the experts ``held=(first, count)`` give (default:
    all the weights hold). ``chosen`` [T, k]: these experts instead of the
    reference's own (their weights still from the scores)."""
    stored_first, stored = held_experts(cfg)
    first, count = held or (stored_first, stored)
    if first < stored_first or first + count > stored_first + stored:
        raise ValueError(f"experts {first}..{first + count - 1} are not in the weights")
    p, select = route_scores(cfg, lw, u)
    top = jnp.argsort(-select, axis=-1)[:, :cfg["num_experts_per_tok"]] \
        if chosen is None else chosen
    weights = p * jnp.zeros_like(p).at[jnp.arange(u.shape[0])[:, None], top].set(1.0)
    if cfg["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    weights = cfg["routed_scaling_factor"] * weights

    def add_expert(e, out):  # one held expert over every token, weighted (0 where not chosen)
        gate, up, down = (lw[f"experts.{n}"][e - stored_first].astype(jnp.float32)
                          for n in ("gate", "up", "down"))
        return out + weights[:, e][:, None] * swiglu(u, gate, up, down)

    return jax.lax.fori_loop(first, first + count, add_expert, 0.0 * u)


def shared_expert(lw: dict, u):
    return swiglu(u, lw["shared.gate"], lw["shared.up"], lw["shared.down"])


def _f32(lw: dict) -> dict:
    """The routed experts' weights stay as stored and are raised one at a time."""
    return {k: (a if k.startswith("experts.") else a.astype(jnp.float32))
            for k, a in lw.items()}


def attention_block(cfg: dict, lw: dict, h):
    lw = _f32(lw)
    return h + latent_attention(cfg, lw, rms_norm(h, lw["attn_norm.w"], cfg["rms_norm_eps"]))


def ffn_block(cfg: dict, moe: bool, lw: dict, h, held=None, chosen=None):
    """A layer's feed-forward half on h [T, d]. With ``chosen`` [T, k] (an expert
    layer) -> (h, regret [T])."""
    lw = _f32(lw)
    u = rms_norm(h, lw["ffn_norm.w"], cfg["rms_norm_eps"])
    if not moe:
        return h + swiglu(u, lw["mlp.gate"], lw["mlp.up"], lw["mlp.down"])
    out = h + routed_experts(cfg, lw, u, held, chosen) + shared_expert(lw, u)
    return out if chosen is None else (out, route_regret(cfg, lw, u, chosen))


def layer_leaves(w: dict, i: int) -> dict:
    prefix = f"layers.{i}."
    return {k[len(prefix):]: a for k, a in w.items() if k.startswith(prefix)}


def head_logits(cfg: dict, w: dict, h):
    y = rms_norm(h, w["norm_f.w"].astype(jnp.float32), cfg["rms_norm_eps"])
    return jnp.matmul(y, w["lm_head.w"].astype(jnp.float32), precision=HIGHEST)


def forward(cfg: dict, w: dict, tokens, held=None):
    """Logits [T, V] of one sequence ``tokens`` [T]."""
    h = w["embed"].astype(jnp.float32)[tokens]
    for i in range(cfg["num_hidden_layers"]):
        lw = layer_leaves(w, i)
        h = ffn_block(cfg, is_moe(cfg, i), lw, attention_block(cfg, lw, h), held)
    return head_logits(cfg, w, h)


def split_routes(cfg: dict, routes):
    """routes [T, n_E * k], the expert layers side by side in order (what the
    program exports) -> {layer index: chosen [T, k]}."""
    k = cfg["num_experts_per_tok"]
    at = [i for i in range(cfg["num_hidden_layers"]) if is_moe(cfg, i)]
    if routes.shape[1] != len(at) * k:
        raise ValueError(f"routes are {routes.shape[1]} wide, {len(at)} x {k} expected")
    return {i: routes[:, j * k:(j + 1) * k] for j, i in enumerate(at)}


# -------------------------------------------------------- serving reference


@lru_cache(maxsize=8)
def _serving_programs(cfg_json: str, n_rows: int):
    """One jitted program a kind of half-layer, and the head's."""
    cfg = json.loads(cfg_json)
    return (jax.jit(partial(attention_block, cfg)),
            {moe: jax.jit(partial(ffn_block, cfg, moe)) for moe in (False, True)},
            jax.jit(lambda w, h, s: head_logits(
                cfg, w, jax.lax.dynamic_slice_in_dim(h, s, n_rows))))


def served_rows_logits(cfg: dict, w: dict, tokens, first_row, n_rows: int, routes=None):
    """(logits [n_rows, V], regret) at rows ``first_row``.. of one sequence
    ``tokens`` [T]: the rows whose next-token distributions produced the served
    tokens. With ``routes`` [T, n_E * k] (`split_routes`) the expert layers follow
    them and ``regret`` [n_E, T] is each layer's `route_regret`; without, they
    choose for themselves and it is None. The caller pads T at the end to one of a
    few lengths (every layer is causal: padding after a row cannot reach it). Half
    a layer at a time, one jitted program a kind, so that it fits and compiles
    once."""
    attn, ffn, run_head = _serving_programs(json.dumps(cfg, sort_keys=True), n_rows)
    chosen = split_routes(cfg, routes) if routes is not None else {}
    regret = []
    h = w["embed"][tokens].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        lw = layer_leaves(w, i)  # each half its own leaves: a program a kind, not a layer
        h = attn({k: a for k, a in lw.items() if k in ATTENTION_LEAVES}, h)
        mine = {k: a for k, a in lw.items() if k not in ATTENTION_LEAVES}
        if i in chosen:
            h, r = ffn[True](mine, h, None, chosen[i])
            regret.append(r)
        else:
            h = ffn[is_moe(cfg, i)](mine, h)
    logits = run_head({k: w[k] for k in ("norm_f.w", "lm_head.w")}, h, first_row)
    return logits, (jnp.stack(regret) if regret else None)
