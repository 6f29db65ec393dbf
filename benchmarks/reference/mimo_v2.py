"""Plain reference of the `mimo_v2` decoder: the yardstick's, and the one the
tier-1 tests import (`tests/test_mimo.py`), so there is one text."""
# Plain reference of the `mimo_v2` decoder (Xiaomi MiMo-V2-Flash / MiMo-V2.5:
# `modeling_mimo_v2.py` of the source named in the configuration file), in
# float32 `jax.numpy` at `highest` matmul precision. No kernels, no cache, no
# ring, no batching: one sequence `tokens` [T] at a time, every attention layer
# as a [T, T] score matrix a head under its mask, the experts as a loop over
# those held. It imports nothing of the program under test and makes its own
# weights from the seed.
#
# A layer is `h <- h + Attn(RMSNorm(h))`, then `h <- h + FFN(RMSNorm(h))`;
# after the last, `RMSNorm(h) @ W_head` (untied, no bias anywhere).
# `hybrid_layer_pattern[i]` says which attention layer i has: 0 full (causal,
# `num_key_value_heads` K/V heads, RoPE base `rope_theta`), 1 sliding window
# (`swa_num_key_value_heads`, `swa_rope_theta`, query i sees keys
# i - window + 1 .. i, and a learned scalar a head joins the softmax's
# denominator: the sink takes weight and gives no value). In both the q/k head
# is `head_dim` wide and the v head `v_head_dim`, RoPE turns only the first
# `rotary_dim(cfg)` of the q/k head (rotate-half over that slice), and
# `v = attention_value_scale * (u @ Wv)`. `moe_layer_freq[i]` says which
# feed-forward: 0 a dense SwiGLU of `intermediate_size`, 1 sigmoid-routed
# SwiGLU experts: `s = sigmoid(u @ Wr)`, the token's experts are the top-k of
# `s + bias`, their weights `s_e / sum of the chosen s`
# (`routed_scaling_factor` null = 1, `n_group` 1: no group limit), no shared
# expert.
#
# A configuration may hold one chip's share of each layer, as
# `reference/nemotron_h.py` sets out: `n_routed_experts` counts the experts held
# and `vocab_size` the rows held; `deployment` states the router's published
# width (`n_routed_experts`) and the first expert held (`held_first`).
# `held=(first, count)` narrows the share further (tests: the shares add up).
# `chosen` / `routes` make the expert layers follow a routing they are given
# (the program's, as it exported it) and `route_regret` says how far each of
# those choices lies from the reference's own; `nemotron_h.py` says why.
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
SINK_MEAN = 4.0
F32_LEAVES = ("router.w", "router.bias", "sink")  # kept in float32
ATTENTION_LEAVES = ("attn_norm.w", "q.w", "k.w", "v.w", "o.w", "sink")


def rotary_dim(cfg: dict) -> int:
    """`partial_rotary_factor` of the head, rounded down to an even width."""
    return int(cfg["partial_rotary_factor"] * cfg["head_dim"]) // 2 * 2


def router_width(cfg: dict) -> int:
    return cfg.get("deployment", {}).get("n_routed_experts", cfg["n_routed_experts"])


def held_experts(cfg: dict) -> tuple[int, int]:
    """(first, count) of the experts whose weights the configuration holds."""
    return cfg.get("deployment", {}).get("held_first", 0), cfg["n_routed_experts"]


def attention_kind(cfg: dict, i: int) -> dict:
    """What layer i's attention is made of."""
    window = bool(cfg["hybrid_layer_pattern"][i])
    return {
        "window": cfg["sliding_window"] if window else None,
        "kv_heads": cfg["swa_num_key_value_heads" if window else "num_key_value_heads"],
        "theta": float(cfg["swa_rope_theta" if window else "rope_theta"]),
        "sink": bool(cfg["add_swa_attention_sink_bias" if window
                         else "add_full_attention_sink_bias"]),
    }


def leaf_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Every parameter leaf by name, in a fixed order."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    nq, dk, dv = cfg["num_attention_heads"], cfg["head_dim"], cfg["v_head_dim"]
    e, h = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    if not (len(cfg["hybrid_layer_pattern"]) == len(cfg["moe_layer_freq"])
            == cfg["num_hidden_layers"]):
        raise ValueError("hybrid_layer_pattern, moe_layer_freq and num_hidden_layers disagree")
    shapes = {"embed": (v, d)}
    for i in range(cfg["num_hidden_layers"]):
        p, kind = f"layers.{i}.", attention_kind(cfg, i)
        shapes.update({
            p + "attn_norm.w": (d,), p + "q.w": (d, nq * dk),
            p + "k.w": (d, kind["kv_heads"] * dk), p + "v.w": (d, kind["kv_heads"] * dv),
            p + "o.w": (nq * dv, d)})
        if kind["sink"]:
            shapes[p + "sink"] = (nq,)
        shapes[p + "ffn_norm.w"] = (d,)
        if cfg["moe_layer_freq"][i]:
            shapes.update({
                p + "router.w": (d, router_width(cfg)), p + "router.bias": (router_width(cfg),),
                p + "experts.gate": (e, d, h), p + "experts.up": (e, d, h),
                p + "experts.down": (e, h, d)})
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
    """Random weights from ``key`` (``seed_key(seed)``), so that every term of
    the equations is exercised: matrices N(0, 0.02); norm gains 1 + N(0, 0.02);
    the router's selection bias N(0, 0.02) (small against the scores' spread,
    as `nemotron_h.py`); the sinks N(SINK_MEAN, 1): a logit among the largest
    of a row's 128 (N(0, 1) would weigh 0.3 % of a window's softmax at these
    scores' spread, less than bfloat16 rounds away, and no comparison could
    tell a sink from none; PERF.md §4). Drawn in float32 and rounded once to
    ``dtype``, except the router and the sinks, which stay float32. Traceable:
    under ``jax.jit`` one program for all seeds."""
    f32 = jnp.float32
    draw = {
        "norm.w": lambda k, shape: 1.0 + INIT_STD * jax.random.normal(k, shape, f32),
        "sink": lambda k, shape: SINK_MEAN + jax.random.normal(k, shape, f32),
    }
    matrix = lambda k, shape: INIT_STD * jax.random.normal(k, shape, f32)  # noqa: E731
    out = {}
    for i, (name, shape) in enumerate(leaf_shapes(cfg).items()):
        leaf = name.split(".", 2)[-1] if name.startswith("layers.") else name
        family = "norm.w" if leaf.endswith("norm.w") or leaf == "norm_f.w" else leaf
        w = draw.get(family, matrix)(jax.random.fold_in(key, i), shape)
        out[name] = w.astype(f32 if leaf in F32_LEAVES else dtype)
    return out


# ------------------------------------------------------------------ forward


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def rope(x, theta: float, width: int):
    """Rotate-half RoPE on the first ``width`` of x [T, H, D] at positions
    0 .. T - 1; the rest of the head passes through."""
    half = width // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs  # [T, half]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:width]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., width:]], axis=-1)


def attention_mixer(cfg: dict, kind: dict, lw: dict, u):
    """One attention layer on u [T, d]; ``kind`` from `attention_kind`. A
    query head at a time, so that a [T, T] score matrix is all that is held."""
    mm = partial(jnp.matmul, precision=HIGHEST)
    t, dk, dv = u.shape[0], cfg["head_dim"], cfg["v_head_dim"]
    nq, nkv = cfg["num_attention_heads"], kind["kv_heads"]
    width = rotary_dim(cfg)
    q = rope(mm(u, lw["q.w"]).reshape(t, nq, dk), kind["theta"], width)
    k = rope(mm(u, lw["k.w"]).reshape(t, nkv, dk), kind["theta"], width)
    v = cfg["attention_value_scale"] * mm(u, lw["v.w"]).reshape(t, nkv, dv)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = j <= i
    if kind["window"] is not None:  # the window counts the query's own position
        seen &= j > i - kind["window"]
    sinks = lw["sink"] if kind["sink"] else jnp.full((nq,), -jnp.inf)

    def head(args):
        h, sink = args
        kv = h // (nq // nkv)
        s = mm(q[:, h], k[:, kv].T) / math.sqrt(dk)
        s = jnp.where(seen, s, -jnp.inf)
        m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), sink)
        p = jnp.exp(s - m)
        return mm(p / (jnp.sum(p, axis=-1, keepdims=True) + jnp.exp(sink - m)), v[:, kv])

    a = jax.lax.map(head, (jnp.arange(nq), sinks))  # [H, T, dv]
    return mm(a.transpose(1, 0, 2).reshape(t, nq * dv), lw["o.w"])


def swiglu(u, gate, up, down):
    mm = partial(jnp.matmul, precision=HIGHEST)
    return mm(jax.nn.silu(mm(u, gate)) * mm(u, up), down)


def route_scores(lw: dict, u):
    """(s [T, E], s + bias): the weights' scores and the ones that choose."""
    s = jax.nn.sigmoid(jnp.matmul(u, lw["router.w"], precision=HIGHEST))
    return s, s + lw["router.bias"]


def route_regret(cfg: dict, lw: dict, u, chosen):
    """[T]: how far the worst of a token's ``chosen`` [T, k] experts lies
    under the reference's own k-th best, in the score that chooses; 0 where
    the choices are the reference's."""
    _, select = route_scores(lw, u)
    kth = -jnp.sort(-select, axis=-1)[:, cfg["num_experts_per_tok"] - 1]
    worst = jnp.min(jnp.take_along_axis(select, chosen, axis=-1), axis=-1)
    return jnp.maximum(kth - worst, 0.0)


def moe_mixer(cfg: dict, lw: dict, u, held: tuple[int, int] | None = None, chosen=None):
    """Sigmoid-routed SwiGLU experts on u [T, d]: route over the router's
    whole width, add up what the experts ``held=(first, count)`` give
    (default: all the weights hold). ``chosen`` [T, k]: these experts instead
    of the top-k (their weights still from the scores)."""
    stored_first, stored = held_experts(cfg)
    first, count = held or (stored_first, stored)
    if first < stored_first or first + count > stored_first + stored:
        raise ValueError(f"experts {first}..{first + count - 1} are not in the weights")
    s, select = route_scores(lw, u)  # [T, E]
    top = jnp.argsort(-select, axis=-1)[:, :cfg["num_experts_per_tok"]] \
        if chosen is None else chosen
    weights = s * jnp.zeros_like(s).at[jnp.arange(u.shape[0])[:, None], top].set(1.0)
    if cfg["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    weights = (cfg.get("routed_scaling_factor") or 1.0) * weights

    def add_expert(e, out):  # one held expert over every token, weighted (0 where not chosen)
        gate, up, down = (lw[f"experts.{n}"][e - stored_first].astype(jnp.float32)
                          for n in ("gate", "up", "down"))
        return out + weights[:, e][:, None] * swiglu(u, gate, up, down)

    return jax.lax.fori_loop(first, first + count, add_expert, 0.0 * u)


def _f32(lw: dict) -> dict:
    """The experts' weights stay as stored and are raised one at a time."""
    return {k: (a if k.startswith("experts.") else a.astype(jnp.float32))
            for k, a in lw.items()}


def attention_block(cfg: dict, i: int, lw: dict, h):
    lw = _f32(lw)
    u = rms_norm(h, lw["attn_norm.w"], cfg["layernorm_epsilon"])
    return h + attention_mixer(cfg, attention_kind(cfg, i), lw, u)


def ffn_block(cfg: dict, i: int, lw: dict, h, held=None, chosen=None):
    """Layer i's feed-forward half on h [T, d]. With ``chosen`` [T, k] (an
    expert layer) -> (h, regret [T])."""
    lw = _f32(lw)
    u = rms_norm(h, lw["ffn_norm.w"], cfg["layernorm_epsilon"])
    if not cfg["moe_layer_freq"][i]:
        return h + swiglu(u, lw["mlp.gate"], lw["mlp.up"], lw["mlp.down"])
    out = h + moe_mixer(cfg, lw, u, held, chosen)
    return out if chosen is None else (out, route_regret(cfg, lw, u, chosen))


def layer_leaves(w: dict, i: int) -> dict:
    prefix = f"layers.{i}."
    return {k[len(prefix):]: a for k, a in w.items() if k.startswith(prefix)}


def head_logits(cfg: dict, w: dict, h):
    y = rms_norm(h, w["norm_f.w"].astype(jnp.float32), cfg["layernorm_epsilon"])
    return jnp.matmul(y, w["lm_head.w"].astype(jnp.float32), precision=HIGHEST)


def forward(cfg: dict, w: dict, tokens, held=None):
    """Logits [T, V] of one sequence ``tokens`` [T]."""
    h = w["embed"].astype(jnp.float32)[tokens]
    for i in range(cfg["num_hidden_layers"]):
        lw = layer_leaves(w, i)
        h = ffn_block(cfg, i, lw, attention_block(cfg, i, lw, h), held)
    return head_logits(cfg, w, h)


def split_routes(cfg: dict, routes):
    """routes [T, n_E * k], the expert layers side by side in order (what the
    program exports) -> {layer index: chosen [T, k]}."""
    k = cfg["num_experts_per_tok"]
    at = [i for i, moe in enumerate(cfg["moe_layer_freq"]) if moe]
    if routes.shape[1] != len(at) * k:
        raise ValueError(f"routes are {routes.shape[1]} wide, {len(at)} x {k} expected")
    return {i: routes[:, j * k:(j + 1) * k] for j, i in enumerate(at)}


# -------------------------------------------------------- serving reference


@lru_cache(maxsize=8)
def _serving_programs(cfg_json: str, n_rows: int):
    """One jitted program a kind of half-layer (by what `attention_kind` and
    `moe_layer_freq` say of it, not by its index), and the head's."""
    cfg = json.loads(cfg_json)
    attn, ffn = {}, {}
    for i in range(cfg["num_hidden_layers"]):
        attn.setdefault(cfg["hybrid_layer_pattern"][i], jax.jit(partial(attention_block, cfg, i)))
        ffn.setdefault(cfg["moe_layer_freq"][i], jax.jit(partial(ffn_block, cfg, i)))
    return attn, ffn, jax.jit(lambda w, h, s: head_logits(
        cfg, w, jax.lax.dynamic_slice_in_dim(h, s, n_rows)))


def served_rows_logits(cfg: dict, w: dict, tokens, first_row, n_rows: int, routes=None):
    """(logits [n_rows, V], regret) at rows ``first_row``.. of one sequence
    ``tokens`` [T]: the rows whose next-token distributions produced the
    served tokens. With ``routes`` [T, n_E * k] (`split_routes`) the expert
    layers follow them and ``regret`` [n_E, T] is each layer's
    `route_regret`; without, they choose for themselves and it is None. The
    caller pads T at the end to one of a few lengths (every layer is causal:
    padding after a row cannot reach it). Half a layer at a time, one jitted
    program a kind, so that it fits and compiles once."""
    attn, ffn, run_head = _serving_programs(json.dumps(cfg, sort_keys=True), n_rows)
    chosen = split_routes(cfg, routes) if routes is not None else {}
    regret = []
    h = w["embed"][tokens].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        lw = layer_leaves(w, i)  # each half its own leaves: a program a kind, not a layer
        mine = {k: a for k, a in lw.items() if k in ATTENTION_LEAVES}
        h = attn[cfg["hybrid_layer_pattern"][i]](mine, h)
        mine = {k: a for k, a in lw.items() if k not in ATTENTION_LEAVES}
        if i in chosen:
            h, r = ffn[cfg["moe_layer_freq"][i]](mine, h, None, chosen[i])
            regret.append(r)
        else:
            h = ffn[cfg["moe_layer_freq"][i]](mine, h)
    logits = run_head({k: w[k] for k in ("norm_f.w", "lm_head.w")}, h, first_row)
    return logits, (jnp.stack(regret) if regret else None)
