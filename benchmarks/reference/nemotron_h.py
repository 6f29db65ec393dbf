"""Plain reference of the `nemotron_h` decoder: the yardstick's, and the one
the tier-1 tests import (`tests/test_hybrid.py`), so there is one text."""
# Plain reference of the `nemotron_h` hybrid decoder (NVIDIA Nemotron-3-Nano:
# `modeling_nemotron_h.py` of the source named in the configuration file), in
# float32 `jax.numpy` at `highest` matmul precision. No kernels, no cache, no
# batching: one sequence `tokens` [T] at a time, the state-space layers as the
# step-by-step recurrence, the experts as a loop over those held. It imports
# nothing of the program under test and makes its own weights from the seed.
#
# The layers are listed by `hybrid_override_pattern`, one character each: `M`
# Mamba-2, `E` mixture of experts, `*` attention. Every layer is
# `h <- h + mixer(RMSNorm(h))`; after the last, `RMSNorm_f(h) @ W_head`.
#
# A configuration may hold one chip's share of each layer: `n_routed_experts`
# then counts the experts held and `vocab_size` the rows of the vocabulary
# held, and `deployment` states the published router width
# (`n_routed_experts`) and the first expert held (`held_first`). The router
# always has its published width; what the experts not held would add is left
# out. `held=(first, count)` narrows the share further (tests: the shares add
# up to the whole layer).
#
# `chosen` (one layer) and `routes` (all of them) make the expert layers
# follow a routing they are given instead of their own top-k: the program's,
# as it exported it. A top-k of 128 near-equal scores is discontinuous: the
# rounding of a bfloat16 stream flips choices, each flip swaps one of six
# experts, and a comparison that lets the reference choose for itself reads
# that, not the arithmetic. Following the program's choices leaves the
# arithmetic; `route_regret` then says how far each of those choices lies
# from the reference's own, in the score that chooses.
#
# Departures from the published model, listed in the configuration file too:
# no positional encoding in attention (the published `nemotron_h` attention
# applies none; `rope_theta` and `partial_rotary_factor` are not read), and
# q, k, v and the Mamba input projection are stored input-major.

from __future__ import annotations

import json
import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp

HIGHEST = "highest"
INIT_STD = 0.02
F32_LEAVES = ("router.w", "router.bias", "dt_bias", "A_log", "D")  # kept in float32


def mamba_sizes(cfg: dict) -> dict:
    heads, dh = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    inner = heads * dh  # not `expand` x hidden
    bc = cfg["n_groups"] * cfg["ssm_state_size"]
    return {"heads": heads, "head_dim": dh, "inner": inner, "bc": bc,
            "conv": inner + 2 * bc, "proj": 2 * inner + 2 * bc + heads}


def router_width(cfg: dict) -> int:
    return cfg.get("deployment", {}).get("n_routed_experts", cfg["n_routed_experts"])


def held_experts(cfg: dict) -> tuple[int, int]:
    """(first, count) of the experts whose weights the configuration holds."""
    return cfg.get("deployment", {}).get("held_first", 0), cfg["n_routed_experts"]


def leaf_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Every parameter leaf by name, in a fixed order."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    m = mamba_sizes(cfg)
    qw = cfg["num_attention_heads"] * cfg["head_dim"]
    kw = cfg["num_key_value_heads"] * cfg["head_dim"]
    e, h = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    hs = cfg["moe_shared_expert_intermediate_size"]
    shapes = {"embed": (v, d)}
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        p = f"layers.{i}."
        shapes[p + "norm.w"] = (d,)
        if kind == "M":
            shapes.update({
                p + "in_proj.w": (d, m["proj"]),
                p + "conv.w": (cfg["conv_kernel"], m["conv"]), p + "conv.b": (m["conv"],),
                p + "dt_bias": (m["heads"],), p + "A_log": (m["heads"],),
                p + "D": (m["heads"],), p + "gate_norm.w": (m["inner"],),
                p + "out_proj.w": (m["inner"], d)})
        elif kind == "E":
            shapes.update({
                p + "router.w": (d, router_width(cfg)), p + "router.bias": (router_width(cfg),),
                p + "experts.up": (e, d, h), p + "experts.down": (e, h, d),
                p + "shared.up": (d, hs), p + "shared.down": (hs, d)})
        elif kind == "*":
            shapes.update({p + "q.w": (d, qw), p + "k.w": (d, kw),
                           p + "v.w": (d, kw), p + "o.w": (qw, d)})
        else:
            raise ValueError(f"unknown layer kind {kind!r} in the pattern")
    shapes.update({"norm_f.w": (d,), "lm_head.w": (d, v)})
    return shapes


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, also one wider than 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def init_weights(cfg: dict, key: jax.Array, dtype=jnp.float32) -> dict:
    """Random weights from ``key`` (``seed_key(seed)``), so that every term
    of the equations is exercised: matrices N(0, 0.02); norm gains and `D`
    1 + N(0, 0.02); the convolution U(+-1/sqrt(kernel)) and its bias
    N(0, 0.02); `A = exp(A_log)` U(1, 16); `softplus(dt_bias)` log-uniform in
    [`time_step_min`, `time_step_max`]; the router's selection bias N(0, 0.02)
    like a matrix (small against the scores' spread: a deployed bias balances
    the experts' load, and N(0, 0.1) left 46 % of the held experts idle).
    Drawn in float32 and rounded once to ``dtype``, except the router, `A_log`,
    `D` and `dt_bias`, which stay float32. Traceable: under ``jax.jit`` one
    program that does not change with the seed."""
    f32 = jnp.float32
    lo, hi = math.log(cfg["time_step_min"]), math.log(cfg["time_step_max"])
    bound = 1.0 / math.sqrt(cfg["conv_kernel"])

    def dt_bias(k, shape):
        dt = jnp.maximum(jnp.exp(jax.random.uniform(k, shape, f32, lo, hi)),
                         cfg["time_step_floor"])
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus(dt_bias) = dt

    near_one = lambda k, shape: 1.0 + INIT_STD * jax.random.normal(k, shape, f32)  # noqa: E731
    draw = {
        "A_log": lambda k, shape: jnp.log(jax.random.uniform(k, shape, f32, 1.0, 16.0)),
        "dt_bias": dt_bias,
        "conv.w": lambda k, shape: jax.random.uniform(k, shape, f32, -bound, bound),
        "D": near_one, "norm.w": near_one, "gate_norm.w": near_one, "norm_f.w": near_one,
    }
    matrix = lambda k, shape: INIT_STD * jax.random.normal(k, shape, f32)  # noqa: E731
    out = {}
    for i, (name, shape) in enumerate(leaf_shapes(cfg).items()):
        leaf = name.split(".", 2)[-1] if name.startswith("layers.") else name
        w = draw.get(leaf, matrix)(jax.random.fold_in(key, i), shape)
        out[name] = w.astype(f32 if leaf in F32_LEAVES else dtype)
    return out


# ------------------------------------------------------------------ forward


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def mamba_mixer(cfg: dict, lw: dict, u):
    """Mamba-2 on one sequence u [T, d]: the recurrence, a token at a time."""
    m, mm = mamba_sizes(cfg), partial(jnp.matmul, precision=HIGHEST)
    t = u.shape[0]
    nh, dh, g, n = m["heads"], m["head_dim"], cfg["n_groups"], cfg["ssm_state_size"]
    z, xbc, dt = jnp.split(mm(u, lw["in_proj.w"]), [m["inner"], m["inner"] + m["conv"]], axis=-1)
    # causal depthwise convolution: tap j of the kernel meets the input j - (K - 1) back
    k = cfg["conv_kernel"]
    padded = jnp.concatenate([jnp.zeros((k - 1, m["conv"]), xbc.dtype), xbc])
    xbc = sum(padded[j:j + t] * lw["conv.w"][j] for j in range(k)) + lw["conv.b"]
    xbc = jax.nn.silu(xbc)
    x, b, c = jnp.split(xbc, [m["inner"], m["inner"] + m["bc"]], axis=-1)
    x = x.reshape(t, nh, dh)
    b = jnp.repeat(b.reshape(t, g, n), nh // g, axis=1)  # a group's B and C serve its heads
    c = jnp.repeat(c.reshape(t, g, n), nh // g, axis=1)
    dt = jax.nn.softplus(dt + lw["dt_bias"])  # [T, H]
    a = -jnp.exp(lw["A_log"])

    def step(s, row):
        x_t, b_t, c_t, dt_t = row
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((nh, dh, n), jnp.float32), (x, b, c, dt))
    y = (y + lw["D"][:, None] * x).reshape(t, m["inner"])
    y = (y * jax.nn.silu(z)).reshape(t, g, m["inner"] // g)  # the gate before the norm
    y = rms_norm(y, lw["gate_norm.w"].reshape(g, -1), cfg["norm_eps"]).reshape(t, m["inner"])
    return mm(y, lw["out_proj.w"])


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


def moe_mixer(cfg: dict, lw: dict, u, held: tuple[int, int] | None = None,
              shared: bool = True, chosen=None):
    """Sigmoid-routed experts on u [T, d]: route over the router's whole
    width, add up what the experts ``held=(first, count)`` give (default:
    all the weights hold), plus the shared expert. ``chosen`` [T, k]: these
    experts instead of the top-k (their weights still from the scores)."""
    mm = partial(jnp.matmul, precision=HIGHEST)
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
    weights = cfg["routed_scaling_factor"] * weights
    out = mm(relu2(mm(u, lw["shared.up"])), lw["shared.down"]) if shared else 0.0 * u

    def add_expert(e, out):  # one held expert over every token, weighted (0 where not chosen)
        up = lw["experts.up"][e - stored_first].astype(jnp.float32)
        down = lw["experts.down"][e - stored_first].astype(jnp.float32)
        return out + weights[:, e][:, None] * mm(relu2(mm(u, up)), down)

    return jax.lax.fori_loop(first, first + count, add_expert, out)


def attention_mixer(cfg: dict, lw: dict, u):
    """Causal grouped-query attention on u [T, d], no positional encoding."""
    mm = partial(jnp.matmul, precision=HIGHEST)
    t, dh = u.shape[0], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q = mm(u, lw["q.w"]).reshape(t, nq, dh)
    k = jnp.repeat(mm(u, lw["k.w"]).reshape(t, nkv, dh), nq // nkv, axis=1)
    v = jnp.repeat(mm(u, lw["v.w"]).reshape(t, nkv, dh), nq // nkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / math.sqrt(dh)
    mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)
    return mm(a.reshape(t, nq * dh), lw["o.w"])


def layer(cfg: dict, kind: str, lw: dict, h, held=None, chosen=None):
    """One layer of kind ``kind`` on h [T, d]; ``lw`` holds its leaves. The
    experts' weights stay as stored and are raised to float32 one at a time.
    With ``chosen`` [T, k] (an `E` layer) -> (h, regret [T])."""
    lw = {k: (a if k.startswith("experts.") else a.astype(jnp.float32))
          for k, a in lw.items()}
    u = rms_norm(h, lw["norm.w"], cfg["norm_eps"])
    if kind == "M":
        return h + mamba_mixer(cfg, lw, u)
    if kind == "*":
        return h + attention_mixer(cfg, lw, u)
    out = h + moe_mixer(cfg, lw, u, held, chosen=chosen)
    return out if chosen is None else (out, route_regret(cfg, lw, u, chosen))


def layer_leaves(w: dict, i: int) -> dict:
    prefix = f"layers.{i}."
    return {k[len(prefix):]: a for k, a in w.items() if k.startswith(prefix)}


def head_logits(cfg: dict, w: dict, h):
    y = rms_norm(h, w["norm_f.w"].astype(jnp.float32), cfg["norm_eps"])
    return jnp.matmul(y, w["lm_head.w"].astype(jnp.float32), precision=HIGHEST)


def forward(cfg: dict, w: dict, tokens, held=None):
    """Logits [T, V] of one sequence ``tokens`` [T]."""
    h = w["embed"].astype(jnp.float32)[tokens]
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        h = layer(cfg, kind, layer_leaves(w, i), h, held)
    return head_logits(cfg, w, h)


def split_routes(cfg: dict, routes):
    """routes [T, n_E * k], the `E` layers side by side in order (what the
    program exports) -> {layer index: chosen [T, k]}."""
    k = cfg["num_experts_per_tok"]
    at = [i for i, kind in enumerate(cfg["hybrid_override_pattern"]) if kind == "E"]
    if routes.shape[1] != len(at) * k:
        raise ValueError(f"routes are {routes.shape[1]} wide, {len(at)} x {k} expected")
    return {i: routes[:, j * k:(j + 1) * k] for j, i in enumerate(at)}


# -------------------------------------------------------- serving reference


@lru_cache(maxsize=8)
def _serving_programs(cfg_json: str, n_rows: int):
    cfg = json.loads(cfg_json)
    return ({kind: jax.jit(partial(layer, cfg, kind)) for kind in "ME*"},
            jax.jit(lambda w, h, s: head_logits(
                cfg, w, jax.lax.dynamic_slice_in_dim(h, s, n_rows))))


def served_rows_logits(cfg: dict, w: dict, tokens, first_row, n_rows: int, routes=None):
    """(logits [n_rows, V], regret) at rows ``first_row``.. of one sequence
    ``tokens`` [T]: the rows whose next-token distributions produced the
    served tokens. With ``routes`` [T, n_E * k] (`split_routes`) the expert
    layers follow them and ``regret`` [n_E, T] is each layer's
    `route_regret`; without, they choose for themselves and it is None.
    The caller pads T at the end to one of a few lengths (every layer is
    causal: padding after a row cannot reach it). A layer at a time, one
    jitted program a layer kind, so that it fits and compiles once."""
    run_layer, run_head = _serving_programs(json.dumps(cfg, sort_keys=True), n_rows)
    chosen = split_routes(cfg, routes) if routes is not None else {}
    regret = []
    h = w["embed"][tokens].astype(jnp.float32)
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        if i in chosen:
            h, r = run_layer[kind](layer_leaves(w, i), h, None, chosen[i])
            regret.append(r)
        else:
            h = run_layer[kind](layer_leaves(w, i), h)
    logits = run_head({k: w[k] for k in ("norm_f.w", "lm_head.w")}, h, first_row)
    return logits, (jnp.stack(regret) if regret else None)
