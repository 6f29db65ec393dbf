"""Plain reference of the `phi4flash` decoder: the yardstick's, and the one the
tier-1 tests import (`tests/test_phi4flash.py`), so there is one text."""
# Plain reference of the `phi4flash` decoder (Microsoft Phi-4-mini-flash-reasoning,
# the "SambaY" family of arXiv:2507.06607: `modeling_phi4flash.py` of the source
# named in the configuration file), in float32 `jax.numpy` at `highest` matmul
# precision. No kernels, no cache, no ring, no batching: one sequence `tokens` [T]
# at a time, the state-space recurrence as a token-by-token `lax.scan`, every
# attention layer as explicit masked [T, T] softmaxes, two a differential head. It
# imports nothing of the program under test and makes its own weights from the seed.
#
# A layer is `h <- h + Mix(LN1(h)); h <- h + MLP(LN2(h))` with LayerNorm (gain and
# bias) and `MLP(u) = (silu(u Wg) * (u Wu)) Wd`; after the last,
# `logits = LN_f(h) E^T` with `E` the embedding (tied, no bias). No positional
# encoding anywhere. `layer_kind(cfg, l)` says which mixer layer `l` of `n` has
# (`mb_per_layer` 2: even layers hold a Mamba-family mixer, odd ones attention; the
# second half is the cross-decoder):
#
#   l even, l <= n/2   "S"  Mamba-1 (E = expand * d channels, state N, dt rank R,
#                           causal depthwise conv K); layer n/2 also publishes its
#                           scan output `m = y` (with the D term, before the gate)
#   l odd,  l <  n/2   "W"  differential attention, window `sliding_window` (a query
#                           sees that many positions, its own among them)
#   l = n/2 + 1        "F"  differential attention, full; its K and V are the only
#                           ones the cross layers read
#   l even, l >  n/2   "G"  gated memory unit: (m * silu(u W1)) W2, m at the same token
#   l odd,  l > n/2+1  "X"  differential cross attention: a query projection only,
#                           over layer n/2 + 1's K and V
#
# S:  [x | z] = u W_in;  x <- silu(conv_K(x) + b_conv);  [delta | B | C] = x W_x;
#     dt = softplus(delta W_dt + b_dt) [T, E];  A = -exp(A_log) [E, N];
#     S_t = exp(dt_t * A) * S_{t-1} + (dt_t * x_t) (x) B_t;  y_t = S_t C_t + D * x_t;
#     out = (y * silu(z)) W_out.
# W, F, X (H query heads, H/2 K/V heads, head D = d / H, scale D^-1/2):
#     differential head j of H/2 pairs query heads 2j (q1) and 2j + 1 (q2) and reads
#     K/V pair p = j // 2: k1 = K head 2p, k2 = K head 2p + 1, V_p = [v head 2p | v
#     head 2p + 1] (2D wide). A_i = softmax(q_i k_i^T * scale + mask) V_p;
#     lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(l);
#     o_j = RMSNorm_2D(A1 - lambda A2; gain g) * (1 - lambda_init(l));
#     out = concat_j(o_j) W_o + b_o;  lambda_init(l) = 0.8 - 0.6 exp(-0.3 l).
#
# Departures (storage only): q, k, v and gate, up are separate matrices where the
# published checkpoint fuses them; the recurrence's state is held as its transpose
# [N, E], and every token's decay exp(dt_t * A) is made before the loop over the
# tokens, as the published `selective_scan_ref` makes it. Assumptions are listed in the configuration
# file under `assumed`, where the reference reads the sizes `config.json` omits.

from __future__ import annotations

import json
import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp

HIGHEST = "highest"
LAMBDA_STD = 0.1
F32_LEAVES = ("A_log", "D", "dt_proj.b", "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")
MLP_LEAVES = ("norm2.w", "norm2.b", "mlp.gate", "mlp.up", "mlp.down")


def sizes(cfg: dict) -> dict:
    """The widths the equations use; those `config.json` omits from `assumed`."""
    a = cfg["assumed"]
    d = cfg["hidden_size"]
    return {"d": d, "heads": cfg["num_attention_heads"], "kv_heads": cfg["num_key_value_heads"],
            "head": d // cfg["num_attention_heads"], "inner": a["mamba_expand"] * d,
            "state": a["mamba_d_state"], "conv": a["mamba_d_conv"], "dt_rank": a["mamba_dt_rank"]}


def layer_kind(cfg: dict, i: int) -> str:
    n = cfg["num_hidden_layers"]
    if cfg["mb_per_layer"] != 2 or n % 4:
        raise ValueError("mb_per_layer 2 and a multiple of four layers are what is written here")
    if i % 2 == 0:
        return "S" if i <= n // 2 else "G"
    return "W" if i < n // 2 else ("F" if i == n // 2 + 1 else "X")


def lambda_init(i: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def leaf_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Every parameter leaf by name, in a fixed order."""
    z = sizes(cfg)
    d, e, n, r = z["d"], z["inner"], z["state"], z["dt_rank"]
    if z["kv_heads"] * 2 != z["heads"] or z["heads"] % 4:
        raise ValueError("differential heads pair two query heads over a pair of K/V heads")
    kv, f = z["kv_heads"] * z["head"], cfg["intermediate_size"]
    shapes = {"embed": (cfg["vocab_size"], d)}
    for i in range(cfg["num_hidden_layers"]):
        p, kind = f"layers.{i}.", layer_kind(cfg, i)
        shapes.update({p + "norm1.w": (d,), p + "norm1.b": (d,)})
        if kind == "S":
            shapes.update({
                p + "in_proj.w": (d, 2 * e), p + "conv.w": (z["conv"], e), p + "conv.b": (e,),
                p + "x_proj.w": (e, r + 2 * n), p + "dt_proj.w": (r, e), p + "dt_proj.b": (e,),
                p + "A_log": (e, n), p + "D": (e,), p + "out_proj.w": (e, d)})
        elif kind == "G":
            shapes.update({p + "gmu.in.w": (d, e), p + "gmu.out.w": (e, d)})
        else:
            shapes.update({p + "q.w": (d, d), p + "q.b": (d,)})
            if kind != "X":
                shapes.update({p + "k.w": (d, kv), p + "k.b": (kv,),
                               p + "v.w": (d, kv), p + "v.b": (kv,)})
            shapes.update({p + "o.w": (d, d), p + "o.b": (d,), p + "subln.w": (2 * z["head"],),
                           **{p + f"lambda_{n}": (z["head"],) for n in ("q1", "k1", "q2", "k2")}})
        shapes.update({p + "norm2.w": (d,), p + "norm2.b": (d,), p + "mlp.gate": (d, f),
                       p + "mlp.up": (d, f), p + "mlp.down": (f, d)})
    shapes.update({"norm_f.w": (d,), "norm_f.b": (d,)})
    return shapes


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, also one wider than 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def init_weights(cfg: dict, key: jax.Array, dtype=jnp.float32) -> dict:
    """Random weights from ``key`` (``seed_key(seed)``), so that every term of
    the equations is exercised at any size: matrices (the embedding among them)
    N(0, 1 / fan-in), which is the other references' N(0, 0.02) at this model's
    2560; gains 1 + N(0, 0.02) and biases N(0, 0.02); the convolution
    U(+-1/sqrt(K)). Mamba's published initialisation, so that the state is not
    degenerate: `A_log = log(1..N)` in every channel, `D` 1, `softplus(b_dt)`
    log-uniform in [0.001, 0.1]. The four lambda vectors N(0, 0.1), so that
    lambda is not lambda_init. Drawn in float32 and rounded once to ``dtype``,
    except `A_log`, `D`, `b_dt` and the lambdas, which stay float32. Traceable:
    under ``jax.jit`` one program for all seeds."""
    f32 = jnp.float32
    z = sizes(cfg)

    def dt_bias(k, shape):
        dt = jnp.exp(jax.random.uniform(k, shape, f32, math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus(b_dt) = dt

    near = lambda mean: lambda k, shape: mean + 0.02 * jax.random.normal(k, shape, f32)  # noqa: E731
    bound = 1.0 / math.sqrt(z["conv"])
    draw = {
        "A_log": lambda k, shape: jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[1] + 1, dtype=f32)), shape),
        "D": lambda k, shape: jnp.ones(shape, f32),
        "dt_proj.b": dt_bias,
        "conv.w": lambda k, shape: jax.random.uniform(k, shape, f32, -bound, bound),
        "embed": lambda k, shape: jax.random.normal(k, shape, f32) / math.sqrt(shape[1]),
    }
    out = {}
    for i, (name, shape) in enumerate(leaf_shapes(cfg).items()):
        leaf = name.split(".", 2)[-1] if name.startswith("layers.") else name
        k = jax.random.fold_in(key, i)
        if leaf in draw:
            w = draw[leaf](k, shape)
        elif leaf.startswith("lambda_"):
            w = LAMBDA_STD * jax.random.normal(k, shape, f32)
        elif len(shape) == 2:
            w = jax.random.normal(k, shape, f32) / math.sqrt(shape[0])
        else:
            w = near(1.0 if leaf.endswith(".w") else 0.0)(k, shape)
        out[name] = w.astype(f32 if leaf in F32_LEAVES else dtype)
    return out


# ------------------------------------------------------------------ forward

mm = partial(jnp.matmul, precision=HIGHEST)


def layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def mamba_mixer(cfg: dict, lw: dict, u):
    """One Mamba-1 layer on u [T, d] from a zero state -> (out [T, d], m [T, E]):
    the recurrence one token at a time."""
    z = sizes(cfg)
    e, n, r, k = z["inner"], z["state"], z["dt_rank"], z["conv"]
    x, gate = jnp.split(mm(u, lw["in_proj.w"]), 2, axis=-1)
    padded = jnp.concatenate([jnp.zeros((k - 1, e)), x])
    x = jax.nn.silu(sum(padded[j:j + u.shape[0]] * lw["conv.w"][j] for j in range(k))
                    + lw["conv.b"])
    delta, b, c = jnp.split(mm(x, lw["x_proj.w"]), [r, r + n], axis=-1)
    dt = jax.nn.softplus(mm(delta, lw["dt_proj.w"]) + lw["dt_proj.b"])
    a = -jnp.exp(lw["A_log"])
    # As the published `selective_scan_ref`: every token's decay and input first,
    # then the tokens in order. The state is held [N, E] (its transpose: the same
    # numbers, and the chip pads a 16-wide last axis eightfold).
    decay = jnp.exp(dt[:, None, :] * a.T[None])  # [T, N, E]
    fed = (dt * x)[:, None, :] * b[:, :, None]

    def token(s, row):
        decay_t, fed_t, ct = row
        s = decay_t * s + fed_t
        return s, jnp.sum(s * ct[:, None], axis=0)

    _, y = jax.lax.scan(token, jnp.zeros((n, e)), (decay, fed, c))
    m = y + lw["D"] * x
    return mm(m * jax.nn.silu(gate), lw["out_proj.w"]), m


def gmu_mixer(lw: dict, u, m):
    return mm(m * jax.nn.silu(mm(u, lw["gmu.in.w"])), lw["gmu.out.w"])


def kv_of(cfg: dict, lw: dict, u):
    """(k, v) [T, kv_heads, D] of a W or F layer's input."""
    z = sizes(cfg)
    shape = (u.shape[0], z["kv_heads"], z["head"])
    return ((mm(u, lw["k.w"]) + lw["k.b"]).reshape(shape),
            (mm(u, lw["v.w"]) + lw["v.b"]).reshape(shape))


def attention_mixer(cfg: dict, lw: dict, u, k, v, lam_init, window: int | None):
    """Differential attention of the queries of u [T, d] over k, v [T, kv_heads,
    D]: a differential head at a time, its two softmaxes written out."""
    z = sizes(cfg)
    t, nq, dh = u.shape[0], z["heads"], z["head"]
    q = (mm(u, lw["q.w"]) + lw["q.b"]).reshape(t, nq, dh)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = j <= i
    if window is not None:  # the window counts the query's own position
        seen &= j > i - window
    lam = (jnp.exp(jnp.sum(lw["lambda_q1"] * lw["lambda_k1"]))
           - jnp.exp(jnp.sum(lw["lambda_q2"] * lw["lambda_k2"])) + lam_init)

    def softmax_of(qh, kh):
        s = jnp.where(seen, mm(qh, kh.T) / math.sqrt(dh), -jnp.inf)
        return jax.nn.softmax(s, axis=-1)

    def head(h):  # differential head h of nq / 2
        p = h // 2
        values = jnp.concatenate([v[:, 2 * p], v[:, 2 * p + 1]], axis=-1)  # [T, 2D]
        a1 = mm(softmax_of(q[:, 2 * h], k[:, 2 * p]), values)
        a2 = mm(softmax_of(q[:, 2 * h + 1], k[:, 2 * p + 1]), values)
        o = a1 - lam * a2
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                              + cfg["layer_norm_eps"]) * lw["subln.w"]
        return o * (1.0 - lam_init)

    o = jax.lax.map(head, jnp.arange(nq // 2))  # [H/2, T, 2D]
    return mm(o.transpose(1, 0, 2).reshape(t, nq * dh), lw["o.w"]) + lw["o.b"]


def _f32(lw: dict) -> dict:
    return {k: a.astype(jnp.float32) for k, a in lw.items()}


def mixer_block(cfg: dict, kind: str, lw: dict, h, lam_init=0.0, carried=None):
    """The mixer half of a layer of ``kind`` on h [T, d] -> (h, carried):
    ``carried`` is what later layers read, ``m`` after an S, ``(k, v)`` after
    an F, and what a G or an X is given."""
    lw = _f32(lw)
    u = layer_norm(h, lw["norm1.w"], lw["norm1.b"], cfg["layer_norm_eps"])
    if kind == "S":
        out, carried = mamba_mixer(cfg, lw, u)
    elif kind == "G":
        out = gmu_mixer(lw, u, carried)
    else:
        if kind != "X":
            carried = kv_of(cfg, lw, u)
        out = attention_mixer(cfg, lw, u, *carried, lam_init,
                              cfg["sliding_window"] if kind == "W" else None)
    return h + out, carried


def mlp_block(cfg: dict, lw: dict, h):
    lw = _f32(lw)
    u = layer_norm(h, lw["norm2.w"], lw["norm2.b"], cfg["layer_norm_eps"])
    return h + mm(jax.nn.silu(mm(u, lw["mlp.gate"])) * mm(u, lw["mlp.up"]), lw["mlp.down"])


def layer_leaves(w: dict, i: int) -> dict:
    prefix = f"layers.{i}."
    return {k[len(prefix):]: a for k, a in w.items() if k.startswith(prefix)}


def head_logits(cfg: dict, w: dict, h):
    y = layer_norm(h, w["norm_f.w"].astype(jnp.float32), w["norm_f.b"].astype(jnp.float32),
                   cfg["layer_norm_eps"])
    return mm(y, w["embed"].astype(jnp.float32).T)


def _trunk(cfg: dict, w: dict, tokens, mixer, mlp):
    """The layers in order: ``mixer(kind)(leaves, h, lam_init, carried)`` and
    ``mlp(leaves, h)``; S's ``m`` goes to the G layers, F's K and V to the X."""
    h = w["embed"][tokens].astype(jnp.float32)
    memory = shared = None
    for i in range(cfg["num_hidden_layers"]):
        lw, kind = layer_leaves(w, i), layer_kind(cfg, i)
        mine = {k: a for k, a in lw.items() if k not in MLP_LEAVES}
        given = memory if kind == "G" else shared if kind == "X" else None
        h, made = mixer(kind)(mine, h, jnp.float32(lambda_init(i)), given)
        if kind == "S":
            memory = made
        elif kind == "F":
            shared = made
        h = mlp({k: a for k, a in lw.items() if k in MLP_LEAVES}, h)
    return h


def forward(cfg: dict, w: dict, tokens):
    """Logits [T, V] of one sequence ``tokens`` [T]."""
    return head_logits(cfg, w, _trunk(cfg, w, tokens, lambda kind: partial(mixer_block, cfg, kind),
                                      partial(mlp_block, cfg)))


# -------------------------------------------------------- serving reference


@lru_cache(maxsize=8)
def _serving_programs(cfg_json: str, n_rows: int):
    """One jitted program a kind of half-layer (`lambda_init` is an argument,
    not a constant: a program a kind, not a layer), and the head's."""
    cfg = json.loads(cfg_json)
    mixers = {kind: jax.jit(partial(mixer_block, cfg, kind)) for kind in "SWFGX"}
    return mixers, jax.jit(partial(mlp_block, cfg)), jax.jit(lambda w, h, s: head_logits(
        cfg, w, jax.lax.dynamic_slice_in_dim(h, s, n_rows)))


def served_rows_logits(cfg: dict, w: dict, tokens, first_row, n_rows: int):
    """Logits [n_rows, V] at rows ``first_row``.. of one sequence ``tokens`` [T]:
    the rows whose next-token distributions produced the served tokens. The
    caller pads T at the end to one of a few lengths (every layer is causal:
    padding after a row cannot reach it). Half a layer at a time, one jitted
    program a kind, so that it fits and compiles once."""
    mixers, mlp, run_head = _serving_programs(json.dumps(cfg, sort_keys=True), n_rows)
    h = _trunk(cfg, w, tokens, mixers.__getitem__, mlp)
    return run_head({k: w[k] for k in ("norm_f.w", "norm_f.b", "embed")}, h, first_row)
