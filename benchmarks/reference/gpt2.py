"""Plain reference of the GPT-2 / GPT-BigCode decoder, in float32 jax.numpy.

Follows the published description (Radford et al. 2019; `modeling_gpt2.py` /
`modeling_gpt_bigcode.py` of the sources named in `benchmarks/configs/`):
token + learned position embeddings, `n_layer` pre-LayerNorm blocks
(causal self-attention with `n_head` heads, or one shared K/V head when
`multi_query`; a 4x MLP with the tanh GELU), a final LayerNorm and a vocabulary
projection. No kernels, no cache, no batching tricks; every matmul runs at
`highest` precision. It imports nothing of the program under test and makes
its own weights from the seed.

Departures from the published models, both forced by the program's
`TransformerLM` and listed in the configuration files: the output projection
is not tied to the token embedding, and it has a bias.

Weights are a flat dict of named leaves (`h.3.attn.q.w`, ...), separate q/k/v
projections (the published fused `c_attn` is the concatenation of the three).
"""

from __future__ import annotations

import math
import time
from functools import lru_cache, partial

import jax
import jax.numpy as jnp

HIGHEST = "highest"
INIT_STD = 0.02  # `initializer_range` of both published configs


def head_dim(cfg: dict) -> int:
    return cfg["n_embd"] // cfg["n_head"]


def kv_width(cfg: dict) -> int:
    return head_dim(cfg) * (1 if cfg.get("multi_query") else cfg["n_head"])


def leaf_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Every parameter leaf by name, in a fixed order."""
    d, v, p = cfg["n_embd"], cfg["vocab_size"], cfg["n_positions"]
    inner = cfg.get("n_inner") or 4 * d
    shapes = {"wte": (v, d), "wpe": (p, d)}
    for i in range(cfg["n_layer"]):
        h = f"h.{i}."
        shapes.update({
            h + "ln_1.g": (d,), h + "ln_1.b": (d,),
            h + "attn.q.w": (d, d), h + "attn.q.b": (d,),
            h + "attn.k.w": (d, kv_width(cfg)), h + "attn.k.b": (kv_width(cfg),),
            h + "attn.v.w": (d, kv_width(cfg)), h + "attn.v.b": (kv_width(cfg),),
            h + "attn.o.w": (d, d), h + "attn.o.b": (d,),
            h + "ln_2.g": (d,), h + "ln_2.b": (d,),
            h + "mlp.fc.w": (d, inner), h + "mlp.fc.b": (inner,),
            h + "mlp.proj.w": (inner, d), h + "mlp.proj.b": (d,),
        })
    shapes.update({"ln_f.g": (d,), "ln_f.b": (d,),
                   "lm_head.w": (d, v), "lm_head.b": (v,)})
    return shapes


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, also one wider than 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def init_weights(cfg: dict, key: jax.Array, dtype=jnp.float32) -> dict:
    """Random weights from ``key`` (``seed_key(seed)``): every leaf N(0, 0.02),
    LayerNorm gains 1 + N(0, 0.02), so that no parameter is left at a value
    (0 or 1) that would hide a fault in how it is used. Drawn in float32 and
    rounded once to ``dtype``, the type the configuration stores them in.

    Traceable, and the key is an argument: under ``jax.jit`` all leaves are
    made in one program that does not change with the seed. Leaves of one
    shape are cut from one draw (a dozen draws, not four hundred)."""
    shapes = leaf_shapes(cfg)
    by_shape: dict = {}
    for name, shape in shapes.items():
        by_shape.setdefault(shape, []).append(name)
    out = {}
    for i, (shape, names) in enumerate(by_shape.items()):
        draw = INIT_STD * jax.random.normal(
            jax.random.fold_in(key, i), (len(names), *shape), jnp.float32)
        for j, name in enumerate(names):
            w = draw[j]
            out[name] = ((1.0 + w) if name.endswith(".g") else w).astype(dtype)
    return {name: out[name] for name in shapes}


# ------------------------------------------------------------------ forward


def gelu_tanh(x):
    """`gelu_new` / `gelu_pytorch_tanh`, as both configs name it."""
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def layer_norm(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def attention(q, k, v, q_start: int = 0):
    """Causal attention: q [B, Tq, H, D] at positions q_start.. over
    k, v [B, Tk, Hkv, D] (Hkv = H, or 1 shared by all heads)."""
    k = jnp.broadcast_to(k, k.shape[:2] + q.shape[2:])
    v = jnp.broadcast_to(v, v.shape[:2] + q.shape[2:])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST)
    s = s / math.sqrt(q.shape[-1])
    q_pos = q_start + jnp.arange(q.shape[1])
    mask = q_pos[:, None] >= jnp.arange(k.shape[1])[None, :]
    p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)


def block(cfg: dict, lw: dict, x, q_block: int | None = None, remat: bool = False):
    """One pre-LN block on x [B, T, d]; ``lw`` holds the layer's leaves
    (``ln_1.g``, ``attn.q.w``, ...). ``q_block`` computes attention in
    blocks of query rows, and ``remat`` recomputes each such block in the
    backward pass: the same mathematics, bounded score memory."""
    lw = {k: a.astype(jnp.float32) for k, a in lw.items()}
    b, t, d = x.shape
    nh, dh, eps = cfg["n_head"], head_dim(cfg), cfg["layer_norm_epsilon"]
    mm = partial(jnp.matmul, precision=HIGHEST)
    attend = jax.checkpoint(attention, static_argnums=(3,)) if remat else attention
    y = layer_norm(x, lw["ln_1.g"], lw["ln_1.b"], eps)
    q = (mm(y, lw["attn.q.w"]) + lw["attn.q.b"]).reshape(b, t, nh, dh)
    k = (mm(y, lw["attn.k.w"]) + lw["attn.k.b"]).reshape(b, t, -1, dh)
    v = (mm(y, lw["attn.v.w"]) + lw["attn.v.b"]).reshape(b, t, -1, dh)
    if q_block is None or q_block >= t:
        a = attend(q, k, v, 0)
    else:
        a = jnp.concatenate([
            attend(q[:, s:s + q_block], k[:, :s + q_block], v[:, :s + q_block], s)
            for s in range(0, t, q_block)], axis=1)
    x = x + mm(a.reshape(b, t, d), lw["attn.o.w"]) + lw["attn.o.b"]
    y = layer_norm(x, lw["ln_2.g"], lw["ln_2.b"], eps)
    h = gelu_tanh(mm(y, lw["mlp.fc.w"]) + lw["mlp.fc.b"])
    return x + mm(h, lw["mlp.proj.w"]) + lw["mlp.proj.b"]


def layer_leaves(w: dict, i: int) -> dict:
    prefix = f"h.{i}."
    return {k[len(prefix):]: a for k, a in w.items() if k.startswith(prefix)}


def embed(w: dict, tokens):
    t = tokens.shape[1]
    return (w["wte"].astype(jnp.float32)[tokens]
            + w["wpe"].astype(jnp.float32)[:t][None])


def head_logits(cfg: dict, w: dict, x):
    """Final LayerNorm + vocabulary projection on x [..., d]."""
    y = layer_norm(x, w["ln_f.g"].astype(jnp.float32),
                   w["ln_f.b"].astype(jnp.float32), cfg["layer_norm_epsilon"])
    return (jnp.matmul(y, w["lm_head.w"].astype(jnp.float32), precision=HIGHEST)
            + w["lm_head.b"].astype(jnp.float32))


LONG = 2048  # above this many positions the reference works in blocks of 1024


def token_losses(cfg: dict, w: dict, tokens, targets, remat: bool = False):
    """Per-token cross-entropy [B, T] of next-token prediction. With
    ``remat`` every block, and for long sequences every block of attention
    rows and of logits rows, is recomputed in the backward pass: memory, not
    mathematics."""
    t = tokens.shape[1]
    chunk = 1024 if t > LONG else None
    blk = partial(block, cfg, q_block=chunk, remat=remat)
    if remat:
        blk = jax.checkpoint(blk)
    x = embed(w, tokens)
    for i in range(cfg["n_layer"]):
        x = blk(layer_leaves(w, i), x)
    tail = {k: w[k] for k in ("ln_f.g", "ln_f.b", "lm_head.w", "lm_head.b")}

    def rows_loss(tail_, x_, y_):
        logits = head_logits(cfg, tail_, x_)
        picked = jnp.take_along_axis(logits, y_[..., None], axis=-1)[..., 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked

    if chunk is None:
        return rows_loss(tail, x, targets)
    rows_loss = jax.checkpoint(rows_loss) if remat else rows_loss
    return jnp.concatenate([rows_loss(tail, x[:, s:s + chunk], targets[:, s:s + chunk])
                            for s in range(0, t, chunk)], axis=1)


# ------------------------------------------------------- training reference


def adamw_update(hp: dict, t, w: dict, g: dict, m: dict, v: dict):
    """Step ``t`` (from 1, a float scalar) of Adam with decoupled weight decay (Loshchilov &
    Hutter 2019) and bias correction, on flat dicts of float32 leaves."""
    b1, b2, eps, lr, wd = (hp[k] for k in ("b1", "b2", "eps", "lr", "weight_decay"))
    c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    new_w, new_m, new_v = {}, {}, {}
    for k in w:
        new_m[k] = b1 * m[k] + (1.0 - b1) * g[k]
        new_v[k] = b2 * v[k] + (1.0 - b2) * g[k] * g[k]
        step = lr * (new_m[k] / c1) / (jnp.sqrt(new_v[k] / c2) + eps)
        new_w[k] = w[k] - step - lr * wd * w[k]
    return new_w, new_m, new_v


def _leaf_norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(a))) for k, a in tree.items()}


def train_reference(cfg: dict, hp: dict, seed: int, batches,
                    rows_per_block: int = 2, store_dtype=jnp.float32) -> dict:
    """Follow the first ``len(batches)`` optimizer steps from the seeded
    weights in float32. The rows of a batch go through in blocks and their
    gradients are summed: the same mathematics, bounded memory.

    Returns per-step mean losses, the per-leaf norms of the first gradient
    and the per-leaf norms of the parameters' change after the last step.
    ``store_dtype`` below
    float32 is the control: weights and Adam's moments are rounded to it after
    every step (arithmetic stays float32), as a trainer without float32
    master state would keep them."""
    @jax.jit
    def grad_block(w, x, y):
        return jax.value_and_grad(
            lambda w_: jnp.sum(token_losses(cfg, w_, x, y, remat=True)))(w)

    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=(0,))
    scale = jax.jit(lambda g, s: jax.tree.map(lambda a: a * s, g),
                    donate_argnums=(0,))
    norms = jax.jit(_leaf_norms)
    change = jax.jit(lambda a, b: _leaf_norms(
        {k: a[k].astype(jnp.float32) - b[k].astype(jnp.float32) for k in a}))
    make = jax.jit(lambda key: init_weights(cfg, key, store_dtype))

    def stored_update(t, w, g, m, v):
        up = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)  # noqa: E731
        out = adamw_update(hp, t, up(w), g, up(m), up(v))
        return jax.tree.map(lambda a: a.astype(store_dtype), out)

    update = jax.jit(stored_update, donate_argnums=(1, 3, 4))

    w0 = make(seed_key(seed))
    w = make(seed_key(seed))
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    losses, first_grad, seconds = [], None, []
    for t, (x, y) in enumerate(batches, start=1):
        t0 = time.perf_counter()
        x, y = jnp.asarray(x), jnp.asarray(y)
        total, grads = 0.0, None
        for r in range(0, x.shape[0], rows_per_block):
            rows = slice(r, r + rows_per_block)
            loss_sum, g = grad_block(w, x[rows], y[rows])
            total += float(loss_sum)
            grads = g if grads is None else add(grads, g)
        grads = scale(grads, 1.0 / x.size)
        losses.append(total / x.size)
        if first_grad is None:
            first_grad = jax.device_get(norms(grads))
        w, m, v = update(jnp.float32(t), w, grads, m, v)
        jax.block_until_ready(w)
        seconds.append(time.perf_counter() - t0)
    return {
        "step_seconds": seconds,
        "losses": losses,
        "first_grad_norms": {k: float(a) for k, a in first_grad.items()},
        "change_norms": {k: float(a)
                         for k, a in jax.device_get(change(w, w0)).items()},
    }


# -------------------------------------------------------- serving reference


@lru_cache(maxsize=8)
def _serving_programs(cfg_items: tuple, q_block: int, n_rows: int):
    cfg = dict(cfg_items)
    return (jax.jit(embed), jax.jit(partial(block, cfg, q_block=q_block)),
            jax.jit(lambda w, x, s: head_logits(
                cfg, w, jax.lax.dynamic_slice_in_dim(x[0], s, n_rows))))


def served_rows_logits(cfg: dict, w: dict, tokens, first_row, n_rows: int,
                       q_block: int = 1024):
    """Logits [n_rows, V] at rows ``first_row``.. of one sequence ``tokens``
    [T]: the rows whose next-token distributions produced the served tokens.
    The caller pads T at the end to one of a few lengths (causal attention:
    padding after a row cannot reach it). Blocks run one jitted program each,
    so one compile serves all layers."""
    keys = ("n_head", "n_embd", "layer_norm_epsilon")
    run_embed, run_block, run_head = _serving_programs(
        tuple((k, cfg[k]) for k in keys), q_block, n_rows)
    x = run_embed({k: w[k] for k in ("wte", "wpe")}, tokens[None])
    for i in range(cfg["n_layer"]):
        x = run_block(layer_leaves(w, i), x)
    tail = {k: w[k] for k in ("ln_f.g", "ln_f.b", "lm_head.w", "lm_head.b")}
    return run_head(tail, x, first_row)
