"""Between the `phi4flash` reference's flat weight names and the program's
parameter tree (`tpudml.models.HybridLM`): renaming only, no arithmetic. Also
builds the program's model from a configuration file and a cell's options.

A published layer is two entries of the program's pattern: its mixer (`S`
Mamba-1, `W` window and `F` full differential attention, `G` gated memory unit,
`X` differential cross attention) and its feed-forward (`D`). The embedding is
the head too (`tied`): the reference has no head leaf and the program no head
parameter."""

from __future__ import annotations

import jax.numpy as jnp

from benchmarks.reference import phi4flash as ref

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
_LEAVES = {
    "norm1.w": ("norm", "scale"), "norm1.b": ("norm", "bias"),
    "norm2.w": ("norm", "scale"), "norm2.b": ("norm", "bias"),
    "mlp.gate": ("mixer", "gate"), "mlp.up": ("mixer", "up"), "mlp.down": ("mixer", "down"),
    "in_proj.w": ("mixer", "in_proj", "kernel"), "conv.w": ("mixer", "conv", "kernel"),
    "conv.b": ("mixer", "conv", "bias"), "x_proj.w": ("mixer", "x_proj", "kernel"),
    "dt_proj.w": ("mixer", "dt_proj", "kernel"), "dt_proj.b": ("mixer", "dt_proj", "bias"),
    "A_log": ("mixer", "A_log"), "D": ("mixer", "D"),
    "out_proj.w": ("mixer", "out_proj", "kernel"),
    "gmu.in.w": ("mixer", "in_proj", "kernel"), "gmu.out.w": ("mixer", "out_proj", "kernel"),
    "q.w": ("mixer", "q", "kernel"), "q.b": ("mixer", "q", "bias"),
    "k.w": ("mixer", "k", "kernel"), "k.b": ("mixer", "k", "bias"),
    "v.w": ("mixer", "v", "kernel"), "v.b": ("mixer", "v", "bias"),
    "o.w": ("mixer", "out", "kernel"), "o.b": ("mixer", "out", "bias"),
    "subln.w": ("mixer", "subln", "scale"),
    **{f"lambda_{n}": ("mixer", f"lambda_{n}") for n in ("q1", "k1", "q2", "k2")},
}


def pattern(cfg: dict) -> str:
    """The program's pattern: two letters a published layer."""
    return "".join(ref.layer_kind(cfg, i) + "D" for i in range(cfg["num_hidden_layers"]))


def name_map(cfg: dict) -> dict[str, tuple]:
    """reference leaf name -> path of keys in the program's tree."""
    out = {"embed": ("embed",), "norm_f.w": ("norm_f", "scale"), "norm_f.b": ("norm_f", "bias")}
    for name in ref.leaf_shapes(cfg):
        if name.startswith("layers."):
            _, i, leaf = name.split(".", 2)
            out[name] = (f"layer{2 * int(i) + (leaf in ref.MLP_LEAVES)}", *_LEAVES[leaf])
    return out


def to_program(flat: dict, cfg: dict) -> dict:
    tree: dict = {}
    for name, path in name_map(cfg).items():
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = flat[name]
    return tree


def build_model(config: dict, options: dict):
    """The program's model at the configuration's sizes, with the cell's
    options (`param_dtype`) and a control's: ``window``, ``pair_rows``,
    ``state_dtype`` as `HybridLM` names them."""
    from tpudml.models import HybridLM

    z = ref.sizes(config)
    if not config["tie_word_embeddings"] or config["mlp_bias"] or config["lm_head_bias"]:
        raise ValueError("a tied head and no bias in the feed-forward or the head are what "
                         "the reference writes out")
    extra = {k: options[k] for k in ("window", "pair_rows") if k in options}
    if "state_dtype" in options:
        extra["state_dtype"] = _DTYPES[options["state_dtype"]]
    return HybridLM(**{**dict(
        vocab_size=config["vocab_size"], pattern=pattern(config), embed_dim=z["d"],
        num_heads=z["heads"], head_dim=z["head"], attn_bias=True, differential=True,
        window=config["sliding_window"], dense_dim=config["intermediate_size"],
        ssm_inner=z["inner"], dt_rank=z["dt_rank"], state_size=z["state"],
        conv_kernel=z["conv"], norm="layer", tied=True, eps=config["layer_norm_eps"],
        dtype=param_dtype(options)), **extra})


def param_dtype(options: dict):
    return _DTYPES[options.get("param_dtype", "float32")]
