"""Between the reference's flat weight names and the program's parameter
tree (`tpudml.models.TransformerLM`): renaming only, no arithmetic. Also
builds the program's model from a configuration file and a cell's options."""

from __future__ import annotations

import jax.numpy as jnp

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
_PAIRS = (("ln_1", "ln1", ("g", "scale"), ("b", "bias")),
          ("ln_2", "ln2", ("g", "scale"), ("b", "bias")))
_DENSE = (("attn.q", ("attn", "q")), ("attn.k", ("attn", "k")),
          ("attn.v", ("attn", "v")), ("attn.o", ("attn", "out")),
          ("mlp.fc", ("fc1",)), ("mlp.proj", ("fc2",)))


def name_map(n_layer: int) -> dict[str, tuple]:
    """reference leaf name -> path of keys in the program's tree."""
    out = {"wte": ("tok_embed",), "wpe": ("pos_embed",),
           "ln_f.g": ("ln_f", "scale"), "ln_f.b": ("ln_f", "bias"),
           "lm_head.w": ("head", "kernel"), "lm_head.b": ("head", "bias")}
    for i in range(n_layer):
        ref, prog = f"h.{i}.", f"block{i}"
        for r, p, *leaves in _PAIRS:
            for a, b in leaves:
                out[f"{ref}{r}.{a}"] = (prog, p, b)
        for r, path in _DENSE:
            out[f"{ref}{r}.w"] = (prog, *path, "kernel")
            out[f"{ref}{r}.b"] = (prog, *path, "bias")
    return out


def to_program(flat: dict, n_layer: int) -> dict:
    tree: dict = {}
    for name, path in name_map(n_layer).items():
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = flat[name]
    return tree


def from_program(tree: dict, n_layer: int) -> dict:
    flat = {}
    for name, path in name_map(n_layer).items():
        node = tree
        for key in path:
            node = node[key]
        flat[name] = node
    return flat


def build_model(config: dict, options: dict):
    """The program's model at the configuration's sizes, with the cell's
    options (`impl`, `fused_ln`, `param_dtype`, `compute_dtype`, `remat`)."""
    from tpudml.models import TransformerLM

    if config.get("n_inner") not in (None, 4 * config["n_embd"]):
        raise ValueError("TransformerLM's MLP is 4x wide; n_inner differs")
    compute = options.get("compute_dtype")
    return TransformerLM(
        vocab_size=config["vocab_size"], embed_dim=config["n_embd"],
        num_heads=config["n_head"], num_layers=config["n_layer"],
        max_len=config["n_positions"], rope=False,
        num_kv_heads=1 if config.get("multi_query") else None,
        impl=options.get("impl", "flash"),
        fused_ln=options.get("fused_ln", False),
        remat=options.get("remat", False),
        dtype=_DTYPES[options.get("param_dtype", "float32")],
        compute_dtype=_DTYPES[compute] if compute else None,
    )


def param_dtype(options: dict):
    return _DTYPES[options.get("param_dtype", "float32")]
