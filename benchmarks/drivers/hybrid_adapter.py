"""Between the `nemotron_h` reference's flat weight names and the program's
parameter tree (`tpudml.models.HybridLM`): renaming only, no arithmetic. Also
builds the program's model from a configuration file and a cell's options."""

from __future__ import annotations

import jax.numpy as jnp

from benchmarks.reference import nemotron_h as ref

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
_MIXER = {
    "M": {"in_proj.w": ("in_proj", "kernel"), "conv.w": ("conv", "kernel"),
          "conv.b": ("conv", "bias"), "dt_bias": ("dt_bias",), "A_log": ("A_log",),
          "D": ("D",), "gate_norm.w": ("norm", "scale"),
          "out_proj.w": ("out_proj", "kernel")},
    "E": {"router.w": ("router", "kernel"), "router.bias": ("router", "bias"),
          "experts.up": ("experts", "up"), "experts.down": ("experts", "down"),
          "shared.up": ("shared", "up"), "shared.down": ("shared", "down")},
    "*": {"q.w": ("q", "kernel"), "k.w": ("k", "kernel"), "v.w": ("v", "kernel"),
          "o.w": ("out", "kernel")},
}


def name_map(cfg: dict) -> dict[str, tuple]:
    """reference leaf name -> path of keys in the program's tree."""
    out = {"embed": ("embed",), "norm_f.w": ("norm_f", "scale"),
           "lm_head.w": ("head", "kernel")}
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        out[f"layers.{i}.norm.w"] = (f"layer{i}", "norm", "scale")
        for leaf, path in _MIXER[kind].items():
            out[f"layers.{i}.{leaf}"] = (f"layer{i}", "mixer", *path)
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
    options (`impl`, `param_dtype`; a control's `state_dtype`)."""
    from tpudml.models import HybridLM

    first, count = ref.held_experts(config)
    width = ref.router_width(config)
    if len(config["hybrid_override_pattern"]) != config["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern and num_hidden_layers disagree")
    return HybridLM(
        vocab_size=config["vocab_size"], pattern=config["hybrid_override_pattern"],
        embed_dim=config["hidden_size"], num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        impl=options.get("impl", "flash"),
        mamba_heads=config["mamba_num_heads"], mamba_head_dim=config["mamba_head_dim"],
        n_groups=config["n_groups"], state_size=config["ssm_state_size"],
        conv_kernel=config["conv_kernel"], chunk_size=config["chunk_size"],
        num_experts=width, top_k=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"],
        shared_dim=config["moe_shared_expert_intermediate_size"],
        routed_scale=config["routed_scaling_factor"], norm_topk=config["norm_topk_prob"],
        held=None if (first, count) == (0, width) else (first, count),
        eps=config["norm_eps"], dtype=param_dtype(options),
        state_dtype=_DTYPES[options.get("state_dtype", "float32")],
    )


def param_dtype(options: dict):
    return _DTYPES[options.get("param_dtype", "float32")]
