"""Between the `mimo_v2` reference's flat weight names and the program's
parameter tree (`tpudml.models.HybridLM`): renaming only, no arithmetic. Also
builds the program's model from a configuration file and a cell's options.

A published layer is two entries of the program's pattern: its attention (`F`
full, `W` window) and its feed-forward (`D` dense, `E` experts)."""

from __future__ import annotations

import jax.numpy as jnp

from benchmarks.reference import mimo_v2 as ref

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
_ATTENTION = {"q.w": ("q", "kernel"), "k.w": ("k", "kernel"), "v.w": ("v", "kernel"),
              "o.w": ("out", "kernel"), "sink": ("sink",)}
_FFN = {"mlp.gate": ("gate",), "mlp.up": ("up",), "mlp.down": ("down",),
        "router.w": ("router", "kernel"), "router.bias": ("router", "bias"),
        "experts.gate": ("experts", "gate"), "experts.up": ("experts", "up"),
        "experts.down": ("experts", "down")}


def pattern(cfg: dict) -> str:
    """The program's pattern: two letters a published layer."""
    return "".join(("W" if window else "F") + ("E" if moe else "D")
                   for window, moe in zip(cfg["hybrid_layer_pattern"], cfg["moe_layer_freq"]))


def name_map(cfg: dict) -> dict[str, tuple]:
    """reference leaf name -> path of keys in the program's tree."""
    out = {"embed": ("embed",), "norm_f.w": ("norm_f", "scale"),
           "lm_head.w": ("head", "kernel")}
    for name in ref.leaf_shapes(cfg):
        if not name.startswith("layers."):
            continue
        _, i, leaf = name.split(".", 2)
        half = 2 * int(i) + (leaf not in ref.ATTENTION_LEAVES)
        if leaf.endswith("norm.w"):
            out[name] = (f"layer{half}", "norm", "scale")
        else:
            out[name] = (f"layer{half}", "mixer", *{**_ATTENTION, **_FFN}[leaf])
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
    options (`impl`, `param_dtype`) and a control's: ``rotary_dim``,
    ``value_scale``, ``window``, ``window_sink`` as `HybridLM` names them."""
    from tpudml.models import HybridLM

    first, count = ref.held_experts(config)
    width = ref.router_width(config)
    if config.get("hybrid_override_pattern", pattern(config)) != pattern(config):
        raise ValueError("hybrid_override_pattern is not the program's pattern of this file")
    sizes = dict(
        v_head_dim=config["v_head_dim"], rotary_dim=ref.rotary_dim(config),
        value_scale=config["attention_value_scale"],
        full_kv_heads=config["num_key_value_heads"], full_rope_base=float(config["rope_theta"]),
        full_sink=config["add_full_attention_sink_bias"],
        window=config["sliding_window"], window_kv_heads=config["swa_num_key_value_heads"],
        window_rope_base=float(config["swa_rope_theta"]),
        window_sink=config["add_swa_attention_sink_bias"])
    sizes.update({k: options[k] for k in sizes if k in options})
    return HybridLM(
        vocab_size=config["vocab_size"], pattern=pattern(config),
        embed_dim=config["hidden_size"], num_heads=config["num_attention_heads"],
        head_dim=config["head_dim"], impl=options.get("impl", "full"),
        dense_dim=config["intermediate_size"],
        num_experts=width, top_k=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"], shared_dim=0, gated_experts=True,
        routed_scale=config.get("routed_scaling_factor") or 1.0,
        norm_topk=config["norm_topk_prob"],
        held=None if (first, count) == (0, width) else (first, count),
        eps=config["layernorm_epsilon"], dtype=param_dtype(options), **sizes)


def param_dtype(options: dict):
    return _DTYPES[options.get("param_dtype", "float32")]
