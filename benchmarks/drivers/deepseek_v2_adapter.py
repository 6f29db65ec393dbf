"""Between the `deepseek_v2` reference's flat weight names and the program's
parameter tree (`tpudml.models.HybridLM`): renaming, and ONE change of layout,
no arithmetic. Also builds the program's model from a configuration file and a
cell's options.

A published layer is two entries of the program's pattern: its latent attention
(`L`) and its feed-forward (`D` dense, `E` experts). The layout: the reference
holds `W_UKV` as published, [kv_lora_rank, H x (nope + v)]; the program holds it a
head at a time, [H, kv_lora_rank, nope + v], so that the absorbed decode's two
halves `W_UK` and `W_UV` are slices of it where it lies."""

from __future__ import annotations

import jax.numpy as jnp

from benchmarks.reference import deepseek_v2 as ref

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
_LEAVES = {"q_a.w": ("q_down", "kernel"), "q_a_norm.w": ("q_norm", "scale"),
           "q_b.w": ("q_up", "kernel"), "kv_a.w": ("kv_down", "kernel"),
           "kv_a_norm.w": ("kv_norm", "scale"), "kv_b.w": ("kv_up", "kernel"),
           "o.w": ("out", "kernel"),
           "mlp.gate": ("gate",), "mlp.up": ("up",), "mlp.down": ("down",),
           "router.w": ("router", "kernel"),
           "experts.gate": ("experts", "gate"), "experts.up": ("experts", "up"),
           "experts.down": ("experts", "down"),
           "shared.gate": ("shared", "gate"), "shared.up": ("shared", "up"),
           "shared.down": ("shared", "down")}


def pattern(cfg: dict) -> str:
    """The program's pattern: two letters a published layer."""
    return "".join("L" + ("E" if ref.is_moe(cfg, i) else "D")
                   for i in range(cfg["num_hidden_layers"]))


def name_map(cfg: dict) -> dict[str, tuple]:
    """reference leaf name -> path of keys in the program's tree."""
    out = {"embed": ("embed",), "norm_f.w": ("norm_f", "scale"),
           "lm_head.w": ("head", "kernel")}
    for name in ref.leaf_shapes(cfg):
        if not name.startswith("layers."):
            continue
        _, i, leaf = name.split(".", 2)
        half = 2 * int(i) + (leaf not in ref.ATTENTION_LEAVES)
        if leaf in ("attn_norm.w", "ffn_norm.w"):
            out[name] = (f"layer{half}", "norm", "scale")
        else:
            out[name] = (f"layer{half}", "mixer", *_LEAVES[leaf])
    return out


def to_program(flat: dict, cfg: dict) -> dict:
    tree: dict = {}
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    for name, path in name_map(cfg).items():
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        leaf = flat[name]
        if name.endswith("kv_b.w"):  # [r, H x n] -> [H, r, n]: the docstring
            leaf = leaf.reshape(rank, heads, -1).transpose(1, 0, 2)
        node[path[-1]] = leaf
    return tree


def yarn(cfg: dict) -> tuple | None:
    rs = cfg.get("rope_scaling")
    return rs and (rs["factor"], rs["original_max_position_embeddings"], rs["beta_fast"],
                   rs["beta_slow"], rs["mscale"], rs["mscale_all_dim"])


def build_model(config: dict, options: dict):
    """The program's model at the configuration's sizes, with the cell's options
    (`param_dtype`) and a control's: ``moe_groups``, ``routed_scale``,
    ``norm_topk`` as `HybridLM` names them."""
    from tpudml.models import HybridLM

    first, count = ref.held_experts(config)
    width = ref.router_width(config)
    if config.get("hybrid_override_pattern", pattern(config)) != pattern(config):
        raise ValueError("hybrid_override_pattern is not the program's pattern of this file")
    if (config["scoring_func"], config["topk_method"]) != ("softmax", "group_limited_greedy"):
        raise ValueError("the router is softmax-scored and group-limited")
    sizes = dict(moe_groups=(config["n_group"], config["topk_group"]),
                 routed_scale=float(config["routed_scaling_factor"]),
                 norm_topk=config["norm_topk_prob"])
    sizes.update({k: options[k] and tuple(options[k]) if k == "moe_groups" else options[k]
                  for k in sizes if k in options})
    return HybridLM(
        vocab_size=config["vocab_size"], pattern=pattern(config),
        embed_dim=config["hidden_size"], num_heads=config["num_attention_heads"],
        q_rank=config["q_lora_rank"], kv_rank=config["kv_lora_rank"],
        nope_dim=config["qk_nope_head_dim"], rope_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"], latent_rope_base=float(config["rope_theta"]),
        yarn=yarn(config), dense_dim=config["intermediate_size"],
        num_experts=width, top_k=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"],
        shared_dim=config["n_shared_experts"] * config["moe_intermediate_size"],
        gated_experts=True, moe_scoring="softmax",
        held=None if (first, count) == (0, width) else (first, count),
        eps=config["rms_norm_eps"], dtype=param_dtype(options), **sizes)


def param_dtype(options: dict):
    return _DTYPES[options.get("param_dtype", "float32")]
