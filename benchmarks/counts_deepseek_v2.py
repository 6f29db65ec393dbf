"""Parameters, bytes and operations of a `deepseek_v2` configuration, from shapes
alone (`counts.py` reads GPT-2 keys, `counts_hybrid.py` `nemotron_h` ones,
`counts_mimo.py` `mimo_v2` ones). Kept with the benchmark so that no later PR
changes what a utilization is a share of."""

from __future__ import annotations

from benchmarks.reference.deepseek_v2 import is_moe, router_width


def attention_layer_params(cfg: dict) -> int:
    """One MLA block with its layer norm: W_DQ, its norm, W_UQ, W_DKV, its norm,
    W_UKV, W_O."""
    d, nh, qr, r = (cfg["hidden_size"], cfg["num_attention_heads"], cfg["q_lora_rank"],
                    cfg["kv_lora_rank"])
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return (d * qr + qr + qr * nh * (nope + rope) + d * (r + rope) + r
            + r * nh * (nope + dv) + nh * dv * d + d)


def expert_params(cfg: dict) -> int:
    """One routed expert: three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * router_width(cfg)  # no bias: softmax scoring


def shared_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["n_shared_experts"] * cfg["moe_intermediate_size"]


def ffn_layer_params(cfg: dict, i: int) -> int:
    """Layer i's feed-forward with its norm: the dense SwiGLU, or the router, the
    shared expert and the `n_routed_experts` experts held."""
    d = cfg["hidden_size"]
    if not is_moe(cfg, i):
        return 3 * d * cfg["intermediate_size"] + d
    return (router_params(cfg) + shared_params(cfg)
            + cfg["n_routed_experts"] * expert_params(cfg) + d)


def moe_layers(cfg: dict) -> int:
    return sum(is_moe(cfg, i) for i in range(cfg["num_hidden_layers"]))


def param_count(cfg: dict) -> int:
    """Every parameter the configuration holds (`n_routed_experts` experts a
    layer, `vocab_size` rows of the embedding and columns of the head)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return (sum(attention_layer_params(cfg) + ffn_layer_params(cfg, i)
                for i in range(cfg["num_hidden_layers"])) + 2 * v * d + d)


def latent_row_bytes(cfg: dict, cache_bytes: int = 2) -> int:
    """One token's latent row in one layer at its own width, `[c_kv | k_r]`: the
    640 lanes it is stored in count 576."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * cache_bytes


def weight_bytes_outside_experts(cfg: dict, weight_bytes: int = 2) -> float:
    """Bytes of the weights every decode step reads whatever the routing: all
    but the routed experts and the embedding table (its gathered rows are
    negligible), the routers float32."""
    routers = moe_layers(cfg) * router_params(cfg)
    matrices = (param_count(cfg) - cfg["vocab_size"] * cfg["hidden_size"] - routers
                - moe_layers(cfg) * cfg["n_routed_experts"] * expert_params(cfg))
    return matrices * weight_bytes + routers * 4


def decode_step_bytes(cfg: dict, rows_latent: float, active: float, experts_touched: float,
                      weight_bytes: int = 2, cache_bytes: int = 2) -> float:
    """Bytes one decode step MUST move, from the step's own counters: the
    weights outside the routed experts, the held experts that have a token
    (``experts_touched``, summed over the expert layers), the live latent rows of
    every layer at their own width (``rows_latent``, summed over the active slots
    and the layers), and the rows written for the ``active`` slots. A floor on
    purpose: the program reads every held expert and every allocated row in 640
    lanes whatever the counters say, so the share of the roof says how far a step
    lies from what it has to do, and cannot pass 100 %."""
    row = latent_row_bytes(cfg, cache_bytes)
    return (weight_bytes_outside_experts(cfg, weight_bytes)
            + experts_touched * expert_params(cfg) * weight_bytes
            + rows_latent * row + active * cfg["num_hidden_layers"] * row)


def decode_attn_counts(cfg: dict, rows: float, active: float,
                       cache_bytes: int = 2) -> tuple[float, float]:
    """(operations, bytes) one call of `decode_attn_latent` MUST do for ``rows``
    live rows summed over ``active`` slots of ONE layer: q . row over 576 and P .
    c_kv over 512 for each of the 128 query heads and every live row; the live
    rows read once at their own width, q read and the output written
    (bfloat16). 242 operations a byte of cache: beside the v5e's ridge of 240."""
    nh, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    width = r + cfg["qk_rope_head_dim"]
    ops = 2.0 * nh * (width + r) * rows
    moved = rows * latent_row_bytes(cfg, cache_bytes) + active * nh * (width + r) * 2
    return ops, moved
