"""Parameters and bytes of a `nemotron_h` configuration, from shapes alone
(`counts.py` reads GPT-2 keys). Kept with the benchmark so that no later PR
changes what a utilization is a share of."""

from __future__ import annotations

from benchmarks.reference.nemotron_h import mamba_sizes as _mamba
from benchmarks.reference.nemotron_h import router_width


def mamba_layer_params(cfg: dict) -> int:
    d, m, h = cfg["hidden_size"], _mamba(cfg), cfg["mamba_num_heads"]
    return (d * m["proj"] + cfg["conv_kernel"] * m["conv"] + m["conv"]  # in, conv
            + 3 * h + m["inner"] + m["inner"] * d + d)  # dt_bias A_log D, gate norm, out, norm


def expert_params(cfg: dict) -> int:
    """One routed expert: two matrices."""
    return 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def moe_layer_params_outside_experts(cfg: dict) -> int:
    d = cfg["hidden_size"]
    return (d * router_width(cfg) + router_width(cfg)  # router and its selection bias
            + 2 * d * cfg["moe_shared_expert_intermediate_size"] + d)  # shared expert, norm


def attention_layer_params(cfg: dict) -> int:
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    return (2 * d * cfg["num_attention_heads"] * dh
            + 2 * d * cfg["num_key_value_heads"] * dh + d)


def param_count(cfg: dict) -> int:
    """Every parameter the configuration holds (`n_routed_experts` experts a
    layer, `vocab_size` rows of the embedding and columns of the head)."""
    d, v, pattern = cfg["hidden_size"], cfg["vocab_size"], cfg["hybrid_override_pattern"]
    return (pattern.count("M") * mamba_layer_params(cfg)
            + pattern.count("E") * (moe_layer_params_outside_experts(cfg)
                                    + cfg["n_routed_experts"] * expert_params(cfg))
            + pattern.count("*") * attention_layer_params(cfg)
            + 2 * v * d + d)


def state_bytes_per_slot(cfg: dict, state_bytes: int = 4, window_bytes: int = 2) -> int:
    """One slot's recurrent state in one Mamba layer: the [H, P, N] state and
    the convolution's K - 1 inputs."""
    return (cfg["mamba_num_heads"] * cfg["mamba_head_dim"] * cfg["ssm_state_size"]
            * state_bytes + (cfg["conv_kernel"] - 1) * _mamba(cfg)["conv"] * window_bytes)


def kv_row_bytes(cfg: dict, cache_bytes: int = 2) -> int:
    """One token's K and V rows in one attention layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * cache_bytes


def decode_step_bytes(cfg: dict, active: float, experts_touched: float,
                      weight_bytes: int = 2, cache_bytes: int = 2,
                      state_bytes: int = 4) -> float:
    """Bytes one decode step MUST move, from the step's own counters
    (``active`` slots, ``experts_touched`` summed over the expert layers):
    every weight outside the routed experts once (the router in float32; the
    embedding rows gathered are negligible), the experts touched, the K/V
    rows written for the active slots, and the active slots' recurrent state
    read and written back. A lower bound on purpose — the K/V rows a step
    reads are not counted, so the share of the roof cannot pass 100 % when a
    later step stops reading rows that hold nothing. The program as it is
    moves more: its dense experts read every held expert, touched or not,
    and its step reads and writes the state of every slot, active or not."""
    d, pattern = cfg["hidden_size"], cfg["hybrid_override_pattern"]
    router = d * router_width(cfg) + router_width(cfg)
    outside = (pattern.count("M") * mamba_layer_params(cfg)
               + pattern.count("E") * (moe_layer_params_outside_experts(cfg) - router)
               + pattern.count("*") * attention_layer_params(cfg)
               + d * cfg["vocab_size"] + d) * weight_bytes \
        + pattern.count("E") * router * 4
    return (outside + experts_touched * expert_params(cfg) * weight_bytes
            + pattern.count("*") * active * kv_row_bytes(cfg, cache_bytes)
            + pattern.count("M") * active * 2 * state_bytes_per_slot(
                cfg, state_bytes, weight_bytes))
