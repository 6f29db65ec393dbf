"""Parameters, bytes and operations of a `mimo_v2` configuration, from shapes
alone (`counts.py` reads GPT-2 keys, `counts_hybrid.py` `nemotron_h` ones). Kept
with the benchmark so that no later PR changes what a utilization is a share of."""

from __future__ import annotations

from benchmarks.reference.mimo_v2 import attention_kind, router_width


def attention_layer_params(cfg: dict, i: int) -> int:
    """Layer i's attention: q, k, v, o, its norm, and a window layer's sinks."""
    d, nq, dk, dv = (cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"],
                     cfg["v_head_dim"])
    kind = attention_kind(cfg, i)
    return (d * nq * dk + d * kind["kv_heads"] * (dk + dv) + nq * dv * d + d
            + (nq if kind["sink"] else 0))


def expert_params(cfg: dict) -> int:
    """One routed expert: three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * router_width(cfg) + router_width(cfg)  # and its selection bias


def ffn_layer_params(cfg: dict, i: int) -> int:
    """Layer i's feed-forward with its norm: the dense SwiGLU, or the router
    and the `n_routed_experts` experts held."""
    d = cfg["hidden_size"]
    if not cfg["moe_layer_freq"][i]:
        return 3 * d * cfg["intermediate_size"] + d
    return router_params(cfg) + cfg["n_routed_experts"] * expert_params(cfg) + d


def param_count(cfg: dict) -> int:
    """Every parameter the configuration holds (`n_routed_experts` experts a
    layer, `vocab_size` rows of the embedding and columns of the head)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return (sum(attention_layer_params(cfg, i) + ffn_layer_params(cfg, i)
                for i in range(cfg["num_hidden_layers"])) + 2 * v * d + d)


def kv_row_bytes(cfg: dict, i: int, cache_bytes: int = 2) -> int:
    """One token's K and V rows in layer i at the head's own widths: a key
    stored in more lanes than the head has (192 in 256) counts 192."""
    return attention_kind(cfg, i)["kv_heads"] * (cfg["head_dim"] + cfg["v_head_dim"]) \
        * cache_bytes


def layer_counts(cfg: dict) -> dict:
    """How many layers of each attention kind, and how many with experts."""
    window = sum(bool(p) for p in cfg["hybrid_layer_pattern"])
    return {"window": window, "full": cfg["num_hidden_layers"] - window,
            "moe": sum(bool(m) for m in cfg["moe_layer_freq"])}


def live_row_bytes(cfg: dict, rows_full: float, rows_window: float,
                   cache_bytes: int = 2) -> tuple[float, float]:
    """(full, window) K/V bytes of the live rows a step must read, from the
    step's counters on `serve/dispatch`: ``rows_full`` and ``rows_window`` are
    summed over the active slots AND over the layers of the kind (position + 1
    rows of a full layer, min(position + 1, window) of a window layer)."""
    kinds = {bool(p): kv_row_bytes(cfg, i, cache_bytes)
             for i, p in enumerate(cfg["hybrid_layer_pattern"])}
    return rows_full * kinds.get(False, 0), rows_window * kinds.get(True, 0)


def weight_bytes_held(cfg: dict, weight_bytes: int = 2) -> float:
    """Bytes of the weights a decode step reads: every matrix held once (the
    router and the sinks float32; the embedding's gathered rows are negligible,
    its table is not read)."""
    f32_leaves = layer_counts(cfg)["moe"] * router_params(cfg) + sum(
        cfg["num_attention_heads"] for i in range(cfg["num_hidden_layers"])
        if attention_kind(cfg, i)["sink"])
    matrices = param_count(cfg) - cfg["vocab_size"] * cfg["hidden_size"] - f32_leaves
    return matrices * weight_bytes + f32_leaves * 4


def decode_step_bytes(cfg: dict, rows_full: float, rows_window: float, active: float,
                      weight_bytes: int = 2, cache_bytes: int = 2) -> float:
    """Bytes one decode step MUST move, from the step's own counters: the
    weights held (`weight_bytes_held`; every held expert: at 128 slots x top-8
    of 256 a held expert has four tokens a step, none is idle), the live K/V
    rows of every layer at the head's own widths, and the rows written for the
    active slots. A floor on purpose: the program reads every allocated row of
    the full layers whatever the positions say, and a key in 256 lanes; the
    share of the roof then says how far a step lies from what it has to do."""
    full, window = live_row_bytes(cfg, rows_full, rows_window, cache_bytes)
    written = active * sum(kv_row_bytes(cfg, i, cache_bytes)
                           for i in range(cfg["num_hidden_layers"]))
    return weight_bytes_held(cfg, weight_bytes) + full + window + written


def decode_attn_counts(cfg: dict, window: bool, rows: float, active: float,
                       cache_bytes: int = 2) -> tuple[float, float]:
    """(operations, bytes) one call of the decode-attention kernel MUST do for
    ``rows`` live rows summed over ``active`` slots of ONE layer (a window
    layer: `decode_attn_window`): q . K over 192 and P . V over 128 for every
    query head and live row, and the live rows' K and V read once, q read and
    the output written (bfloat16)."""
    i = cfg["hybrid_layer_pattern"].index(int(window))
    nq, dk, dv = cfg["num_attention_heads"], cfg["head_dim"], cfg["v_head_dim"]
    ops = 2.0 * nq * (dk + dv) * rows
    moved = rows * kv_row_bytes(cfg, i, cache_bytes) + active * nq * (dk + dv) * 2
    return ops, moved
