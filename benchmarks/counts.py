"""Operations and bytes the algorithm needs, from shapes alone. Kept with the
benchmark so that no later PR changes what a utilization is a share of."""

from __future__ import annotations


def _kv_width(cfg: dict) -> int:
    dh = cfg["n_embd"] // cfg["n_head"]
    return dh * (1 if cfg.get("multi_query") else cfg["n_head"])


def matmul_params(cfg: dict) -> int:
    """Weights that take part in a matmul per token: the blocks' projections
    and MLP and the vocabulary head (embedding lookups are gathers)."""
    d = cfg["n_embd"]
    inner = cfg.get("n_inner") or 4 * d
    per_layer = 2 * d * d + 2 * d * _kv_width(cfg) + 2 * d * inner
    return cfg["n_layer"] * per_layer + d * cfg["vocab_size"]


def train_step_flops(cfg: dict, batch: int, seq_len: int) -> float:
    """Model FLOPs of one training step (copied from bench.py
    ``_analytic_lm_flops``, PaLM appendix B convention): 2 FLOP per
    multiply-add, backward twice the forward, causal attention counted at the
    half of the score and value matmuls that is computed, elementwise work and
    recomputation not counted."""
    matmul = 6.0 * batch * seq_len * matmul_params(cfg)
    attention = 6.0 * cfg["n_layer"] * batch * seq_len * seq_len * cfg["n_embd"]
    return matmul + attention


def param_count(cfg: dict) -> int:
    d, v = cfg["n_embd"], cfg["vocab_size"]
    inner = cfg.get("n_inner") or 4 * d
    kv = _kv_width(cfg)
    per_layer = (2 * d * d + 2 * d * kv + 2 * d * inner  # matrices
                 + 2 * d + 2 * kv + inner + d            # biases
                 + 4 * d)                                # two LayerNorms
    return (cfg["n_layer"] * per_layer + v * d + cfg["n_positions"] * d
            + 2 * d + d * v + v)


def decode_step_bytes(cfg: dict, slots: int, rows_read: int,
                      weight_bytes: int = 2, cache_bytes: int = 2) -> float:
    """Bytes one decode step has to read from HBM: every matmul weight once
    (the token and position rows gathered are negligible) and, for each slot,
    ``rows_read`` rows of keys and of values in every layer."""
    weights = matmul_params(cfg) * weight_bytes
    cache = 2.0 * cfg["n_layer"] * slots * rows_read * _kv_width(cfg) * cache_bytes
    return weights + cache
