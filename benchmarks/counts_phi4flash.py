"""Parameters, bytes and operations of a `phi4flash` configuration, from shapes
alone (`counts.py` reads GPT-2 keys, `counts_hybrid.py` `nemotron_h` ones,
`counts_mimo.py` `mimo_v2` ones). Kept with the benchmark so that no later PR
changes what a utilization is a share of."""

from __future__ import annotations

import math

from benchmarks.reference.phi4flash import F32_LEAVES, layer_kind, leaf_shapes, sizes


def layer_counts(cfg: dict) -> dict:
    """How many published layers hold each kind of mixer."""
    kinds = [layer_kind(cfg, i) for i in range(cfg["num_hidden_layers"])]
    return {kind: kinds.count(kind) for kind in "SWFGX"}


def param_count(cfg: dict) -> int:
    """Every parameter the configuration holds (the embedding once: it is the head)."""
    return sum(math.prod(shape) for shape in leaf_shapes(cfg).values())


def weight_bytes_held(cfg: dict, weight_bytes: int = 2) -> float:
    """Bytes of the weights a decode step reads: every leaf once, the embedding
    among them (the tied head contracts the whole table), the few float32
    leaves (`A_log`, `D`, the step-size bias, the lambdas) at four bytes."""
    return sum(math.prod(shape) * (4 if name.split(".", 2)[-1] in F32_LEAVES else weight_bytes)
               for name, shape in leaf_shapes(cfg).items())


def kv_row_bytes(cfg: dict, cache_bytes: int = 2) -> int:
    """One token's K and V rows in one attention layer's cache: every K/V head
    at the head's width (a pair a 128-lane row: nothing is padded)."""
    z = sizes(cfg)
    return 2 * z["kv_heads"] * z["head"] * cache_bytes


def prefill_entries(cfg: dict) -> tuple[int, int]:
    """(pattern entries a prefill chunk runs, entries of the pattern): the
    program's pattern has a mixer and a feed-forward a published layer, and a
    chunk stops behind the full layer's mixer, the last to write per-slot state."""
    n = cfg["num_hidden_layers"]
    full = next(i for i in range(n) if layer_kind(cfg, i) == "F")
    return 2 * full + 1, 2 * n


def decode_step_bytes(cfg: dict, rows_read_full: float, rows_window: float, state_bytes: float,
                      active: float, weight_bytes: int = 2, cache_bytes: int = 2) -> float:
    """Bytes one decode step MUST move, from the step's own counters on
    `serve/dispatch`: the weights held, the full cache's live rows once for
    every layer that reads them (``rows_read_full``: the full layer and each
    cross layer) and the rings' live rows (``rows_window``), the active slots'
    recurrent state read and written back (``state_bytes``, one way), and the
    rows written for the active slots (the full layer's and every ring's). A
    floor on purpose: the program reads every allocated row of the full cache
    whatever the positions say, eight times."""
    counts = layer_counts(cfg)
    row = kv_row_bytes(cfg, cache_bytes)
    return (weight_bytes_held(cfg, weight_bytes) + (rows_read_full + rows_window) * row
            + 2 * state_bytes + active * (counts["F"] + counts["W"]) * row)


def shared_read_bytes(cfg: dict, rows_full: float, rows_read_full: float,
                      cache_bytes: int = 2) -> float:
    """Of `decode_step_bytes`, what the cross layers' re-reads of the full
    layer's cache are."""
    return (rows_read_full - rows_full) * kv_row_bytes(cfg, cache_bytes)


def decode_attn_counts(cfg: dict, rows: float, active: float,
                       cache_bytes: int = 2) -> tuple[float, float]:
    """(operations, bytes) one call of the decode-attention kernel MUST do for
    ``rows`` live rows summed over ``active`` slots of ONE layer's read
    (`decode_attn`, `decode_attn_shared`, `decode_attn_window`): for every
    differential head and live row q1 . k1 and q2 . k2 over the head's width
    and two P . V over the pair's two value heads; the live rows' K and V read
    once, the queries read and A1, A2 written (bfloat16). The kernel's zero
    lanes (`[q1 | 0]` scores all 128) are not counted: they are not asked for."""
    z = sizes(cfg)
    diff_heads, d = z["heads"] // 2, z["head"]
    ops = 2.0 * diff_heads * (2 * d + 2 * 2 * d) * rows
    moved = rows * kv_row_bytes(cfg, cache_bytes) + active * (z["heads"] * d
                                                              + 2 * diff_heads * 2 * d) * 2
    return ops, moved

