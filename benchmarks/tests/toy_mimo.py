"""Toy `mimo_v2` configuration and cell for the CPU tests: the published
patterns' first seven layers at sizes a test can hold (every ratio kept: two
K/V head counts, a q/k head wider than the v head, a rotary slice, a window
shorter than a chunk), and the real `serve_mimo` driver over it."""

from __future__ import annotations

import copy
import json

from benchmarks import cells

TOY_MIMO = {
    "hidden_size": 48, "layernorm_epsilon": 1e-5, "num_hidden_layers": 7,
    "hybrid_layer_pattern": [0, 1, 1, 1, 1, 0, 1], "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1],
    "vocab_size": 96, "num_attention_heads": 8, "num_key_value_heads": 2,
    "swa_num_key_value_heads": 4, "head_dim": 24, "v_head_dim": 16,
    "partial_rotary_factor": 0.334, "rope_theta": 10000000, "swa_rope_theta": 10000,
    "sliding_window": 8, "attention_value_scale": 0.707,
    "add_full_attention_sink_bias": False, "add_swa_attention_sink_bias": True,
    "intermediate_size": 64, "moe_intermediate_size": 24,
    "n_routed_experts": 4, "num_experts_per_tok": 4, "norm_topk_prob": True,
    "routed_scaling_factor": None,
    # one chip of four a layer: experts 0-3 of 16 held
    "deployment": {"n_routed_experts": 16, "held_first": 0},
}

CELL = "mimo-v2.5.serve-reasoning"


def serve_cell(config: dict = TOY_MIMO) -> cells.Cell:
    with open(cells.BENCH / "workloads" / f"{CELL}.json") as f:
        spec = copy.deepcopy(json.load(f))
    spec["engine"]["serve_config"].update(slots=4, max_len=64, prefill_chunk=16,
                                          cache_kind="f32")
    spec["model"].update(param_dtype="float32")
    spec["warmup"] = [{"prompt_len": 49, "max_new_tokens": 2}]
    spec["ramp_s"] = 0.5
    spec["trace"] = {"start_s": 0.0, "seconds": 60.0}
    spec["check"]["pad_to"] = [64]
    # float32 against float32: exact ties aside, the sound engine's gaps are 0
    spec["check"]["limits"] = {"served_token_gap": 1e-4, "served_mean_gap": 1e-6,
                               "route_regret_mean": 1e-7}
    return cells.Cell(
        name="toy.serve-mimo", chips=1, config=copy.deepcopy(config),
        traffic={"generator": "requests", "rate_per_s": 20.0,
                 "prompt_len": {"median": 12, "sigma": 0.8, "min": 1, "max": 48},
                 "output_len": {"median": 6, "sigma": 0.5, "min": 2, "max": 12}},
        spec=spec,
        end_to_end=[{"name": n, "unit": u} for n, u in (
            ("serve.tokens_per_s", "tokens/s"), ("setup_s", "s"))],
        per_layer=[])
