"""Toy cell of the pattern model for the CPU tests: the real `serve_hybrid`
driver at sizes a test can hold."""

from __future__ import annotations

import copy
import json

from benchmarks import cells

TOY_NEMOTRON = {
    "hidden_size": 48, "norm_eps": 1e-5, "num_hidden_layers": 4,
    "hybrid_override_pattern": "ME*M", "vocab_size": 96,
    "mamba_num_heads": 8, "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16,
    "conv_kernel": 4, "chunk_size": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "n_routed_experts": 4, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "moe_intermediate_size": 24,
    "moe_shared_expert_intermediate_size": 40,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    # one chip of two a layer: experts 0-3 of 8 held
    "deployment": {"n_routed_experts": 8, "held_first": 0},
}

CELL = "nemotron-3-nano-30b-a3b.serve-chat"


def serve_cell(config: dict = TOY_NEMOTRON) -> cells.Cell:
    with open(cells.BENCH / "workloads" / f"{CELL}.json") as f:
        spec = copy.deepcopy(json.load(f))
    spec["engine"]["serve_config"].update(slots=4, max_len=64, prefill_chunk=16,
                                          cache_kind="f32")
    spec["model"].update(impl="full", param_dtype="float32")
    spec["warmup"] = [{"prompt_len": 49, "max_new_tokens": 2}]
    spec["ramp_s"] = 0.5
    spec["trace"] = {"start_s": 0.0, "seconds": 60.0}
    # float32 against float32: exact ties aside, the sound engine's gaps are 0
    spec["check"]["limits"] = {"served_token_gap": 1e-4, "served_mean_gap": 1e-6,
                               "route_regret_mean": 1e-7}
    return cells.Cell(
        name="toy.serve-hybrid", chips=1, config=copy.deepcopy(config),
        traffic={"generator": "requests", "rate_per_s": 20.0,
                 "prompt_len": {"median": 12, "sigma": 0.8, "min": 1, "max": 48},
                 "output_len": {"median": 6, "sigma": 0.5, "min": 2, "max": 12}},
        spec=spec,
        end_to_end=[{"name": n, "unit": u} for n, u in (
            ("serve.tpot_p95_ms", "ms"),
            ("serve.tokens_per_s", "tokens/s"), ("setup_s", "s"))],
        per_layer=[])
