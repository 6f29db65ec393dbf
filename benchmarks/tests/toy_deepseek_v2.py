"""Toy `deepseek_v2` configuration and cell for the CPU tests: one dense and two
expert layers at sizes a test can hold (every ratio kept: a q/k head of nope +
rope wider than the value head, two ranks, a rotary table whose pairs YaRN treats
three ways inside the positions a test reaches, groups of experts of which one is
held), and the real `serve_deepseek_v2` driver over it."""

from __future__ import annotations

import copy
import json

from benchmarks import cells

TOY = {
    "hidden_size": 64, "rms_norm_eps": 1e-6, "num_hidden_layers": 3, "vocab_size": 256,
    "num_attention_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_theta": 100,
    # pairs 0 and 1 keep their frequency, 2 lies on the ramp, 3 is interpolated
    "rope_scaling": {"type": "yarn", "factor": 40, "original_max_position_embeddings": 16,
                     "beta_fast": 2, "beta_slow": 0.25, "mscale": 0.707,
                     "mscale_all_dim": 0.707},
    "first_k_dense_replace": 1, "moe_layer_freq": 1, "intermediate_size": 96,
    "moe_intermediate_size": 24, "n_shared_experts": 2,
    "n_routed_experts": 4, "num_experts_per_tok": 3, "n_group": 4, "topk_group": 2,
    "norm_topk_prob": False, "routed_scaling_factor": 16, "scoring_func": "softmax",
    "topk_method": "group_limited_greedy",
    # one chip of four a layer: group 1 (experts 4-7) of 16 held
    "deployment": {"n_routed_experts": 16, "held_first": 4},
}

CELL = "deepseek-v2.serve-reasoning-256"


def serve_cell(config: dict = TOY) -> cells.Cell:
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
        name="toy.serve-deepseek-v2", chips=1, config=copy.deepcopy(config),
        traffic={"generator": "requests", "rate_per_s": 20.0,
                 "prompt_len": {"median": 12, "sigma": 0.8, "min": 1, "max": 48},
                 "output_len": {"median": 6, "sigma": 0.5, "min": 2, "max": 12}},
        spec=spec,
        end_to_end=[{"name": n, "unit": u} for n, u in (
            ("serve.tokens_per_s", "tokens/s"), ("setup_s", "s"))],
        per_layer=[])
