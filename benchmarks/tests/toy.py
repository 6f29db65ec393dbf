"""Toy cells for the CPU tests: the real drivers at sizes a test can hold."""

from __future__ import annotations

import copy
import json

from benchmarks import cells

TOY_GPT2 = {"n_layer": 2, "n_embd": 64, "n_head": 4, "n_inner": None,
            "n_positions": 64, "vocab_size": 256, "layer_norm_epsilon": 1e-5,
            "multi_query": False}
TOY_BIGCODE = {**TOY_GPT2, "multi_query": True, "n_inner": 256}


def _spec(cell_name: str) -> dict:
    with open(cells.BENCH / "workloads" / f"{cell_name}.json") as f:
        return json.load(f)


def train_cell(engine: dict | None = None, config: dict = TOY_GPT2) -> cells.Cell:
    spec = copy.deepcopy(_spec("gpt2-medium.pretrain-1k"))
    spec["model"].update(impl="full", fused_ln=False, compute_dtype=None)
    spec["loop"].update(log_every=0, warm_steps=1)
    if engine:
        spec["engine"] = engine
    return cells.Cell(
        name="toy.train", chips=1, config=dict(config),
        traffic={"generator": "lm_batches", "batch": 4, "seq_len": 32,
                 "rows": 128, "shuffle": True},
        spec=spec,
        end_to_end=[{"name": "train.tokens_per_s", "unit": "tokens/s"},
                    {"name": "setup_s", "unit": "s"}],
        per_layer=[])


def serve_cell(config: dict = TOY_BIGCODE) -> cells.Cell:
    spec = copy.deepcopy(_spec("starcoderbase-1b.serve-code"))
    spec["engine"]["serve_config"].update(slots=4, max_len=64, prefill_chunk=16,
                                          cache_kind="f32")
    spec["model"].update(impl="full", param_dtype="float32", compute_dtype=None)
    spec["warmup"] = [{"prompt_len": 49, "max_new_tokens": 2}]
    # float32 against float32: exact ties aside, the sound engine's gaps are 0
    spec["check"]["limits"] = {"served_token_gap": 1e-4, "served_mean_gap": 1e-6}
    return cells.Cell(
        name="toy.serve", chips=1, config=dict(config),
        traffic={"generator": "requests", "rate_per_s": 20.0,
                 "prompt_len": {"median": 16, "sigma": 0.6, "min": 4, "max": 48},
                 "output_len": {"median": 6, "sigma": 0.5, "min": 2, "max": 12}},
        spec=spec,
        end_to_end=[{"name": n, "unit": u} for n, u in (
            ("serve.tpot_p95_ms", "ms"),
            ("serve.tokens_per_s", "tokens/s"), ("setup_s", "s"))],
        per_layer=[])
