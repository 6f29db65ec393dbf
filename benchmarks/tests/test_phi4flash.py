"""The `phi4flash` cell's part of the yardstick at toy size on the CPU: its
counts against hand arithmetic, its configuration against the catalog's, its
driver end to end beside each of the cell's controls, and its readers of the new
counters and kernels."""

import json
import time
from pathlib import Path

import jax
import pytest

from benchmarks import cells, counts_phi4flash, kernel_ops, program_spans, run
from benchmarks.drivers import phi4flash_adapter
from benchmarks.drivers import serve_phi4flash as drv
from benchmarks.reference import phi4flash as ref
from benchmarks.tests import toy_phi4flash
from benchmarks.tools import control_phi4flash

CELL = toy_phi4flash.CELL
# The catalog's `config` for this model (`model-configs`: architectures.jsonl).
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 10240,
    "layer_norm_eps": 1e-05, "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40, "num_hidden_layers": 32,
    "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512,
    "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False, "vocab_size": 200064}


def _config() -> dict:
    return json.loads((cells.BENCH / "configs" / "phi-4-mini-flash-reasoning.json").read_text())


def _run(cell, seed=2150000123, seconds=1.0, trace=False, tmp_path="/tmp"):
    return run.run_cell(cell, seed, seconds, trace, jax.devices()[:cell.chips],
                        time.perf_counter(), str(tmp_path))


def test_counts_against_hand_arithmetic():
    cfg = _config()
    d, e, f, v = 2560, 5120, 10240, 200064
    mamba = d * 2 * e + 4 * e + e + e * (160 + 32) + 160 * e + e + e * 16 + e + e * d
    attention = 2 * (d * d + d) + 2 * (d * 1280 + 1280) + 4 * 64 + 128
    cross = 2 * (d * d + d) + 4 * 64 + 128
    mlp, norms = 3 * d * f, 4 * d
    assert (mamba, attention, 2 * d * e, cross, mlp) == (
        41_241_600, 19_668_864, 26_214_400, 13_112_704, 78_643_200)
    held = 9 * mamba + 9 * attention + 7 * 2 * d * e + 7 * cross + 32 * (mlp + norms) \
        + v * d + 2 * d
    assert counts_phi4flash.param_count(cfg) == held == 3_852_562_944
    assert sum(a.size for a in jax.eval_shape(
        lambda: ref.init_weights(cfg, ref.seed_key(0))).values()) == held
    assert counts_phi4flash.layer_counts(cfg) == {"S": 9, "W": 8, "F": 1, "G": 7, "X": 7}
    assert counts_phi4flash.kv_row_bytes(cfg) == 20 * 64 * 2 * 2 == 5120
    assert counts_phi4flash.prefill_entries(cfg) == (35, 64)
    f32 = 9 * (e * 16 + 2 * e) + 16 * 4 * 64  # A_log, D, the step-size bias; the lambdas
    nothing = counts_phi4flash.decode_step_bytes(cfg, 0, 0, 0, 0)
    assert nothing == counts_phi4flash.weight_bytes_held(cfg) == 2 * (held - f32) + 4 * f32
    assert 7.70e9 < nothing < 7.72e9  # 9.4 ms at 819 GB/s
    state = 64 * 9 * (16 * e * 4 + 3 * e * 2)
    step = counts_phi4flash.decode_step_bytes(cfg, 8 * 70_400, 8 * 32_768, state, 64)
    assert step - nothing == (8 * 70_400 + 8 * 32_768) * 5120 + 2 * state + 64 * 9 * 5120
    assert counts_phi4flash.shared_read_bytes(cfg, 70_400, 8 * 70_400) == 7 * 70_400 * 5120
    # the trunk a prefill chunk runs, in matmul parameters: 1,872 M of 3,340 M
    prefilled = 9 * mamba + 8 * attention + 17 * mlp + 2 * (d * 1280 + 1280)
    assert round(prefilled / 1e6) == 1872 and round((held - v * d) / 1e6) == 3340
    ops, moved = counts_phi4flash.decode_attn_counts(cfg, 70_400, 64)
    assert ops == 2 * 20 * (64 + 64 + 128 + 128) * 70_400
    assert moved == 70_400 * 5120 + 64 * (40 * 64 + 40 * 128) * 2


def test_the_configuration_file_is_the_published_one_whole():
    cfg = _config()
    bench = json.loads((cells.ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "phi-4-mini-flash-reasoning")
    assert entry["source"] == cfg["source"] and entry["reduced"] == []
    assert {k: cfg[k] for k in PUBLISHED} == PUBLISHED  # every key of the catalog, as published
    assumed = cfg["assumed"]
    assert (assumed["mamba_d_state"], assumed["mamba_d_conv"], assumed["mamba_expand"],
            assumed["mamba_dt_rank"]) == (16, 4, 2, 160)
    assert {"biases", "positions", "differential_attention", "memory_layer",
            "shared_cache_layer", "window", "initialisation"} <= set(assumed["why"])
    dep = cfg["deployment"]
    assert (dep["chips_per_layer"], dep["num_hidden_layers"], dep["vocab_size"]) == (
        1, 32, 200064)
    assert ref.sizes(cfg) == {"d": 2560, "heads": 40, "kv_heads": 20, "head": 64, "inner": 5120,
                              "state": 16, "conv": 4, "dt_rank": 160}
    kinds = "".join(ref.layer_kind(cfg, i) for i in range(32))
    assert kinds == "SW" * 8 + "SF" + "GX" * 7
    assert [round(ref.lambda_init(i), 4) for i in (0, 1, 17, 31)] == [0.2, 0.3555, 0.7963, 0.7999]
    model = phi4flash_adapter.build_model(cfg, {"param_dtype": "bfloat16"})
    assert model.pattern == "SDWD" * 8 + "SDFD" + "GDXD" * 7 and model.prefill_entries == 35
    assert (model.window, model.ssm_inner, model.dt_rank, model.state_size, model.tied,
            model.norm, model.differential, model.pair_rows) == (
        512, 5120, 160, 16, True, "layer", True, True)


def test_driver_result_line_and_correct(capsys):
    cell = toy_phi4flash.serve_cell()
    result = _run(cell)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 4
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end} == {
        "serve.tokens_per_s", "setup_s"}
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert {r["compared"] for r in lines if "compared" in r} == {
        "requests_not_finished", "token_count_mismatch", "served_token_gap", "served_mean_gap"}
    info = next(r["info"] for r in lines if "info" in r)
    assert info["ramp_s"] == 0.5 and 0 < info["tokens_in_window"] < info["generated_tokens"]
    assert info["tokens_checked"] > 0 and info["agree_with_reference"] > 0.99


def _control_engine(monkeypatch, control: dict):
    """What `control_phi4flash._arm` does to the driver's engine for one control."""
    undo = control_phi4flash.plant(control.get("plant"))
    real = drv.build_engine
    monkeypatch.setattr(drv, "build_engine",
                        lambda cell, seed: real(cell, seed, **control.get("model", {})))
    return undo


def _toy_control(control: dict) -> dict:
    """The cell's control at the toy's widths: 511 of 512 rows is 7 of 8."""
    model = dict(control.get("model", {}))
    if "window" in model:
        model["window"] = toy_phi4flash.TOY_PHI["sliding_window"] - 1
    return {**control, "model": model}


CONTROLS = json.loads((cells.BENCH / "workloads" / f"{CELL}.json").read_text())["check"]["controls"]
STORAGE = {"weights_fp8", "state_bf16"}  # move nothing in float32 at this size: PERF.md, on the chip


@pytest.mark.parametrize("name", sorted(set(CONTROLS) - STORAGE))
def test_each_control_of_the_cell_is_not_correct(name, monkeypatch):
    """Every `check.controls` entry of the cell but the storage types, built as
    `tools/control_phi4flash.py` builds it, under the toy's traffic with prompts
    longer than two chunks and slots taken over."""
    cell = toy_phi4flash.serve_cell()
    undo = _control_engine(monkeypatch, _toy_control(CONTROLS[name]))
    try:
        assert _run(cell, seconds=2.0)["correct"] is False
    finally:
        undo()


def test_the_controls_are_those_the_issue_names():
    assert set(CONTROLS) == {
        "weights_fp8", "fault_lambda_of_layer0", "fault_no_subln", "fault_window_511",
        "fault_memory_after_gate", "fault_cross_reads_own_kv", "fault_no_state_reset",
        "fault_prefill_skips_kv", "state_bf16"}
    assert {c["plant"] for c in CONTROLS.values() if "plant" in c} == set(control_phi4flash.PLANTS)
    assert [n for n, c in CONTROLS.items() if c.get("reported_only")] == ["state_bf16"]
    with pytest.raises(ValueError, match="no plant"):
        control_phi4flash.plant("nothing")


def test_step_bytes_follow_the_steps_own_counters():
    from tpudml.obs.tracer import Span

    cfg = _config()
    spec = json.loads((cells.BENCH / "workloads" / f"{CELL}.json").read_text())
    events = [Span("dispatch", "serve", ts, 1, args={
        "step": i, "active": a, "rows_full": rows, "rows_read_full": 8 * rows,
        "rows_window": 8 * 512 * a, "state_bytes": 1000 * a})
        for i, (a, rows, ts) in enumerate([(50, 45_000, 10), (60, 55_000, 20), (5, 900, 90)])]
    steps = drv.step_counters(events, 0, 50)
    assert [s["active"] for s in steps] == [50, 60]
    assert drv.step_bytes(cfg, spec, steps[0]) == counts_phi4flash.decode_step_bytes(
        cfg, 360_000, 204_800, 50_000, 50)
    bare = [Span("dispatch", "serve", 15, 1, args={"step": 0, "active": 3, "rows_full": 9})]
    assert drv.step_counters(bare, 0, 50) == []  # an earlier program: no shared-read counter


def _ctx(cell, **host):
    class Trace:
        def median_program_s(self, pattern):
            return {"^jit_step$": 0.027, "^jit__serve_prefill_chunk$": 0.045}[pattern]

    return {"cell": cell, "trace": Trace(), "n_devices": 1,
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}, "host": host}


NEW = {"serve.tpot_p95_ms.reasoning-4k", "serve.decode_device_ms.reasoning-4k",
       "serve.prefill_device_ms.reasoning-4k", "serve.decode_hbm.reasoning-4k",
       "serve.cache_rows_live.reasoning-4k", "serve.shared_read_share",
       "serve.prefill_trunk_share", "decode_attn_roofline.reasoning-4k",
       "decode_attn_shared_roofline", "decode_attn_window_roofline.reasoning-4k"}


def test_readers_of_the_new_counters_and_kernels(monkeypatch):
    cell = cells.load_cell(CELL)
    state = 50 * 9 * (16 * 5120 * 4 + 3 * 5120 * 2)
    dispatch = [["serve/dispatch", 0.1 * i, 0.001, {
        "step": i, "active": 50, "rows": r, "rows_full": r + 50, "rows_read_full": 8 * (r + 50),
        "rows_window": 8 * 25_000, "state_bytes": state, "cache_bytes_full": 1_342_177_280,
        "cache_bytes_window": 1_342_177_280}] for i, r in enumerate([50_000, 60_000])]
    admits = [["serve/admit", 0.03, 0.001, {"rid": 1, "trunk_prefilled": 35}]]
    monkeypatch.setattr(program_spans, "of_cell", lambda ctx: dispatch + admits)
    ctx = _ctx(cell, decode_step_bytes=12.0e9, tpot_s=[0.030 + 0.001 * i for i in range(21)],
               decode_active=50, decode_rows_full=55_050, decode_rows_read_full=8 * 55_050,
               decode_rows_window=200_000, decode_state_bytes=state)
    read = lambda name: run.read_layer_metric(name, ctx)  # noqa: E731
    assert read("serve.cache_rows_live.reasoning-4k") == pytest.approx(100 * 55_050 / (64 * 4096))
    steps = [counts_phi4flash.decode_step_bytes(cell.config, 8 * (r + 50), 200_000, state, 50)
             for r in (50_000, 60_000)]
    assert read("serve.shared_read_share") == pytest.approx(
        100 * 7 * 110_100 * 5120 / sum(steps))
    assert 15 < read("serve.shared_read_share") < 25
    assert read("serve.prefill_trunk_share") == pytest.approx(100 * 35 / 64)
    assert read("serve.decode_device_ms.reasoning-4k") == pytest.approx(27.0)
    assert read("serve.prefill_device_ms.reasoning-4k") == pytest.approx(45.0)
    assert read("serve.decode_hbm.reasoning-4k") == pytest.approx(100 * 12.0e9 / 819e9 / 0.027)
    assert read("serve.tpot_p95_ms.reasoning-4k") == pytest.approx(49.0)
    # the kernels by their trace names: 70 calls in half a second
    monkeypatch.setattr(kernel_ops, "kernel_seconds", lambda ctx, kernel: (0.5, 70))
    _, moved = counts_phi4flash.decode_attn_counts(cell.config, 55_050, 50)
    for name in ("decode_attn_roofline.reasoning-4k", "decode_attn_shared_roofline"):
        assert read(name) == pytest.approx(100 * 70 * moved / 819e9 / 0.5)
    _, moved = counts_phi4flash.decode_attn_counts(cell.config, 25_000, 50)
    assert read("decode_attn_window_roofline.reasoning-4k") == pytest.approx(
        100 * 70 * moved / 819e9 / 0.5)
    # the parent's program (no such counters), a trace without the kernels: None, no error
    monkeypatch.setattr(kernel_ops, "kernel_seconds", lambda ctx, kernel: None)
    monkeypatch.setattr(program_spans, "of_cell", lambda ctx: [
        ["serve/dispatch", 0.1, 0.001, {"step": 0, "rows": 3, "active": 1}],
        ["serve/admit", 0.2, 0.001, {"rid": 0}]])
    bare = _ctx(cell)
    for name in sorted(NEW - {"serve.decode_device_ms.reasoning-4k",
                              "serve.prefill_device_ms.reasoning-4k"}):
        assert run.read_layer_metric(name, bare) is None, name
    assert run.read_layer_metric("decode_attn_shared_roofline", ctx) is None


def test_the_cell_loads_with_the_metrics_it_can_report():
    cell = cells.load_cell(CELL)
    bench = json.loads((cells.ROOT / "BENCHMARK.json").read_text())
    assert cell.driver == "serve_phi4flash" and cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {"serve.tokens_per_s", "setup_s"}
    accepted = {m["name"] for m in bench["per_layer"]
                if m["moves"] == "serve.tokens_per_s" and "workloads" not in m}
    assert accepted == {"serve.ttft_p95_ms", "serve.queue_wait_p95_ms",
                        "serve.stage_lateness_p50_ms", "serve.slot_occupancy"}
    assert {m["name"] for m in cell.per_layer} == accepted | NEW
    for m in cell.per_layer:
        assert m["moves"] == "serve.tokens_per_s"
        assert (Path(cells.BENCH) / "layer_metrics" / f"{m['name']}.py").exists()
    assert [m["workloads"] for m in bench["per_layer"] if m["name"] in NEW] == [[CELL]] * len(NEW)
    assert set(cell.spec["kernels"]) == {"decode_attn", "decode_attn_shared", "decode_attn_window"}
    serve = cell.spec["engine"]["serve_config"]
    assert (serve["slots"], serve["max_len"], serve["prefill_chunk"], serve["cache_kind"],
            serve["cache_layout"]) == (64, 4096, 512, "bf16", "dense")
    t = cell.traffic
    assert t["prompt_len"] == {"median": 512, "sigma": 1.0, "min": 32, "max": 2048}
    assert t["output_len"] == {"median": 512, "sigma": 0.7, "min": 64, "max": 2048}
    assert t["shuffle_block"] == 4 and 40 <= cell.spec["ramp_s"] <= 45
    assert 0.7 * t["knee"]["rate_per_s"] <= t["rate_per_s"] <= 0.8 * t["knee"]["rate_per_s"] + 1e-9
    longest = t["prompt_len"]["max"] + t["output_len"]["max"]
    assert max(cell.spec["check"]["pad_to"]) == longest == serve["max_len"]  # fills a slot exactly
    assert cell.spec["warmup"][0]["prompt_len"] == t["prompt_len"]["max"]  # every chunk offset
    assert cell.spec["check"]["sample"] == 64 and cell.spec["trace"] == {"start_s": 20.0,
                                                                           "seconds": 2.0}
