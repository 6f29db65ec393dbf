"""The `mimo_v2` cell's part of the yardstick at toy size on the CPU: its
counts against hand arithmetic, its driver end to end beside each of the cell's
controls, and its readers of the new counters and kernels."""

import json
import time
from pathlib import Path

import jax
import pytest

from benchmarks import cells, counts_mimo, kernel_ops, program_spans, run
from benchmarks.drivers import mimo_adapter
from benchmarks.drivers import serve_mimo as drv
from benchmarks.reference import mimo_v2 as ref
from benchmarks.tests import toy_mimo
from benchmarks.tools import control_mimo

CELL = toy_mimo.CELL


def _config() -> dict:
    return json.loads((cells.BENCH / "configs" / "mimo-v2.5.json").read_text())


def _run(cell, seed=2150000123, seconds=1.0, trace=False, tmp_path="/tmp"):
    return run.run_cell(cell, seed, seconds, trace, jax.devices()[:cell.chips],
                        time.perf_counter(), str(tmp_path))


def test_counts_against_hand_arithmetic():
    cfg = _config()
    # a full block: q 4096 x 12288, k 4096 x 768, v 4096 x 512, o 8192 x 4096, its norm
    assert counts_mimo.attention_layer_params(cfg, 0) == (
        4096 * (12288 + 768 + 512) + 8192 * 4096 + 4096) == 89_133_056
    # a window block: 8 K/V heads, and a sink a query head
    assert counts_mimo.attention_layer_params(cfg, 1) == (
        4096 * (12288 + 1536 + 1024) + 8192 * 4096 + 4096 + 64) == 94_376_000
    assert counts_mimo.expert_params(cfg) == 3 * 4096 * 2048 == 25_165_824
    assert counts_mimo.router_params(cfg) == 4096 * 256 + 256
    assert counts_mimo.ffn_layer_params(cfg, 0) == 3 * 4096 * 16384 + 4096 == 201_330_688
    assert counts_mimo.ffn_layer_params(cfg, 1) == 1_048_832 + 16 * 25_165_824 + 4096
    held = (2 * 89_133_056 + 5 * 94_376_000 + 201_330_688
            + 6 * (1_048_832 + 16 * 25_165_824 + 4096) + 2 * 19072 * 4096 + 4096)
    assert counts_mimo.param_count(cfg) == held == cfg["parameters"] == 3_429_955_392
    assert sum(a.size for a in jax.eval_shape(
        lambda: ref.init_weights(cfg, ref.seed_key(0))).values()) == held
    assert counts_mimo.layer_counts(cfg) == {"window": 5, "full": 2, "moe": 6}
    # one token's K and V: the head's own widths, whatever the lanes a key is stored in
    assert counts_mimo.kv_row_bytes(cfg, 0) == 4 * (192 + 128) * 2
    assert counts_mimo.kv_row_bytes(cfg, 1) == 8 * (192 + 128) * 2
    nothing = counts_mimo.decode_step_bytes(cfg, 0, 0, 0)
    f32 = 6 * (4096 * 256 + 256) + 5 * 64  # the routers and the sinks
    assert nothing == counts_mimo.weight_bytes_held(cfg) == (
        2 * (held - 19072 * 4096 - f32) + 4 * f32)
    step = counts_mimo.decode_step_bytes(cfg, 2 * 100_000, 5 * 12_800, 100)
    assert step - nothing == (200_000 * 2560 + 64_000 * 5120
                              + 100 * (2 * 2560 + 5 * 5120))
    assert 6.7e9 < nothing < 6.72e9  # 8.2 ms at 819 GB/s
    ops, moved = counts_mimo.decode_attn_counts(cfg, False, 100_000, 100)
    assert ops == 2 * 64 * 320 * 100_000 and moved == 100_000 * 2560 + 100 * 64 * 320 * 2
    ops, moved = counts_mimo.decode_attn_counts(cfg, True, 12_800, 100)
    assert moved == 12_800 * 5120 + 100 * 64 * 320 * 2


def test_the_configuration_file_is_the_published_one_cut_as_it_says():
    cfg = _config()
    bench = json.loads((cells.ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "mimo-v2.5")
    assert entry["source"] == cfg["source"] and set(entry["reduced"]) == set(cfg["reduced_why"])
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"], cfg["v_head_dim"],
            cfg["num_key_value_heads"], cfg["swa_num_key_value_heads"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["sliding_window"], cfg["num_experts_per_tok"]) == (
        4096, 64, 192, 128, 4, 8, 16384, 2048, 128, 8)
    assert (cfg["rope_theta"], cfg["swa_rope_theta"]) == (10000000, 10000)
    assert cfg["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 0, 1]
    assert cfg["moe_layer_freq"] == [0, 1, 1, 1, 1, 1, 1] and cfg["num_hidden_layers"] == 7
    dep = cfg["deployment"]
    assert dep["chips_per_layer"] == 16 and dep["n_routed_experts"] == 256 == 16 * cfg[
        "n_routed_experts"] and dep["vocab_size"] == 8 * cfg["vocab_size"]
    assert ref.rotary_dim(cfg) == 64 and ref.router_width(cfg) == 256
    assert ref.held_experts(cfg) == (0, 16)
    assert cfg["hybrid_override_pattern"] == mimo_adapter.pattern(cfg) == "FDWEWEWEWEFEWE"
    model = mimo_adapter.build_model(cfg, {"param_dtype": "bfloat16"})
    assert (model.held, model.num_experts, model.top_k, model.window) == ((0, 16), 256, 8, 128)
    caches = jax.eval_shape(lambda: model.init_decode_cache(128, 8192, "bf16"))
    assert caches[0].k.shape == (128, 8192, 4, 256) and caches[0].v.shape == (128, 8192, 4, 128)
    assert caches[2].k.shape == (128, 128, 8, 256) and caches[2].v.shape == (128, 128, 8, 128)
    by = model.cache_bytes(caches)
    assert by == {"cache_bytes_full": 2 * 128 * 8192 * 4 * 384 * 2,
                  "cache_bytes_window": 5 * 128 * 128 * 8 * 384 * 2}


def test_driver_result_line_and_correct(capsys):
    cell = toy_mimo.serve_cell()
    result = _run(cell)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 4
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end} == {
        "serve.tokens_per_s", "setup_s"}
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert {r["compared"] for r in lines if "compared" in r} == {
        "requests_not_finished", "token_count_mismatch", "served_token_gap",
        "served_mean_gap", "route_regret_mean"}
    info = next(r["info"] for r in lines if "info" in r)
    assert info["ramp_s"] == 0.5 and 0 < info["tokens_in_window"] < info["generated_tokens"]
    assert info["routings_checked"] > 0 and info["routings_flipped"] == 0.0


def _control_engine(monkeypatch, control: dict):
    """What `control_mimo._arm` does to the driver's engine for one control."""
    real = drv.build_engine
    monkeypatch.setattr(drv, "build_engine",
                        lambda cell, seed: real(cell, seed, **control.get("model", {})))
    return control_mimo.plant(control.get("plant"))


def _toy_control(name: str, control: dict) -> dict:
    """The cell's control at the toy's widths: 127 of 128 rows is 7 of 8, the
    whole head is 24 wide."""
    model = dict(control.get("model", {}))
    if "window" in model:
        model["window"] = toy_mimo.TOY_MIMO["sliding_window"] - 1
    if "rotary_dim" in model:
        model["rotary_dim"] = toy_mimo.TOY_MIMO["head_dim"]
    return {**control, "model": model}


CONTROLS = json.loads((cells.BENCH / "workloads" / f"{CELL}.json").read_text())["check"]["controls"]


@pytest.mark.parametrize("name", sorted(set(CONTROLS) - {"weights_fp8"}))
def test_each_control_of_the_cell_is_not_correct(name, monkeypatch):
    """Every `check.controls` entry of the cell but the storage type (which
    moves nothing in float32 at this size; PERF.md has its reading on the
    chip), built as `tools/control_mimo.py` builds it, under the toy's traffic
    with prompts longer than two chunks."""
    cell = toy_mimo.serve_cell()
    undo = _control_engine(monkeypatch, _toy_control(name, CONTROLS[name]))
    try:
        assert _run(cell, seconds=2.0)["correct"] is False
    finally:
        undo()


def test_the_controls_are_the_six_the_issue_names():
    assert set(CONTROLS) == {"weights_fp8", "fault_no_sink", "fault_window_127",
                             "fault_rope_whole_head", "fault_no_value_scale",
                             "fault_ring_forgets_chunk"}
    with pytest.raises(ValueError, match="no plant"):
        control_mimo.plant("nothing")


def test_step_bytes_follow_the_steps_own_counters():
    from tpudml.obs.tracer import Span

    cfg = _config()
    spec = json.loads((cells.BENCH / "workloads" / f"{CELL}.json").read_text())
    events = [Span("dispatch", "serve", ts, 1, args={
        "step": i, "active": a, "rows_full": 2 * rows, "rows_window": 5 * 128 * a})
        for i, (a, rows, ts) in enumerate([(100, 90_000, 10), (120, 110_000, 20), (5, 900, 90)])]
    steps = drv.step_counters(events, 0, 50)
    assert [s["active"] for s in steps] == [100, 120]
    want = (counts_mimo.decode_step_bytes(cfg, 180_000, 64_000, 100)
            + counts_mimo.decode_step_bytes(cfg, 220_000, 76_800, 120)) / 2
    assert drv.step_bytes_from_spans(cfg, spec, steps) == want
    assert drv.step_bytes_from_spans(cfg, spec, drv.step_counters(events, 200, 300)) is None
    bare = [Span("dispatch", "serve", 15, 1, args={"step": 0, "active": 3})]  # no cache counters
    assert drv.step_counters(bare, 0, 50) == []


def _ctx(cell, **host):
    class Trace:
        def median_program_s(self, pattern):
            return {"^jit_step$": 0.020, "^jit__serve_prefill_chunk$": 0.045}[pattern]

    return {"cell": cell, "trace": Trace(), "n_devices": 1,
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}, "host": host}


def test_readers_of_the_new_counters_and_kernels(monkeypatch):
    cell = cells.load_cell(CELL)
    dispatch = [["serve/dispatch", 0.1 * i, 0.001, {
        "step": i, "active": 100, "rows": r, "rows_full": 2 * (r + 100),
        "rows_window": 5 * 12_800, "cache_bytes_full": 6_442_450_944,
        "cache_bytes_window": 503_316_480}] for i, r in enumerate([100_000, 140_000])]
    commits = [["serve/commit", 0.1 * i + 0.05, 0.001, {
        "step": i, "moe_routed": 4800, "moe_held": 300, "experts_touched": 90,
        "expert_load_max": 9}] for i in range(2)]
    monkeypatch.setattr(program_spans, "of_cell", lambda ctx: dispatch + commits)
    ctx = _ctx(cell, decode_step_bytes=8.0e9, tpot_s=[0.030 + 0.001 * i for i in range(21)],
               decode_active=100, decode_rows_full=240_200, decode_rows_window=64_000)
    read = lambda name: run.read_layer_metric(name, ctx)  # noqa: E731
    assert read("serve.cache_rows_live.reasoning") == pytest.approx(
        100 * 240_200 / (128 * 8192 * 2))
    assert read("serve.window_cache_share") == pytest.approx(
        100 * 503_316_480 / (503_316_480 + 6_442_450_944))
    assert read("serve.moe_experts_touched") == pytest.approx(100 * 90 / (16 * 6))
    assert read("serve.moe_held_share") == pytest.approx(100 * 300 / 4800)
    assert read("serve.moe_load_max_over_mean") == pytest.approx(9 * 90 / 300)
    for name in ("serve.decode_device_ms", "serve.prefill_device_ms", "serve.decode_hbm"):
        assert read(f"{name}.reasoning") == read(name) > 0
    assert read("serve.decode_hbm.reasoning") == pytest.approx(100 * 8.0e9 / 819e9 / 0.020)
    assert read("serve.tpot_p95_ms.reasoning") == pytest.approx(49.0)
    # the kernels by their trace names: 100 calls in half a second
    monkeypatch.setattr(kernel_ops, "kernel_seconds", lambda ctx, kernel: (0.5, 100))
    _, moved = counts_mimo.decode_attn_counts(cell.config, False, 120_100, 100)
    assert read("decode_attn_roofline") == pytest.approx(100 * 100 * moved / 819e9 / 0.5)
    _, moved = counts_mimo.decode_attn_counts(cell.config, True, 12_800, 100)
    assert read("decode_attn_window_roofline") == pytest.approx(100 * 100 * moved / 819e9 / 0.5)
    # a program without the counters, a trace without the kernels: nothing to read, no error
    monkeypatch.setattr(kernel_ops, "kernel_seconds", lambda ctx, kernel: None)
    monkeypatch.setattr(program_spans, "of_cell",
                        lambda ctx: [["serve/dispatch", 0.1, 0.001, {"step": 0, "rows": 3}]])
    bare = _ctx(cell)
    for name in ("serve.cache_rows_live.reasoning", "serve.window_cache_share",
                 "serve.decode_hbm.reasoning", "serve.tpot_p95_ms.reasoning",
                 "decode_attn_roofline", "decode_attn_window_roofline"):
        assert run.read_layer_metric(name, bare) is None, name
    assert run.read_layer_metric("decode_attn_roofline", ctx) is None


def test_kernel_seconds_reads_the_trace_by_the_kernels_name(monkeypatch):
    events = {"devices": {"/device:TPU:0": {"modules": [], "ops": [
        ["decode_attn.3", 1.0, 0.010], ["decode_attn_window.7", 1.02, 0.001],
        ["decode_attn.3", 1.5, 0.012], ["fusion.1", 1.6, 0.5], ["decode_attn.4", 2.9, 0.2]]}},
        "host": [["bench:trace_window", 0.9, 2.1]]}
    monkeypatch.setattr(kernel_ops, "_events", lambda ctx: events)
    assert kernel_ops.kernel_seconds({}, "decode_attn") == (pytest.approx(0.022), 2)  # one cut off
    assert kernel_ops.kernel_seconds({}, "decode_attn_window") == (pytest.approx(0.001), 1)
    assert kernel_ops.kernel_seconds({}, "flash_fwd") is None
    peaks = {"peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
    assert kernel_ops.roofline_share(peaks, "decode_attn", 1e9, 819e9 * 0.0055) == pytest.approx(50)
    monkeypatch.setattr(kernel_ops, "_events", lambda ctx: None)
    assert kernel_ops.kernel_seconds({}, "decode_attn") is None


def test_the_cell_loads_with_the_metrics_it_can_report():
    cell = cells.load_cell(CELL)
    bench = json.loads((cells.ROOT / "BENCHMARK.json").read_text())
    assert cell.driver == "serve_mimo" and cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {"serve.tokens_per_s", "setup_s"}
    accepted = {m["name"] for m in bench["per_layer"]
                if m["moves"] == "serve.tokens_per_s" and "workloads" not in m}
    new = {"serve.moe_experts_touched", "serve.moe_load_max_over_mean", "serve.moe_held_share",
           "serve.tpot_p95_ms.reasoning", "serve.decode_device_ms.reasoning",
           "serve.prefill_device_ms.reasoning", "serve.decode_hbm.reasoning",
           "serve.cache_rows_live.reasoning", "serve.window_cache_share",
           "decode_attn_roofline", "decode_attn_window_roofline"}
    assert {m["name"] for m in cell.per_layer} == accepted | new
    for m in cell.per_layer:
        assert m["moves"] == "serve.tokens_per_s"
        assert (Path(cells.BENCH) / "layer_metrics" / f"{m['name']}.py").exists()
    assert set(cell.spec["kernels"]) == {"decode_attn", "decode_attn_window"}
    serve = cell.spec["engine"]["serve_config"]
    assert (serve["slots"], serve["max_len"], serve["prefill_chunk"]) == (128, 8192, 512)
    t = cell.traffic
    assert t["prompt_len"] == {"median": 512, "sigma": 1.0, "min": 32, "max": 4096}
    assert t["output_len"] == {"median": 512, "sigma": 0.7, "min": 64, "max": 2048}
    assert t["shuffle_block"] == 4 and cell.spec["ramp_s"] >= 30
    assert t["rate_per_s"] == pytest.approx(0.8 * t["knee"]["rate_per_s"])
    longest = t["prompt_len"]["max"] + t["output_len"]["max"]
    assert max(cell.spec["check"]["pad_to"]) >= longest <= serve["max_len"]
    assert cell.spec["warmup"][0]["prompt_len"] == t["prompt_len"]["max"]  # every chunk offset
