"""The `deepseek_v2` cell's part of the yardstick at toy size on the CPU: its
configuration file against the published one, its counts against hand arithmetic,
its driver end to end beside each of the cell's controls, the accepted readers
on its spans, and the new kernel by its trace name."""

import json
import time
from pathlib import Path

import jax
import pytest

from benchmarks import cells, counts_deepseek_v2, kernel_ops, program_spans, run
from benchmarks.drivers import deepseek_v2_adapter as adapter
from benchmarks.drivers import serve_deepseek_v2 as drv
from benchmarks.reference import deepseek_v2 as ref
from benchmarks.tests import toy_deepseek_v2 as toy
from benchmarks.tools import control_deepseek_v2 as control

CELL = toy.CELL

# The catalog row's `config` (model-configs guide, `DeepSeek-V2`): the published
# config.json without the keys that say nothing about the model's shape.
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 12288, "kv_lora_rank": 512,
    "max_position_embeddings": 163840, "model_type": "deepseek_v2",
    "moe_intermediate_size": 1536, "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 160,
    "n_shared_experts": 2, "norm_topk_prob": False, "num_attention_heads": 128,
    "num_experts_per_tok": 6, "num_hidden_layers": 60, "num_key_value_heads": 128,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
                     "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 16, "scoring_func": "softmax", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 3, "topk_method": "group_limited_greedy",
    "v_head_dim": 128, "vocab_size": 102400}
REDUCED = {"num_hidden_layers": 5, "n_routed_experts": 20, "vocab_size": 12800}


def _config() -> dict:
    return json.loads((cells.BENCH / "configs" / "deepseek-v2.json").read_text())


def _run(cell, seed=2150000123, seconds=1.0, trace=False, tmp_path="/tmp"):
    return run.run_cell(cell, seed, seconds, trace, jax.devices()[:cell.chips],
                        time.perf_counter(), str(tmp_path))


def test_the_configuration_file_is_the_published_one_cut_as_it_says():
    cfg = _config()
    bench = json.loads((cells.ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "deepseek-v2")
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/deepseek-ai/DeepSeek-V2/blob/main/config.json")
    assert set(entry["reduced"]) == set(cfg["reduced_why"]) == set(REDUCED)
    for key, published in PUBLISHED.items():  # every key, unchanged but for the three cuts
        assert cfg[key] == REDUCED.get(key, published), key
    dep = cfg["deployment"]
    assert {k: dep[k] for k in REDUCED} == {k: PUBLISHED[k] for k in REDUCED}
    assert (dep["chips_per_layer"], dep["held_first"], dep["held_group"]) == (8, 0, 0)
    assert dep["n_routed_experts"] == 8 * cfg["n_routed_experts"] == cfg["n_group"] * 20
    assert dep["vocab_size"] == 8 * cfg["vocab_size"]
    assert set(cfg["assumed"]) >= {"weights", "router", "rope_pairing", "cache_row"}
    assert ref.router_width(cfg) == 160 and ref.held_experts(cfg) == (0, 20)
    assert [ref.is_moe(cfg, i) for i in range(5)] == [False, True, True, True, True]
    assert cfg["hybrid_override_pattern"] == adapter.pattern(cfg) == "LDLELELELE"
    model = adapter.build_model(cfg, {"param_dtype": "bfloat16"})
    assert (model.held, model.num_experts, model.top_k, model.moe_groups, model.moe_scoring) == (
        (0, 20), 160, 6, (8, 3), "softmax")
    assert (model.routed_scale, model.norm_topk, model.shared_dim, model.eps) == (
        16.0, False, 3072, 1e-6)
    assert model.yarn == (40, 4096, 32, 1, 0.707, 0.707)
    mixer = model._mixer("L")
    assert (mixer.q_rank, mixer.kv_rank, mixer.nope_dim, mixer.rope_dim, mixer.v_dim,
            mixer.num_heads, mixer.row_width) == (1536, 512, 128, 64, 128, 128, 576)
    caches = jax.eval_shape(lambda: model.init_decode_cache(256, 4096, "bf16"))
    assert [getattr(c, "rows", c) is None for c in caches] == [False, True] * 5
    assert caches[0].rows.shape == (256, 4096, 640)  # 576 values in whole 128-lane tiles
    assert model.cache_bytes(caches)["cache_bytes_latent"] == 5 * 256 * 4096 * 640 * 2


def test_counts_against_hand_arithmetic():
    cfg = _config()
    # W_DQ 5120 x 1536, its norm, W_UQ 1536 x 128 x 192, W_DKV 5120 x 576, its norm,
    # W_UKV 512 x 128 x 256, W_O 16384 x 5120, the layer's norm
    assert counts_deepseek_v2.attention_layer_params(cfg) == (
        7_864_320 + 1536 + 37_748_736 + 2_949_120 + 512 + 16_777_216 + 83_886_080 + 5120
    ) == 149_232_640
    assert counts_deepseek_v2.expert_params(cfg) == 3 * 5120 * 1536 == 23_592_960
    assert counts_deepseek_v2.router_params(cfg) == 5120 * 160 == 819_200
    assert counts_deepseek_v2.shared_params(cfg) == 3 * 5120 * 3072 == 47_185_920
    assert counts_deepseek_v2.ffn_layer_params(cfg, 0) == 3 * 5120 * 12288 + 5120 == 188_748_800
    assert counts_deepseek_v2.ffn_layer_params(cfg, 1) == (
        819_200 + 47_185_920 + 20 * 23_592_960 + 5120)
    # the issue's sums: layer 0 337,981,440, an expert layer 669,102,080, the rest 131,077,120
    assert 149_232_640 + 188_748_800 == 337_981_440
    assert 149_232_640 + 819_200 + 47_185_920 + 471_859_200 + 5120 == 669_102_080
    held = 337_981_440 + 4 * 669_102_080 + 2 * 12800 * 5120 + 5120
    assert counts_deepseek_v2.param_count(cfg) == held == cfg["parameters"] == 3_145_466_880
    assert sum(a.size for a in jax.eval_shape(
        lambda: ref.init_weights(cfg, ref.seed_key(0))).values()) == held
    assert counts_deepseek_v2.latent_row_bytes(cfg) == 1152  # against 128 x (192 + 128) x 2
    outside = counts_deepseek_v2.weight_bytes_outside_experts(cfg)
    routers = 4 * 819_200
    assert outside == 2 * (held - 12800 * 5120 - routers - 4 * 20 * 23_592_960) + 4 * routers
    nothing = counts_deepseek_v2.decode_step_bytes(cfg, 0, 0, 0)
    assert nothing == outside == 2_391_541_760  # 2.9 ms at 819 GB/s
    step = counts_deepseek_v2.decode_step_bytes(cfg, 5 * 180_000, 165, 78)
    assert step - nothing == 78 * 23_592_960 * 2 + 900_000 * 1152 + 165 * 5 * 1152
    whole = counts_deepseek_v2.decode_step_bytes(cfg, 0, 0, 80)
    assert 6.15e9 < whole < 6.17e9  # every held expert: 7.5 ms at 819 GB/s
    ops, moved = counts_deepseek_v2.decode_attn_counts(cfg, 180_000, 165)
    assert ops == 2 * 128 * (576 + 512) * 180_000
    assert moved == 180_000 * 1152 + 165 * 128 * 1088 * 2
    assert 240 < ops / (180_000 * 1152) < 243  # beside the v5e's ridge of 240


def test_the_cell_loads_with_the_metrics_it_can_report():
    cell = cells.load_cell(CELL)
    bench = json.loads((cells.ROOT / "BENCHMARK.json").read_text())
    assert cell.driver == "serve_deepseek_v2" and cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {"serve.tokens_per_s", "setup_s"}
    accepted = {m["name"] for m in bench["per_layer"]
                if m["moves"] == "serve.tokens_per_s" and "workloads" not in m}
    assert accepted == {"serve.ttft_p95_ms", "serve.queue_wait_p95_ms",
                        "serve.stage_lateness_p50_ms", "serve.slot_occupancy"}
    # accepted readers that list their cells, this one appended to each list: no
    # new `per_layer` entry (benchmarks/tests/test_pass_log.py pins the list's tail)
    listed = {"serve.moe_experts_touched", "serve.moe_load_max_over_mean", "serve.moe_held_share",
              "serve.tpot_p95_ms.reasoning", "serve.decode_device_ms.reasoning",
              "serve.prefill_device_ms.reasoning", "serve.decode_hbm.reasoning"}
    assert {m["name"] for m in cell.per_layer} == accepted | listed
    for m in cell.per_layer:
        assert m["moves"] == "serve.tokens_per_s"
        assert (Path(cells.BENCH) / "layer_metrics" / f"{m['name']}.py").exists()
    for m in bench["per_layer"]:
        if m["name"] in listed:
            assert m["workloads"].count(CELL) == 1
        else:
            assert CELL not in m.get("workloads", [])
    assert set(cell.spec["kernels"]) == {"decode_attn_latent"}
    serve = cell.spec["engine"]["serve_config"]
    assert serve == {"slots": 256, "max_len": 4096, "prefill_chunk": 512, "cache_kind": "bf16",
                     "cache_layout": "dense"}
    t = cell.traffic
    assert t["prompt_len"] == {"median": 512, "sigma": 1.0, "min": 32, "max": 2048}
    assert t["output_len"] == {"median": 512, "sigma": 0.7, "min": 64, "max": 2048}
    assert t["shuffle_block"] == 4 and cell.spec["ramp_s"] >= 40
    assert t["rate_per_s"] == pytest.approx(0.8 * t["knee"]["rate_per_s"])
    longest = t["prompt_len"]["max"] + t["output_len"]["max"]
    assert max(cell.spec["check"]["pad_to"]) >= longest == serve["max_len"]
    assert min(cell.spec["check"]["pad_to"]) >= t["output_len"]["max"]
    assert cell.spec["warmup"][0]["prompt_len"] == t["prompt_len"]["max"]  # every chunk offset
    assert cell.spec["check"]["sample"] == 64
    assert set(cell.spec["check"]["limits"]) == {"served_token_gap", "served_mean_gap",
                                                 "route_regret_mean"}


def test_driver_result_line_and_correct(capsys):
    cell = toy.serve_cell()
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


CONTROLS = json.loads((cells.BENCH / "workloads" / f"{CELL}.json").read_text())["check"]["controls"]


@pytest.mark.parametrize("name", sorted(set(CONTROLS) - {"weights_fp8"}))
def test_each_control_of_the_cell_is_not_correct(name, monkeypatch):
    """Every `check.controls` entry of the cell but the storage type (which moves
    nothing in float32 at this size; PERF.md has its reading on the chip), built as
    `tools/control_deepseek_v2.py` builds it, under the toy's traffic with prompts
    longer than two chunks."""
    cell = toy.serve_cell()
    entry = CONTROLS[name]
    real = drv.build_engine
    monkeypatch.setattr(drv, "build_engine",
                        lambda cell, seed: real(cell, seed, **entry.get("model", {})))
    undo = control.plant(entry.get("plant"))
    try:
        assert _run(cell, seconds=2.0)["correct"] is False
    finally:
        undo()


def test_the_controls_are_the_eight_the_issue_names():
    assert set(CONTROLS) == {"weights_fp8", "fault_no_mscale", "fault_plain_rope",
                             "fault_no_groups", "fault_no_routed_scale", "fault_norm_topk",
                             "fault_latent_before_norm", "fault_key_before_rope"}
    assert {c["plant"] for c in CONTROLS.values() if "plant" in c} == set(control.PLANTS)
    with pytest.raises(ValueError, match="no plant"):
        control.plant("nothing")
    from tpudml.nn import attention

    before = (attention.yarn_inv_freq, attention.LatentAttention.__dict__["_scale"],
              attention.LatentAttention.latent_rows)
    for name in control.PLANTS:
        control.plant(name)()
    assert before == (attention.yarn_inv_freq, attention.LatentAttention.__dict__["_scale"],
                      attention.LatentAttention.latent_rows)  # every plant comes out again


def test_step_bytes_follow_the_steps_own_counters():
    from tpudml.obs.tracer import Span

    cfg = _config()
    spec = json.loads((cells.BENCH / "workloads" / f"{CELL}.json").read_text())
    shape = [(160, 170_000, 70, 10), (170, 190_000, 78, 20), (5, 900, 4, 90)]
    events = [Span("dispatch", "serve", ts, 1, args={
        "step": i, "active": a, "rows_latent": 5 * rows}) for i, (a, rows, _, ts) in enumerate(shape)]
    events += [Span("commit", "serve", ts + 30, 1, args={"step": i, "experts_touched": e})
               for i, (_, _, e, ts) in enumerate(shape)]
    steps = drv.step_counters(events, 0, 50)
    assert [(s["active"], s["experts_touched"]) for s in steps] == [(160, 70), (170, 78)]
    want = (counts_deepseek_v2.decode_step_bytes(cfg, 850_000, 160, 70)
            + counts_deepseek_v2.decode_step_bytes(cfg, 950_000, 170, 78)) / 2
    assert drv.step_bytes_from_spans(cfg, spec, steps) == want
    assert drv.step_bytes_from_spans(cfg, spec, drv.step_counters(events, 200, 300)) is None
    bare = [Span("dispatch", "serve", 15, 1, args={"step": 0, "active": 3}),
            Span("commit", "serve", 16, 1, args={"step": 0, "tokens": 3})]  # the parent's spans
    assert drv.step_counters(bare, 0, 50) == []


def _ctx(cell, **host):
    class Trace:
        def median_program_s(self, pattern):
            return {"^jit_step$": 0.020, "^jit__serve_prefill_chunk$": 0.045}[pattern]

    return {"cell": cell, "trace": Trace(), "n_devices": 1,
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}, "host": host}


def test_the_accepted_readers_read_this_cell(monkeypatch):
    """The seven accepted readers whose `workloads` lists this cell was appended
    to, on its spans and host numbers; what no accepted reader reads (live latent
    rows, the group hit share, the kernel's counts) is on the driver's info line."""
    cell = cells.load_cell(CELL)
    dispatch = [["serve/dispatch", 0.1 * i, 0.001, {
        "step": i, "active": 165, "rows": r, "rows_latent": 5 * (r + 165),
        "cache_bytes_latent": 6_710_886_400}] for i, r in enumerate([170_000, 190_000])]
    commits = [["serve/commit", 0.1 * i + 0.05, 0.001, {
        "step": i, "moe_routed": 165 * 6 * 4, "moe_held": 500, "experts_touched": 78,
        "expert_load_max": 14, "moe_group_hit": 250}] for i in range(2)]
    monkeypatch.setattr(program_spans, "of_cell", lambda ctx: dispatch + commits)
    ctx = _ctx(cell, decode_step_bytes=7.0e9, tpot_s=[0.030 + 0.001 * i for i in range(21)])
    read = lambda name: run.read_layer_metric(name, ctx)  # noqa: E731
    assert read("serve.moe_experts_touched") == pytest.approx(100 * 78 / (20 * 4))
    assert read("serve.moe_held_share") == pytest.approx(100 * 500 / 3960)
    assert read("serve.moe_load_max_over_mean") == pytest.approx(14 * 78 / 500)
    assert read("serve.decode_device_ms.reasoning") == pytest.approx(20.0)
    assert read("serve.prefill_device_ms.reasoning") == pytest.approx(45.0)
    assert read("serve.decode_hbm.reasoning") == pytest.approx(100 * 7.0e9 / 819e9 / 0.020)
    assert read("serve.tpot_p95_ms.reasoning") == pytest.approx(49.0)
    # a program without the counters (the parent's spans): nothing to read, no error
    monkeypatch.setattr(program_spans, "of_cell", lambda ctx: [
        ["serve/dispatch", 0.1, 0.001, {"step": 0, "rows": 3, "active": 2}],
        ["serve/commit", 0.15, 0.001, {"step": 0, "tokens": 2}]])
    bare = _ctx(cell)
    for m in cell.per_layer:
        if "moe_" in m["name"] or m["name"] in ("serve.decode_hbm.reasoning",
                                                "serve.tpot_p95_ms.reasoning"):
            assert run.read_layer_metric(m["name"], bare) is None, m["name"]


def test_the_info_line_carries_what_no_accepted_reader_reads():
    cell = cells.load_cell(CELL)
    steps = [{"active": 160, "rows_latent": 5 * 170_000, "moe_routed": 160 * 6 * 4,
              "moe_group_hit": 250, "experts_touched": 70},
             {"active": 170, "rows_latent": 5 * 190_000, "moe_routed": 170 * 6 * 4,
              "moe_group_hit": 245, "experts_touched": 78}]
    info = drv.latent_step_means(cell, steps)
    assert info["decode_active"] == 165 and info["decode_rows_latent"] == 5 * 180_000
    assert info["latent_rows_live_share"] == pytest.approx(100 * 180_000 / (256 * 4096))
    assert info["moe_group_hit_share"] == pytest.approx(100 * 495 / (330 * 4))  # 3 / 8
    # the kernel's counts take a layer's rows: the info line's over the L layers
    ops, moved = counts_deepseek_v2.decode_attn_counts(
        cell.config, info["decode_rows_latent"] / 5, info["decode_active"])
    assert ops / 197e12 < moved / 819e9 < 1.25 * ops / 197e12  # the memory's roof binds
    assert drv.latent_step_means(cell, []) == {}
    assert drv.latent_step_means(cell, [{"active": 3, "rows_latent": 40}])[
        "moe_group_hit_share"] is None


def test_the_reference_compiles_outside_the_persistent_cache(monkeypatch):
    """JAX writes an entry only where the compile took the configured least time:
    raised around the reference, put back behind it, also when it fails."""
    name = "jax_persistent_cache_min_compile_time_secs"
    before = getattr(jax.config, name)
    seen, served_gaps = [], drv.served_gaps
    monkeypatch.setattr(drv, "served_gaps", lambda *a: (
        seen.append(getattr(jax.config, name)), served_gaps(*a))[1])
    assert _run(toy.serve_cell())["correct"] is True
    assert seen == [1e9] and getattr(jax.config, name) == before
    with pytest.raises(ZeroDivisionError), drv.outside_the_compile_cache():
        1 / 0
    assert getattr(jax.config, name) == before


def test_kernel_seconds_reads_the_latent_kernel_by_its_name(monkeypatch):
    events = {"devices": {"/device:TPU:0": {"modules": [], "ops": [
        ["decode_attn_latent.3", 1.0, 0.002], ["decode_attn_latent.5", 1.02, 0.002],
        ["decode_attn.3", 1.5, 0.012], ["fusion.1", 1.6, 0.5]]}},
        "host": [["bench:trace_window", 0.9, 2.1]]}
    monkeypatch.setattr(kernel_ops, "_events", lambda ctx: events)
    assert kernel_ops.kernel_seconds({}, "decode_attn_latent") == (pytest.approx(0.004), 2)
    assert kernel_ops.kernel_seconds({}, "decode_attn") == (pytest.approx(0.012), 1)
