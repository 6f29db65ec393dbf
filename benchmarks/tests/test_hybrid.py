"""The pattern model's part of the yardstick at toy size on the CPU: its
counts against hand arithmetic, its driver end to end beside planted faults
and the control one precision down, and its readers of the new counters."""

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from benchmarks import cells, counts_hybrid, run
from benchmarks.drivers import hybrid_adapter
from benchmarks.drivers import serve_hybrid as drv
from benchmarks.reference import nemotron_h as ref
from benchmarks.tests import toy_hybrid
from benchmarks.tools import control_hybrid

CELL = toy_hybrid.CELL


def _run(cell, seed=2150000123, seconds=1.0, trace=False, tmp_path="/tmp"):
    return run.run_cell(cell, seed, seconds, trace, jax.devices()[:cell.chips],
                        time.perf_counter(), str(tmp_path))


def test_counts_against_hand_arithmetic():
    cfg = json.loads((cells.BENCH / "configs" / "nemotron-3-nano-30b-a3b.json").read_text())
    # Mamba-2: in 2688 x (4096 + 6144 + 64), conv 4 x 6144 + 6144, 3 x 64,
    # gate norm 4096, out 4096 x 2688, the layer's norm 2688
    assert counts_hybrid.mamba_layer_params(cfg) == (
        2688 * 10304 + 4 * 6144 + 6144 + 192 + 4096 + 4096 * 2688 + 2688) == 38_744_896
    assert counts_hybrid.expert_params(cfg) == 2 * 2688 * 1856 == 9_977_856
    assert counts_hybrid.moe_layer_params_outside_experts(cfg) == (
        2688 * 128 + 128 + 2 * 2688 * 3712 + 2688) == 20_302_592
    assert counts_hybrid.attention_layer_params(cfg) == (
        2 * 2688 * 4096 + 2 * 2688 * 256 + 2688) == 23_399_040
    held = (7 * 38_744_896 + 7 * (20_302_592 + 64 * 9_977_856) + 2 * 23_399_040
            + 2 * 65536 * 2688 + 2688)
    assert counts_hybrid.param_count(cfg) == held == cfg["parameters"] == 5_282_534_208
    assert sum(a.size for a in jax.eval_shape(
        lambda: ref.init_weights(cfg, ref.seed_key(0))).values()) == held
    # the published model: 52 layers, 128 experts, the whole vocabulary
    whole = {**cfg, **{k: cfg["deployment"][k] for k in ("n_routed_experts", "vocab_size")},
             "hybrid_override_pattern": "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"}
    assert counts_hybrid.param_count(whole) == 31_577_940_288
    # a slot's state in one layer: 64 x 64 x 128 float32 + 3 x 6144 bfloat16
    assert counts_hybrid.state_bytes_per_slot(cfg) == 2_097_152 + 36_864
    assert counts_hybrid.kv_row_bytes(cfg) == 2 * 2 * 128 * 2
    nothing = counts_hybrid.decode_step_bytes(cfg, 0, 0)
    outside = 2 * (held - 7 * 64 * 9_977_856 - 65536 * 2688) + 7 * 2 * (2688 * 128 + 128)
    assert nothing == outside  # every weight but experts and embedding; the router float32
    full = counts_hybrid.decode_step_bytes(cfg, 128, 7 * 64)
    assert full - nothing == (7 * 64 * 9_977_856 * 2 + 2 * 128 * 1024
                              + 7 * 128 * 2 * (2_097_152 + 36_864))
    assert 14.0e9 < full < 14.1e9  # 17.1 ms at 819 GB/s


def test_adapter_renames_every_leaf():
    cfg = toy_hybrid.TOY_NEMOTRON
    model = hybrid_adapter.build_model(cfg, {"impl": "full"})
    weights = ref.init_weights(cfg, ref.seed_key(3))
    tree = hybrid_adapter.to_program(weights, cfg)
    init, _ = model.init(jax.random.key(0))
    assert jax.tree.structure(tree) == jax.tree.structure(init)
    assert all(a.shape == b.shape and a.dtype == b.dtype
               for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(init)))
    assert tree["layer1"]["mixer"]["experts"]["up"] is weights["layers.1.experts.up"]
    assert model.held == (0, 4) and model.num_experts == 8
    tokens = jax.random.randint(jax.random.key(1), (1, 19), 0, cfg["vocab_size"])
    with jax.default_matmul_precision("highest"):
        logits, _ = model.apply(tree, {}, tokens)
        want = ref.forward(cfg, weights, tokens[0])
        rows, regret = ref.served_rows_logits(cfg, weights, tokens[0], 11, 8)
    assert float(jnp.abs(logits[0] - want).max()) < 1e-5
    assert float(jnp.abs(rows - want[11:19]).max()) < 1e-5 and regret is None


def test_reference_follows_the_routes_it_is_given():
    """Along its own choices the reference gives what it gives alone, with no
    regret; along others (each token's worst-scored experts) it gives other
    logits and the regret says how far those choices lie from its own."""
    cfg = toy_hybrid.TOY_NEMOTRON
    weights = ref.init_weights(cfg, ref.seed_key(4))
    tokens = jax.random.randint(jax.random.key(2), (19,), 0, cfg["vocab_size"])
    with jax.default_matmul_precision("highest"):
        h = weights["embed"][tokens]
        h = ref.layer(cfg, "M", ref.layer_leaves(weights, 0), h)
        lw = {k: a.astype(jnp.float32) for k, a in ref.layer_leaves(weights, 1).items()}
        _, select = ref.route_scores(lw, ref.rms_norm(h, lw["norm.w"], cfg["norm_eps"]))
        own = jnp.argsort(-select, axis=-1)[:, :2]
        want, _ = ref.served_rows_logits(cfg, weights, tokens, 11, 8)
        same, regret = ref.served_rows_logits(cfg, weights, tokens, 11, 8, own)
        assert float(jnp.abs(same - want).max()) < 1e-6
        assert regret.shape == (1, 19) and float(regret.max()) == 0.0
        worst = jnp.argsort(select, axis=-1)[:, :2]
        other, regret = ref.served_rows_logits(cfg, weights, tokens, 11, 8, worst)
    assert float(jnp.abs(other - want).max()) > 1e-3
    spread = jnp.sort(select, axis=-1)
    assert jnp.allclose(regret[0], spread[:, -2] - spread[:, 0], atol=1e-6)
    with pytest.raises(ValueError, match="wide"):
        ref.split_routes(cfg, jnp.zeros((19, 3), jnp.int32))


def test_driver_result_line_and_correct(capsys):
    cell = toy_hybrid.serve_cell()
    result = _run(cell)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 4
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end} == {
        "serve.tpot_p95_ms", "serve.tokens_per_s", "setup_s"}  # the toy cell's own list
    rows = [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith('{"compared"')]
    assert {r["compared"] for r in rows} == {"requests_not_finished", "token_count_mismatch",
                                             "served_token_gap", "served_mean_gap",
                                             "route_regret_mean"}


def test_window_opens_after_the_ramp(capsys):
    """The cell file's `ramp_s` of the same traffic are served first and count
    as set-up: the window's requests are those due after it, and the tokens
    counted are those completed inside the window, the ramp's late ones too."""
    cell = toy_hybrid.serve_cell()
    cell.spec["ramp_s"] = 1.0
    result = _run(cell, seconds=1.0)
    info = next(json.loads(line)["info"] for line in capsys.readouterr().out.splitlines()
                if line.startswith('{"info"'))
    assert info["ramp_s"] == 1.0 and info["requests_offered"] == 40 == result["attempted"]
    assert 15 <= info["requests"] <= 25  # 20 a second: about half are the window's
    assert 0 < info["tokens_in_window"] < info["generated_tokens"]
    assert result["metrics"]["serve.tokens_per_s"]["value"] == info["tokens_in_window"] / 1.0
    assert result["metrics"]["setup_s"]["value"] >= 1.0 and result["correct"] is True
    cell.spec["ramp_s"] = 0.0
    _run(cell, seconds=1.0)
    empty = next(json.loads(line)["info"] for line in capsys.readouterr().out.splitlines()
                 if line.startswith('{"info"'))
    assert empty["requests_offered"] == empty["requests"] == 20


def _expert_left_out(monkeypatch):
    real = drv.make_params

    def without_one(cell, seed):
        params = real(cell, seed)
        ex = params["layer1"]["mixer"]["experts"]
        ex["down"] = ex["down"].at[2].set(0.0)
        return params

    monkeypatch.setattr(drv, "make_params", without_one)
    return lambda: None


@pytest.mark.parametrize("plant", ["no_state_reset", "tail_advances_state", _expert_left_out],
                         ids=lambda p: p if isinstance(p, str) else p.__name__.strip("_"))
def test_planted_fault_is_not_correct(plant, monkeypatch):
    """`tools/control_hybrid.py`'s plants (what its `fault_*` arms run at the
    cell's size) and an expert whose output is dropped."""
    undo = control_hybrid.plant(plant) if isinstance(plant, str) else plant(monkeypatch)
    try:
        assert _run(toy_hybrid.serve_cell(), seconds=2.0)["correct"] is False
    finally:
        undo()


def test_router_one_precision_down_is_not_correct(capsys):
    """The cell's `check.controls` entry `router_bf16`, planted as the control
    tool plants it, with outputs long enough for flipped choices to show
    (2,700 tokens): the reference follows the program's choices, so what
    fails is `route_regret_mean` (the sound engine's is 0 in float32). The
    other control, `state_bf16`, moves nothing at this size (16 states a
    head, 60-token sequences); its readings at the cell's size are in
    PERF.md."""
    cell = toy_hybrid.serve_cell()
    cell.spec["engine"]["serve_config"].update(max_len=128)
    cell.traffic["output_len"] = {"median": 40, "sigma": 0.5, "min": 8, "max": 64}
    assert cell.spec["check"]["controls"]["router_bf16"] == {"plant": "router_bf16"}
    assert _run(cell, seconds=4.0)["correct"] is True
    capsys.readouterr()
    undo = control_hybrid.plant("router_bf16")
    try:
        assert _run(cell, seconds=4.0)["correct"] is False
    finally:
        undo()
    failed = {json.loads(line)["compared"] for line in capsys.readouterr().out.splitlines()
              if line.startswith('{"compared"') and not json.loads(line)["ok"]}
    assert "route_regret_mean" in failed


def test_step_bytes_follow_the_steps_own_counters():
    from tpudml.obs.tracer import Span

    cfg = json.loads((cells.BENCH / "configs" / "nemotron-3-nano-30b-a3b.json").read_text())
    spec = json.loads((cells.BENCH / "workloads" / f"{CELL}.json").read_text())
    events = []
    for step, (active, touched, ts) in enumerate([(100, 400, 10), (120, 440, 20), (5, 30, 90)]):
        events.append(Span("dispatch", "serve", ts, 1, args={"step": step, "active": active}))
        events.append(Span("commit", "serve", ts + 5, 1,
                           args={"step": step, "experts_touched": touched}))
    got = drv.step_bytes_from_spans(cfg, spec, events, 0, 50)
    want = (counts_hybrid.decode_step_bytes(cfg, 100, 400)
            + counts_hybrid.decode_step_bytes(cfg, 120, 440)) / 2
    assert got == want
    assert drv.step_bytes_from_spans(cfg, spec, events, 200, 300) is None
    bare = [Span("commit", "serve", 15, 1, args={"step": 0})]  # a program without counters
    assert drv.step_bytes_from_spans(cfg, spec, events[:1] + bare, 0, 50) is None


def test_readers_of_the_new_counters(monkeypatch):
    from benchmarks import program_spans

    cell = cells.load_cell(CELL)
    commits = [["serve/commit", 0.1 * i, 0.001, {"step": i, "moe_routed": r, "moe_held": h,
                                                  "experts_touched": t, "expert_load_max": m}]
               for i, (r, h, t, m) in enumerate([(4200, 2100, 420, 10), (4200, 2058, 392, 21)])]
    spans = commits + [["serve/commit", 0.3, 0.001, {"step": 2, "moe_routed": 0, "moe_held": 0,
                                                     "experts_touched": 0, "expert_load_max": 0}]]
    monkeypatch.setattr(program_spans, "of_cell", lambda ctx: spans)
    ctx = {"cell": cell}
    assert run.read_layer_metric("serve.moe_held_share", ctx) == pytest.approx(100 * 4158 / 8400)
    assert run.read_layer_metric("serve.moe_experts_touched", ctx) == pytest.approx(
        100 * (420 + 392 + 0) / 3 / (64 * 7))
    assert run.read_layer_metric("serve.moe_load_max_over_mean", ctx) == pytest.approx(
        (10 * 420 / 2100 + 21 * 392 / 2058) / 2)
    # a program without the counters (an earlier commit): nothing to read, no error
    monkeypatch.setattr(program_spans, "of_cell",
                        lambda ctx: [["serve/commit", 0.1, 0.001, {"step": 0, "tokens": 3}]])
    for name in ("serve.moe_held_share", "serve.moe_experts_touched",
                 "serve.moe_load_max_over_mean"):
        assert run.read_layer_metric(name, ctx) is None
    monkeypatch.setattr(program_spans, "of_cell", lambda ctx: None)
    assert run.read_layer_metric("serve.moe_held_share", ctx) is None


def test_the_cell_loads_with_the_metrics_it_can_report():
    """`serve.tpot_p95_ms` spreads too widely in this cell to be one of its
    end-to-end metrics (PERF.md, PR 30): it reports `serve.tokens_per_s` and
    `setup_s`, the accepted serving metrics that move `serve.tokens_per_s`, its
    own expert counters, and under names of its own the TPOT tail and three
    readers the harness would otherwise not run here."""
    cell = cells.load_cell(CELL)
    bench = json.loads((cells.ROOT / "BENCHMARK.json").read_text())
    assert cell.driver == "serve_hybrid" and cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {"serve.tokens_per_s", "setup_s"}
    accepted = {m["name"] for m in bench["per_layer"]
                if m["moves"] == "serve.tokens_per_s" and "workloads" not in m}
    assert accepted == {"serve.ttft_p95_ms", "serve.queue_wait_p95_ms",
                        "serve.stage_lateness_p50_ms", "serve.slot_occupancy"}
    new = {"serve.moe_experts_touched", "serve.moe_load_max_over_mean", "serve.moe_held_share",
           "serve.tpot_p95_ms.chat", "serve.decode_device_ms.chat",
           "serve.prefill_device_ms.chat", "serve.decode_hbm.chat"}
    assert {m["name"] for m in cell.per_layer} == accepted | new
    for m in cell.per_layer:
        assert m["moves"] == "serve.tokens_per_s"
        assert (Path(cells.BENCH) / "layer_metrics" / f"{m['name']}.py").exists()
    other = cells.load_cell("starcoderbase-1b.serve-code")
    assert not new & {m["name"] for m in other.per_layer}
    assert cell.traffic["rate_per_s"] == pytest.approx(0.8 * cell.traffic["knee"]["rate_per_s"])
    assert cell.traffic["shuffle_block"] == 4


def test_readers_under_the_cells_own_names(monkeypatch):
    """The three aliases give what the readers they name give; the TPOT tail
    reads the driver's `host` record, and nothing where there is none."""
    cell = cells.load_cell(CELL)

    class Trace:
        def median_program_s(self, pattern):
            return {"^jit_step$": 0.022, "^jit__serve_prefill_chunk$": 0.034}[pattern]

    ctx = {"cell": cell, "trace": Trace(), "peaks": {"hbm_bytes_per_s": 819e9},
           "host": {"decode_step_bytes": 11.0e9, "tpot_s": [0.040 + 0.001 * i for i in range(21)]}}
    for name in ("serve.decode_device_ms", "serve.prefill_device_ms", "serve.decode_hbm"):
        assert run.read_layer_metric(f"{name}.chat", ctx) == run.read_layer_metric(name, ctx) > 0
    assert run.read_layer_metric("serve.decode_hbm.chat", ctx) == pytest.approx(
        100 * 11.0e9 / 819e9 / 0.022)
    assert run.read_layer_metric("serve.tpot_p95_ms.chat", ctx) == pytest.approx(59.0)
    assert run.read_layer_metric("serve.tpot_p95_ms.chat", {**ctx, "host": {}}) is None
