"""The readers of the program's pass log (`benchmarks/pass_log.py`) and of the
idle gaps by program span (`benchmarks/idle_by_span.py`): the arithmetic by
hand, each reader of `layer_metrics/` on a hand-made log, nothing where the
program keeps no log, the toy cells end to end, then the log and the gaps
recorded from a chip run kept beside this file (`recorded_pass_log.json`: what
`tools/pass_log_of_run.py` wrote after a traced run of `serve-code` and of the
training cell, cut to the passes around the traced window)."""

import json
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

from benchmarks import idle_by_span, pass_log, run
from benchmarks.tests import toy

RECORDED = Path(__file__).with_name("recorded_pass_log.json")
SERVE = ["serve.steady_pass_p50_ms", "serve.steady_pass_max_ms", "serve.host_hiccup_max_ms"]
NEW = SERVE + ["train.iter_max_ms", "device_idle.serve_engaged"]


def _ctx(**spec):
    return {"cell": SimpleNamespace(name="toy.cell", spec=spec), "host": {}}


def _serve_log():
    """Eight passes: steady ones of 11, 12, 400 (before the ramp's end), 13,
    11.5 and 30 ms, an admitting one of 45 and an idle one of 50."""
    classes = ["steady", "admitting", "idle"]
    passes = [(0.5, 11.0, 0, 0.2), (1.0, 400.0, 0, 390.0), (2.0, 12.0, 0, 0.3),
              (2.1, 45.0, 1, 0.1), (2.2, 13.0, 0, 4.5), (2.3, 50.0, 2, 0.0),
              (2.4, 11.5, 0, 0.4), (2.5, 30.0, 0, 0.2)]
    cols = list(zip(*passes))
    return {"kind": "serve", "classes": classes,
            "rows": {"start_s": list(cols[0]), "ms": list(cols[1]), "cls": list(cols[2]),
                     "hiccup_ms": list(cols[3]), "step": list(range(8))},
            "summary": {}}


def _train_log(ms):
    """Passes of steps 17, 18, ... of the given milliseconds, the exhausted
    loader's pass (class `other`) at the end."""
    n = len(ms)
    return {"kind": "train", "classes": ["step", "other"],
            "rows": {"start_s": [0.2 * i for i in range(n + 1)], "ms": list(ms) + [0.05],
                     "cls": [0] * n + [1], "step": list(range(17, 17 + n + 1)),
                     "hiccup_ms": [0.1] * (n + 1)},
            "summary": {}}


# ------------------------------------------------------------- the pass log


def test_passes_of_a_class_from_a_time_on():
    loaded = _serve_log()
    assert pass_log.passes(loaded, "steady") == [0, 1, 2, 4, 6, 7]
    assert pass_log.passes(loaded, "steady", 2.0) == [2, 4, 6, 7]
    assert pass_log.passes(loaded, "idle", 2.0) == [5]
    assert pass_log.column(loaded, "ms", [3, 5]) == [45.0, 50.0]


@pytest.mark.parametrize("metric, ramp, want", [
    ("serve.steady_pass_p50_ms", 2.0, 12.5), ("serve.steady_pass_max_ms", 2.0, 30.0),
    ("serve.host_hiccup_max_ms", 2.0, 4.5),
    # a cell file without `ramp_s`: the window opens with the run
    ("serve.steady_pass_p50_ms", None, 12.5), ("serve.steady_pass_max_ms", None, 400.0),
    ("serve.host_hiccup_max_ms", None, 390.0),
    # nothing steady behind the ramp
    ("serve.steady_pass_max_ms", 9.0, None),
])
def test_serving_readers_on_a_hand_made_log(metric, ramp, want, monkeypatch):
    monkeypatch.setattr(pass_log, "load", lambda kind: _serve_log() if kind == "serve" else None)
    ctx = _ctx() if ramp is None else _ctx(ramp_s=ramp)
    got = run.read_layer_metric(metric, ctx)
    assert got == want if want is None else got == pytest.approx(want)


@pytest.mark.parametrize("spec, want", [
    ({}, 900.0),                                            # no traced steps named
    ({"trace": {"start_step": 4, "steps": 2}}, 171.0),      # the 4th and 6th start, stop it
    ({"trace": {"start_step": 2, "steps": 3}}, 900.0),
])
def test_train_iter_max_leaves_out_the_first_step_and_the_profilers_two(spec, want, monkeypatch):
    ms = [2500.0, 165.0, 166.0, 900.0, 171.0, 800.0, 164.0]   # the window's steps 1..7
    monkeypatch.setattr(pass_log, "load", lambda kind: _train_log(ms) if kind == "train" else None)
    assert run.read_layer_metric("train.iter_max_ms", _ctx(**spec)) == pytest.approx(want)


def test_train_iter_max_of_one_step_is_nothing(monkeypatch):
    monkeypatch.setattr(pass_log, "load", lambda kind: _train_log([170.0]))
    assert run.read_layer_metric("train.iter_max_ms", _ctx()) is None


# ------------------------------------------------ idle gaps by program span


def _three_gaps():
    """A window of 1 s: the engine waits for an arrival through two
    `serve/idle` sleeps (gap 1), the host holds the device back inside a
    `serve/fetch` (gap 2), and a sliver between two programs no span
    covers (gap 3: the loop's spans start at 0.05)."""
    spans = [["serve/iter", 0.10, 0.060, {}], ["serve/idle", 0.105, 0.054, {}],
             ["serve/iter", 0.161, 0.045, {}], ["serve/idle", 0.162, 0.043, {}],
             ["serve/iter", 0.50, 0.20, {"step": 7}], ["serve/dispatch", 0.501, 0.002, {}],
             ["serve/fetch", 0.504, 0.190, {"step": 6}], ["serve/commit", 0.695, 0.001, {}]]
    modules = [["jit_step", 0.0, 0.02], ["jit_step", 0.03, 0.07], ["jit__serve_prefill_chunk", 0.21, 0.29],
               ["jit_step", 0.60, 0.40]]
    gaps = [[0.10, 0.21], [0.50, 0.60], [0.02, 0.03]]
    return {"window": [0.0, 1.0], "gaps": gaps, "modules": modules, "spans": spans}


def test_each_gap_takes_the_innermost_span_over_its_middle_else_its_neighbours():
    found = _three_gaps()
    named = idle_by_span.by_span(found["gaps"], found["spans"], found["modules"])
    assert named == [["serve/idle", 0.10, pytest.approx(0.11)],       # middle 0.155: the 1st sleep
                     ["serve/fetch", 0.50, pytest.approx(0.10)],      # not the pass around it
                     ["jit_step -> jit_step", 0.02, pytest.approx(0.01)]]
    assert idle_by_span.table(named + [["serve/fetch", 0.9, 0.05]]) == [
        ["serve/fetch", pytest.approx(0.15), 2], ["serve/idle", pytest.approx(0.11), 1],
        ["jit_step -> jit_step", pytest.approx(0.01), 1]]
    edges = idle_by_span.by_span([[-0.5, -0.3], [1.5, 1.6]], [], found["modules"])
    assert [g[0] for g in edges] == ["trace start -> jit_step", "jit_step -> trace end"]


def test_the_engaged_share_is_the_idle_time_outside_the_engines_own_waits():
    found = _three_gaps()
    # gap 1 less its two sleeps: 0.11 - 0.054 - 0.043; gaps 2 and 3 whole
    left = idle_by_span.uncovered(found["gaps"], found["spans"], "serve/idle")
    assert left == pytest.approx(0.013 + 0.10 + 0.01)
    assert idle_by_span.uncovered(found["gaps"], found["spans"], "serve/nothing") == pytest.approx(0.22)
    # the share is over what the program's spans span, 0.10 to 0.70: gap 3 lies before it
    assert idle_by_span.engaged_idle_share(found) == pytest.approx(100 * 0.113 / 0.6)


def test_a_wait_the_trace_cuts_off_is_not_read_as_engaged():
    """The profiler keeps no span that is open when the trace stops: the
    engine's last sleep is missing, and the gap under it counts for nothing."""
    found = _three_gaps()
    found["window"] = [0.0, 1.2]
    found["gaps"].append([1.0, 1.2])           # the device idle to the end of the trace
    found["spans"] += [["serve/iter", 1.001, 0.051, {}], ["serve/idle", 1.002, 0.050, {}]]
    # spans now reach 1.052: 0.052 more of window, 0.002 of it outside a sleep
    assert idle_by_span.engaged_idle_share(found) == pytest.approx(100 * 0.115 / 0.952)


def test_bubbles_between_a_programs_operations_are_one_unnamed_entry():
    found = _three_gaps()
    gaps = found["gaps"] + [[0.7 + i * 1e-3, 0.7 + i * 1e-3 + 4e-9] for i in range(50)]
    named = idle_by_span.by_span(gaps, found["spans"], found["modules"])
    assert len(named) == 4 and named[-1][0] == "gaps under 1e-06 s"
    assert named[-1][2] == pytest.approx(50 * 4e-9)


def test_device_idle_serve_engaged_reads_the_cells_trace(monkeypatch):
    seen = []
    monkeypatch.setattr(idle_by_span, "load", lambda d: seen.append(d) or _three_gaps())
    assert run.read_layer_metric("device_idle.serve_engaged", _ctx()) == pytest.approx(
        100 * 0.113 / 0.6)
    assert seen[0].endswith("benchmarks/.trace/toy.cell")


# ----------------------------------------------- nothing to read: an earlier commit


@pytest.mark.parametrize("metric", NEW)
def test_readers_return_none_without_a_log_or_a_trace(metric, monkeypatch):
    monkeypatch.setattr(pass_log, "load", lambda kind: None)
    monkeypatch.setattr(idle_by_span.program_spans, "load", lambda trace_dir: None)
    assert run.read_layer_metric(metric, _ctx(ramp_s=20)) is None


def test_a_trace_without_program_spans_gives_no_engaged_share(monkeypatch):
    monkeypatch.setattr(idle_by_span, "load", lambda d: {**_three_gaps(), "spans": []})
    assert run.read_layer_metric("device_idle.serve_engaged", _ctx()) is None


def test_a_program_from_before_the_pass_log_loads_nothing(monkeypatch):
    import tpudml.obs

    monkeypatch.delattr(tpudml.obs, "last_pass_log")
    assert pass_log.load("serve") is None and pass_log.load("train") is None


def test_a_loop_that_never_ran_loads_nothing(monkeypatch):
    from tpudml.obs import passlog

    monkeypatch.setattr(passlog, "_last", {})
    assert pass_log.load("serve") is None


def test_every_new_reader_is_declared_in_benchmark_json():
    """The four serving metrics list `serve-code` alone: the readers read in
    every serving cell (PERF.md §6, PR 42), but two of the pattern cells' own
    tests pin the set of metrics their cell reports, and a PR that adds may
    not edit them; a `benchmark` PR appends the three cells (ROADMAP C17)."""
    from benchmarks import cells

    with open(idle_by_span.ROOT / "BENCHMARK.json") as f:
        per_layer = json.load(f)["per_layer"]
    assert [m["name"] for m in per_layer[-5:]] == NEW  # appended, in the issue's order
    declared = {m["name"]: m for m in per_layer}
    for name in SERVE:
        assert declared[name] == {
            "name": name, "unit": "ms", "better": "lower", "source": "program_counter",
            "layer": "serving engine host loop", "moves": "serve.tokens_per_s",
            "workloads": ["starcoderbase-1b.serve-code"]}
    assert declared["train.iter_max_ms"] == {
        "name": "train.iter_max_ms", "unit": "ms", "better": "lower",
        "source": "program_counter", "layer": "trainer loop", "moves": "train.tokens_per_s"}
    assert declared["device_idle.serve_engaged"] == {
        "name": "device_idle.serve_engaged", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "device", "moves": "serve.tokens_per_s",
        "workloads": ["starcoderbase-1b.serve-code"]}
    reported = {cell: {m["name"] for m in cells.load_cell(cell).per_layer} & set(NEW)
                for cell in ("starcoderbase-1b.serve-code", "gpt2-medium.pretrain-1k",
                             "nemotron-3-nano-30b-a3b.serve-chat")}
    assert reported == {"starcoderbase-1b.serve-code": set(SERVE) | {"device_idle.serve_engaged"},
                        "gpt2-medium.pretrain-1k": {"train.iter_max_ms"},
                        "nemotron-3-nano-30b-a3b.serve-chat": set()}


# ------------------------------------------------- the toy cells, end to end


@pytest.mark.parametrize("make, kind, metrics", [
    (toy.serve_cell, "serve", SERVE), (toy.train_cell, "train", ["train.iter_max_ms"])],
    ids=["serve", "train"])
def test_a_run_of_a_toy_cell_leaves_a_log_the_readers_read(make, kind, metrics, capsys):
    cell = make()
    result = run.run_cell(cell, 5, 0.5, False, jax.devices()[:1], time.perf_counter(), "/tmp")
    assert result["correct"] is True
    # what the log warns of goes to stderr: stdout stays the harness's
    assert "slow " not in capsys.readouterr().out
    loaded = pass_log.load(kind)
    assert loaded["kind"] == kind and len(loaded["rows"]["ms"]) >= result["attempted"] > 0
    assert set(loaded["summary"]["slow"]) == set(loaded["classes"])
    regular = pass_log.passes(loaded, loaded["classes"][0])
    assert len(regular) > 4
    starts = pass_log.column(loaded, "start_s", regular)
    assert starts == sorted(starts) and 0.0 <= starts[0] and starts[-1] < 60.0
    for metric in metrics:
        value = run.read_layer_metric(metric, {"cell": cell, "host": {}})
        assert 0.0 < value < 5000.0, metric
    json.dumps(loaded)


# ------------------------------------------------------- recorded on the chip


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded pass log kept yet")
@pytest.mark.parametrize("cell", ["starcoderbase-1b.serve-code", "gpt2-medium.pretrain-1k"])
def test_recorded_log_and_gaps_reduce_to_the_recorded_readings(cell, monkeypatch):
    recorded = json.loads(RECORDED.read_text())[cell]
    monkeypatch.setattr(pass_log, "load", lambda kind: recorded["pass_log"].get(kind))
    monkeypatch.setattr(idle_by_span, "load", lambda trace_dir: recorded.get("idle"))
    ctx = {"cell": SimpleNamespace(name=cell, spec=recorded["spec"]), "host": {}}
    assert recorded["expected"]
    for metric, value in recorded["expected"].items():
        assert run.read_layer_metric(metric, ctx) == pytest.approx(value, rel=1e-9)
    if "idle" in recorded:
        idle = recorded["idle"]
        named = idle_by_span.by_span(idle["gaps"], idle["spans"], idle["modules"])
        assert [g[0] for g in named[:len(recorded["longest_gaps"])]] == recorded["longest_gaps"]
        assert sum(g[2] for g in named) == pytest.approx(
            sum(e - s for s, e in idle["gaps"]), rel=1e-9)
