"""The arithmetic over the program's spans, by hand on a small list, then each
reader of `layer_metrics/` that reads them, then the list recorded from a chip
run kept beside this file (`recorded_spans.json`: what `program_spans.load`
read from a traced run of each cell, cut to a few passes)."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks import program_spans, run, tracing

RECORDED = Path(__file__).with_name("recorded_spans.json")


def _pass(start, step, *, fetch=0.100, host=0.002, admit=None, active=2, rows=300):
    """One pass of the serving loop: [arrive] [admit] dispatch fetch commit."""
    t, out = start + 0.0002, []
    if admit is not None:
        out.append(["serve/arrive", t, 0.0001, {"rid": admit, "late_us": 40000 * (admit + 1),
                                                "rejected": 0}])
        out.append(["serve/admit", t + 0.0002, 0.004, {"rid": admit, "slot": 1, "chunks": 2,
                                                       "prompt_len": 900, "shared_pages": 0}])
        t += 0.0045
    out.append(["serve/dispatch", t, host / 2, {"step": step, "active": active, "rows": rows}])
    out.append(["serve/fetch", t + host / 2, fetch, {"step": step}])
    out.append(["serve/commit", t + host / 2 + fetch, host / 4,
                {"step": step, "tokens": active, "finished": 0, "expired": 0}])
    end = t + host / 2 + fetch + host / 4 + host / 4
    return [["serve/iter", start, end - start, {"step": step, "queue": 0, "active": active}]] + out


def _loaded():
    """Four passes; the window opens inside the first and closes inside the
    last, so only the middle two count. The second admits request 1."""
    spans, t = [], 0.0
    for step, kw in enumerate([{}, {"admit": 1, "active": 3, "rows": 1200}, {"host": 0.004},
                               {}]):
        made = _pass(t, step, **kw)
        spans += made
        t = made[0][1] + made[0][2] + 0.0001
    return {"window": [0.05, t - 0.05], "spans": sorted(spans, key=lambda s: s[1])}


def test_only_spans_wholly_inside_the_window_count():
    loaded = _loaded()
    spans = program_spans.inside(loaded)
    passes = program_spans.named(spans, "serve/iter")
    assert program_spans.stat(passes, "step") == [1, 2]
    # the first pass's commit lies inside the window; its fetch and the pass do not
    assert program_spans.stat(program_spans.named(spans, "serve/fetch"), "step") == [1, 2]
    assert program_spans.stat(program_spans.named(spans, "serve/commit"), "step") == [0, 1, 2]
    assert len(program_spans.inside({**loaded, "window": None})) == len(loaded["spans"])


def test_children_and_the_steady_pass_by_hand():
    spans = program_spans.inside(_loaded())
    one, two = program_spans.named(spans, "serve/iter")
    assert [c[3]["rid"] for c in program_spans.children(spans, one, "serve/admit")] == [1]
    assert program_spans.children(spans, two, "serve/admit") == []
    assert program_spans.children(spans, one, "serve/iter") == []
    # pass 1 admitted somebody, so only pass 2 is steady: 0.0002 + host 0.004
    assert program_spans.loop_host_s(spans) == [pytest.approx(0.0042)]


def test_prefill_stall_holds_the_device_programs_against_the_passes():
    spans = program_spans.inside(_loaded())
    one, two = program_spans.named(spans, "serve/iter")
    programs = {"jit__serve_prefill_chunk": [0.008, 0.009, 0.008], "jit_step": [0.1]}
    starts = {"jit__serve_prefill_chunk": [one[1] + 0.001, one[1] + 0.010, two[1] + 0.001],
              "jit_step": [one[1] + 0.02]}
    stalls = program_spans.prefill_stall_s(spans, programs, starts, "^jit__serve_prefill_chunk$")
    assert stalls == [pytest.approx(0.017)]  # pass 2 has no successor in the window
    assert program_spans.prefill_stall_s(spans, programs, starts, "^nothing$") == [0.0]


def _ctx(kind="serve"):
    spec = {"programs": {"decode": "^jit_step$", "prefill": "^jit__serve_prefill_chunk$"},
            "engine": {"serve_config": {"slots": 4, "max_len": 1000}}}
    trace = tracing.Summary(window_s=0.3, busy_s=0.29,
                            programs={"jit__serve_prefill_chunk": [0.008, 0.009]},
                            program_starts={"jit__serve_prefill_chunk": [0.105, 0.115]})
    return {"cell": SimpleNamespace(name=f"toy.{kind}", spec=spec), "trace": trace, "host": {}}


SERVE_READINGS = {
    "serve.loop_host_ms": 4.2,
    "serve.commit_host_ms": 0.5,              # median of 0.5, 0.5, 1.0
    "serve.prefill_stall_p95_ms": 17.0,
    "serve.stage_lateness_p50_ms": 80.0,
    "serve.slot_occupancy": 100.0 * (3 + 2 + 2) / 3 / 4,  # dispatches of passes 1, 2, 3
    "serve.cache_rows_live": 100.0 * (1200 + 300 + 300) / 3 / 4000,
}


@pytest.mark.parametrize("metric", sorted(SERVE_READINGS))
def test_serve_readers_on_the_hand_made_list(metric, monkeypatch):
    monkeypatch.setattr(program_spans, "load", lambda trace_dir: _loaded())
    assert run.read_layer_metric(metric, _ctx()) == pytest.approx(SERVE_READINGS[metric])


def test_train_reader_on_a_hand_made_list(monkeypatch):
    spans = []
    for step in range(1, 5):
        t = 0.2 * step
        spans += [["train/iter", t, 0.19, {"step": step}],
                  ["train/next_batch", t + 0.001, 0.002, {"step": step}],
                  ["train/step", t + 0.004, 0.001 * step, {"step": step}]]
    monkeypatch.setattr(program_spans, "load",
                        lambda trace_dir: {"window": [0.3, 0.95], "spans": spans})
    # the steps of passes 2, 3 and 4 lie inside the window; pass 4 is cut by its end
    assert run.read_layer_metric("train.dispatch_host_ms", _ctx("train")) == pytest.approx(3.0)


METRICS = sorted(SERVE_READINGS) + ["train.dispatch_host_ms"]


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("found", [None, {"window": [0.0, 1.0], "spans": []}],
                         ids=["no-trace", "no-program-span"])
def test_readers_return_none_when_the_program_has_no_span(metric, found, monkeypatch):
    """An earlier commit's program opens no `tpudml:` span: the line leaves
    the metric out, and nothing raises."""
    monkeypatch.setattr(program_spans, "load", lambda trace_dir: found)
    assert run.read_layer_metric(metric, _ctx()) is None


def test_every_new_reader_is_declared_in_benchmark_json():
    with open(program_spans.ROOT / "BENCHMARK.json") as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    for metric in METRICS:
        assert declared[metric]["source"] == "device_trace"
        assert "workloads" not in declared[metric]


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded list yet")
@pytest.mark.parametrize("cell", ["starcoderbase-1b.serve-code", "gpt2-medium.pretrain-1k"])
def test_recorded_spans_reduce_to_the_recorded_readings(cell, monkeypatch):
    recorded = json.loads(RECORDED.read_text())[cell]
    monkeypatch.setattr(program_spans, "load", lambda trace_dir: recorded["loaded"])
    t = recorded["trace"]
    ctx = {"cell": SimpleNamespace(name=cell, spec=recorded["spec"]), "host": {},
           "trace": tracing.Summary(window_s=0.0, busy_s=0.0, programs=t["programs"],
                                    program_starts=t["program_starts"])}
    for metric, value in recorded["expected"].items():
        assert run.read_layer_metric(metric, ctx) == pytest.approx(value, rel=1e-9)
