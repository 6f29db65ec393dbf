"""Spans recorded from `ServingEngine.run` itself (a toy model, on the CPU),
held against what the readers of `program_spans.py` rely on: they pair a
pass's spans by containment in its `serve/iter`. The loop keeps that whether
a pass fetches the step it just dispatched or the one before (one step kept
in flight). Then the reader of `ahead`, the counter that says which."""

from types import SimpleNamespace

import pytest

from benchmarks import program_spans, run


def _recorded_from_the_loop(**config):
    """The spans of one run as `program_spans.load` gives them: the names,
    the order and the containment are the program's own."""
    import jax
    import numpy as np
    from tpudml.models import TransformerLM
    from tpudml.obs import Tracer, use_tracer
    from tpudml.serve import Request, ServeConfig, ServingEngine

    model = TransformerLM(vocab_size=48, embed_dim=32, num_heads=4, num_layers=2,
                          max_len=64, rope=True, num_kv_heads=2)
    params, _ = model.init(jax.random.key(0))
    engine = ServingEngine(model, params, ServeConfig(
        slots=2, max_len=64, prefill_chunk=8, **config))
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, prompt=rng.integers(0, 48, 5 + 4 * i).astype(np.int32),
                    max_new_tokens=6 + i) for i in range(4)]
    tracer = Tracer()
    with use_tracer(tracer):
        report = engine.run(reqs)
    spans = sorted(([f"{e.cat}/{e.name}", e.ts_us / 1e6, e.dur_us / 1e6, dict(e.args or {})]
                    for e in tracer.events), key=lambda s: s[1])
    return spans, report


@pytest.fixture(scope="module", params=[{}, {"spec_k": 2}], ids=["step-in-flight", "fetch-first"])
def recorded(request):
    return _recorded_from_the_loop(**request.param)


def test_a_pass_holds_one_fetch_and_commit_of_a_step_and_the_dispatch_of_the_next(recorded):
    spans, report = recorded
    passes = program_spans.named(spans, "serve/iter")
    inside = {name: [program_spans.children(spans, it, f"serve/{name}") for it in passes]
              for name in ("dispatch", "fetch", "commit")}
    for name, found in inside.items():  # each lies in exactly one pass, alone of its name
        assert all(len(f) <= 1 for f in found)
        assert sum(map(len, found)) == report.decode_steps
    for dispatch, fetch, commit in zip(*inside.values()):
        assert len(fetch) == len(commit)
        if not fetch:
            continue
        assert fetch[0][3]["step"] == commit[0][3]["step"]
        assert fetch[0][1] + fetch[0][2] <= commit[0][1]
        for d in dispatch:  # the step it launches: the fetched one, or the one after
            assert d[3]["step"] == fetch[0][3]["step"] + d[3]["ahead"]
            assert d[1] + d[2] <= fetch[0][1]


def test_the_readers_have_one_entry_a_steady_pass(recorded):
    spans, _ = recorded
    passes = program_spans.named(spans, "serve/iter")
    steady = [it for it in passes if program_spans.children(spans, it, "serve/fetch")
              and not program_spans.children(spans, it, "serve/admit")]
    assert len(steady) >= 4
    got = program_spans.decode_passes(spans)
    assert [it for it, _ in got] == steady
    assert [fetch for _, fetch in got] == [
        program_spans.children(spans, it, "serve/fetch")[0] for it in steady]
    host = program_spans.loop_host_s(spans)
    assert len(host) == len(steady) and all(h > 0 for h in host)
    # a prefill program that starts inside pass k is charged to pass k alone
    k = passes.index(steady[1])
    programs = {"jit__serve_prefill_chunk": [0.004], "jit_step": [0.01] * len(passes)}
    starts = {"jit__serve_prefill_chunk": [passes[k][1] + passes[k][2] / 2],
              "jit_step": [it[1] for it in passes]}
    stalls = program_spans.prefill_stall_s(spans, programs, starts, "^jit__serve_prefill_chunk$")
    assert stalls == [pytest.approx(0.004 * (i == k)) for i in range(len(passes) - 1)]


def _ctx():
    return {"cell": SimpleNamespace(name="toy.serve", spec={}), "host": {}}


def test_dispatch_ahead_share_is_the_mean_of_ahead(recorded, monkeypatch):
    spans, report = recorded
    monkeypatch.setattr(program_spans, "load", lambda trace_dir: {"window": None, "spans": spans})
    ahead = program_spans.stat(program_spans.named(spans, "serve/dispatch"), "ahead")
    assert len(ahead) == report.decode_steps
    # everybody is there at the start: only the first step finds nothing in flight
    want = 100.0 * (report.decode_steps - 1) / report.decode_steps if any(ahead) else 0.0
    assert run.read_layer_metric("serve.dispatch_ahead_share", _ctx()) == pytest.approx(want)
    assert (want > 90.0) == ("spec" not in str(report.events))


@pytest.mark.parametrize("found", [None, {"window": [0.0, 1.0], "spans": []},
                                   {"window": None, "spans": [
                                       ["serve/dispatch", 0.1, 0.001, {"step": 0, "active": 1}]]}],
                         ids=["no-trace", "no-program-span", "no-such-counter"])
def test_dispatch_ahead_share_is_left_out_where_the_program_has_no_counter(found, monkeypatch):
    """The parent's `serve/dispatch` carries no `ahead`: the line leaves the
    metric out, and nothing raises."""
    monkeypatch.setattr(program_spans, "load", lambda trace_dir: found)
    assert run.read_layer_metric("serve.dispatch_ahead_share", _ctx()) is None


def test_dispatch_ahead_share_is_declared_as_cache_rows_live_is():
    import json

    with open(program_spans.ROOT / "BENCHMARK.json") as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    new, old = declared["serve.dispatch_ahead_share"], declared["serve.cache_rows_live"]
    assert (new["unit"], new["better"], new["source"]) == ("%", "higher", "program_counter")
    assert (new["layer"], new["moves"]) == ("serving engine host loop", "serve.tpot_p95_ms")
    assert "workloads" not in new and "workloads" not in old and new["moves"] == old["moves"]
