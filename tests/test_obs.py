"""Flight-recorder tests: tracer determinism, in-graph StepStats,
serve-trace parity, the zero-cost disabled path, and the drift gate.

Load-bearing properties:

- a fixed event log exports a byte-identical Chrome trace (golden file
  under ``tests/obs_fixtures/``), and the document passes both our own
  schema validator and the sort/nesting contract trace viewers require;
- the serving trace is a PURE function of the engine's deterministic
  event log: two identical paged+spec 2×-overload runs (the PR 11 golden
  config) write byte-identical ``trace.json`` files;
- ``obs=False`` (the default) allocates ZERO ``Span`` objects across a
  full train step — pinned via the module's ``SPANS_ALLOCATED`` counter,
  not a benchmark;
- DP's in-graph ``StepStats`` agrees with ground truth: loss matches the
  metrics dict, the split-step comm-bytes leaf reproduces the measured
  ``CommStats`` accounting exactly, and the leaf grows linearly in step;
- ``metrics.jsonl`` stays strict JSON through NaN/Inf losses;
- ``python -m tpudml.obs --check-drift`` exits 0 on the live world-4
  regimes (static-vs-measured agreement, the PR 10 pin held
  continuously) and non-zero on a seeded mismatch fixture.
"""

import json
from pathlib import Path

import jax
import numpy as np
import pytest

from tpudml.core.config import MeshConfig
from tpudml.core.dist import make_mesh
from tpudml.core.prng import seed_key
from tpudml.data.datasets import synthetic_classification
from tpudml.metrics import MetricsWriter
from tpudml.models import LeNet, TransformerLM
from tpudml.obs import (
    TRACE_SCHEMA_VERSION,
    Tracer,
    dump_trace,
    get_tracer,
    serve_trace_events,
    use_tracer,
    validate_chrome_trace,
    write_serve_trace,
)
from tpudml.obs import tracer as tracer_mod
from tpudml.optim import make_optimizer
from tpudml.parallel.dp import DataParallel
from tpudml.serve import ServeConfig, ServingEngine, poisson_workload

FIXTURES = Path(__file__).parent / "obs_fixtures"
WORLD = 8


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(MeshConfig({"data": WORLD}))


@pytest.fixture(scope="module")
def batch():
    images, labels = synthetic_classification(WORLD * 4, (28, 28, 1), 10, seed=7)
    return np.asarray(images), np.asarray(labels)


# ------------------------------------------------------------ tracer core


def golden_tracer() -> Tracer:
    """The fixed event log behind ``obs_fixtures/golden_trace.json`` —
    one span per feed source, explicit timestamps (no wall clock)."""
    tr = Tracer(clock=lambda: 0.0)
    tr.add_complete("train_step", cat="step", ts_us=0, dur_us=1500, tid=0)
    tr.add_complete("psum", cat="comm", ts_us=100, dur_us=300, tid=0,
                    args={"bytes": 4096})
    tr.add_complete("checkpoint_save", cat="checkpoint", ts_us=1600,
                    dur_us=400, tid=1, args={"step": 3})
    tr.instant("sentinel_trip", cat="sentinel", ts_us=900,
               args={"step": 2, "consecutive": 1})
    tr.instant("launch_restart", cat="launch", ts_us=2100,
               args={"attempt": 1, "why": "exit 1"})
    return tr


def test_chrome_trace_matches_golden_bytes():
    """Byte-for-byte against the checked-in fixture: any change to the
    sort order, key set, or serialization is a schema change and must
    bump TRACE_SCHEMA_VERSION + regenerate the golden."""
    got = dump_trace(golden_tracer().chrome_trace(pid=0)).encode()
    want = (FIXTURES / "golden_trace.json").read_bytes()
    assert got == want


def test_chrome_trace_validates_and_sorts():
    doc = golden_tracer().chrome_trace(pid=0)
    validate_chrome_trace(doc)
    events = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    # Deterministic order: ts ascending, parents (longer dur) first.
    keys = [(e["ts"], -e.get("dur", 0), e["tid"]) for e in events]
    assert keys == sorted(keys)
    assert events[0]["name"] == "train_step"  # contains the comm span
    assert doc["metadata"]["tpudml_trace_schema"] == TRACE_SCHEMA_VERSION


def test_validate_rejects_malformed():
    with pytest.raises(ValueError, match="traceEvents"):
        validate_chrome_trace([])
    with pytest.raises(ValueError, match="schema"):
        validate_chrome_trace({"traceEvents": [], "metadata": {}})
    doc = golden_tracer().chrome_trace(pid=0)
    doc["traceEvents"][1]["ts"] = 0.5  # float timestamps break Perfetto
    with pytest.raises(ValueError, match="int ts"):
        validate_chrome_trace(doc)


def test_merge_chrome_traces_one_pid_track_per_process():
    """Multi-process evidence path (tpudml.elastic drill): per-rank
    exports merge into one document with one pid track per process,
    deterministically ordered, and a pid collision is a loud error."""
    from tpudml.obs import merge_chrome_traces

    docs = [golden_tracer().chrome_trace(pid=p) for p in (1, 0)]
    merged = merge_chrome_traces(docs)
    validate_chrome_trace(merged)
    metas = [e for e in merged["traceEvents"] if e["ph"] == "M"]
    events = [e for e in merged["traceEvents"] if e["ph"] != "M"]
    assert [m["pid"] for m in metas] == [0, 1]
    assert {e["pid"] for e in events} == {0, 1}
    keys = [(e["pid"], e["ts"], -e.get("dur", 0)) for e in events]
    assert keys == sorted(keys)
    # Byte-deterministic regardless of input order.
    assert dump_trace(merged) == dump_trace(merge_chrome_traces(docs[::-1]))
    with pytest.raises(ValueError, match="duplicate pid"):
        merge_chrome_traces([docs[0], docs[0]])


def test_tracer_summary_percentiles():
    s = golden_tracer().summary()
    assert s["schema"] == TRACE_SCHEMA_VERSION
    st = s["spans"]["step/train_step"]
    assert st["count"] == 1 and st["total_us"] == 1500
    assert st["p50_us"] == 1500 and st["p99_us"] == 1500
    assert set(s["spans"]) == {
        "step/train_step", "comm/psum", "checkpoint/checkpoint_save",
        "sentinel/sentinel_trip", "launch/launch_restart",
    }


def test_ambient_tracer_scoping():
    assert get_tracer() is tracer_mod.NULL_TRACER
    tr = Tracer()
    with use_tracer(tr):
        assert get_tracer() is tr
        with get_tracer().span("inner", cat="test"):
            pass
    assert get_tracer() is tracer_mod.NULL_TRACER
    assert [s.name for s in tr.events] == ["inner"]


# -------------------------------------------------------- metrics writer


def test_metrics_jsonl_stays_strict_json_through_nonfinite(tmp_path):
    w = MetricsWriter(tmp_path, run_name="nf")
    w.add_scalar("Train Loss", 1.25, 0)
    w.add_scalar("Train Loss", float("nan"), 1)
    w.add_scalar("Train Loss", float("inf"), 2)
    w.add_scalars({"obs/grad_norm": float("-inf"), "obs/loss": 0.5}, 3)
    w.close()
    lines = (w.run_dir / "metrics.jsonl").read_text().splitlines()
    recs = [json.loads(line) for line in lines]  # every line strict JSON
    assert recs[0]["value"] == 1.25 and "finite" not in recs[0]
    for r in recs[1:3]:
        assert r["value"] is None and r["finite"] is False
    assert recs[3]["tag"] == "obs/grad_norm" and recs[3]["value"] is None
    assert recs[4] == {k: recs[4][k] for k in ("tag", "value", "step",
                                               "wall_time")}


# ------------------------------------------------- serve trace conversion


def test_serve_trace_events_pure_conversion():
    events = [
        ("admit", 7, 0, 0),
        ("spec", 7, 0, 2, 1),
        ("reject", 9, -1, 3),
        ("evict", 7, 0, 5),
        ("admit", 8, 0, 6),  # still resident at log end
    ]
    evs = serve_trace_events(events, step_time_s=0.01)
    spans = [e for e in evs if e["ph"] == "X"]
    instants = [e for e in evs if e["ph"] == "i"]
    assert len(instants) == 5
    assert {(s["name"], s["ts"], s["dur"], s["tid"]) for s in spans} == {
        ("slot0:rid7", 0, 50_000, 1),
        ("slot0:rid8", 60_000, 0, 1),  # closed at max_step
    }
    reject = next(e for e in instants if e["name"] == "reject")
    assert reject["tid"] == 0 and reject["ts"] == 30_000
    # Pure function: same events in, same events out.
    assert serve_trace_events(events, step_time_s=0.01) == evs


def test_paged_spec_overload_trace_is_byte_deterministic(tmp_path):
    """PR 11's golden config (paged + spec + bounded queue at 2× overload
    on the virtual clock): two identical runs must write byte-identical
    trace.json files, with queue, slot-residency, and spec events all
    present."""
    model = TransformerLM(vocab_size=48, embed_dim=32, num_heads=4,
                          num_layers=2, num_kv_heads=2, max_len=32,
                          rope=True)
    params, _ = model.init(jax.random.key(6))
    cfg = ServeConfig(slots=1, max_len=32, prefill_chunk=4,
                      cache_layout="paged", page_size=4, spec_k=2,
                      max_queue=2, step_time_s=0.01)

    def once(tag):
        reqs, _ = poisson_workload(10, 40.0, seed=5, vocab_size=48,
                                   prompt_len=(2, 6), new_tokens=(8, 8))
        report = ServingEngine(model, params, cfg, draft_layers=1).run(reqs)
        path = write_serve_trace(report, tmp_path / tag / "trace.json",
                                 step_time_s=0.01, pid=0)
        return report, path.read_bytes()

    report, a = once("a")
    _, b = once("b")
    assert a == b

    doc = json.loads(a)
    validate_chrome_trace(doc)
    kinds = {e["name"] for e in doc["traceEvents"] if e["ph"] == "i"}
    assert {"admit", "spec", "reject"} <= kinds  # overload guard engaged
    residency = [e for e in doc["traceEvents"]
                 if e["ph"] == "X" and e["name"].startswith("slot")]
    assert residency and all(e["tid"] >= 1 for e in residency)
    assert any(e["tid"] == 0 for e in doc["traceEvents"]
               if e.get("name") == "reject")
    assert report.rejected > 0


# --------------------------------------------- engine knob: off = free


def test_obs_off_allocates_zero_spans(mesh, batch):
    dp = DataParallel(LeNet(), make_optimizer("sgd", 0.01), mesh)
    ts = dp.create_state(seed_key(0))
    step = dp.make_train_step()
    before = tracer_mod.SPANS_ALLOCATED
    for _ in range(2):
        ts, m = step(ts, *batch)
    jax.block_until_ready(m["loss"])
    assert tracer_mod.SPANS_ALLOCATED == before
    assert "step_stats" not in m


def test_obs_on_records_spans_and_stepstats(mesh, batch):
    tr = Tracer()
    dp = DataParallel(LeNet(), make_optimizer("sgd", 0.01), mesh, obs=tr)
    ts = dp.create_state(seed_key(0))
    step = dp.make_train_step()
    ts, m0 = step(ts, *batch)
    ts, m1 = step(ts, *batch)
    assert [(s.cat, s.name) for s in tr.events] == [("step", "train_step")] * 2

    stats = m1["step_stats"]
    scal = {k: float(v) for k, v in stats.to_scalars().items()}
    assert scal["loss"] == pytest.approx(float(m1["loss"]), rel=1e-6)
    assert scal["grad_norm"] > 0
    assert scal["sentinel_skips"] == 0 and scal["sentinel_consecutive"] == 0
    # comm_bytes is (per-step ring-model constant) × (step+1).
    b0 = float(m0["step_stats"].comm_bytes)
    assert b0 > 0 and scal["comm_bytes"] == pytest.approx(2 * b0, rel=1e-6)


def test_split_step_stats_match_measured_comm(mesh, batch):
    """The in-graph comm-bytes leaf is priced on the same ring model as
    the measured path, so one split step's StepStats reproduces the
    CommStats byte accounting exactly."""
    dp = DataParallel(LeNet(), make_optimizer("sgd", 0.01), mesh,
                      measure_comm=True, obs=True)
    ts = dp.create_state(seed_key(0))
    ts, m = dp.make_train_step()(ts, *batch)
    got = float(m["step_stats"].comm_bytes)
    assert got == pytest.approx(dp.comm_stats.comm_bytes, rel=1e-9)
    # measure_comm feeds the tracer too: comm spans carry byte args.
    comm = [s for s in dp.tracer.events if s.cat == "comm"]
    assert comm and all(s.args and s.args.get("bytes", 0) > 0 for s in comm)


# ------------------------------------------------------------ drift gate


def test_drift_cli_live_regimes_within_threshold(tmp_path, capsys):
    """The CI gate on the live world-4 regimes (DP/SGD, ZeRO-1/Adam):
    static cost reports agree with measured CommStats within 10%."""
    from tpudml.obs.__main__ import main

    out = tmp_path / "drift.json"
    rc = main(["--check-drift", "--out", str(out), "--format", "json"])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert report["ok"] and report["worst_rel_err"] <= 0.10
    assert {r["entrypoint"] for r in report["records"]} == {
        "task2_dp", "dp_zero1"}
    assert json.loads(out.read_text())["records"] == report["records"]


def test_drift_cli_gates_on_seeded_mismatch(tmp_path, capsys):
    from tpudml.obs.__main__ import main

    fixture = tmp_path / "pairs.json"
    fixture.write_text(json.dumps([
        {"entrypoint": "task2_dp", "static_wire_bytes": 100.0,
         "measured_wire_bytes": 200.0},
        {"entrypoint": "dp_zero1", "static_wire_bytes": 100.0,
         "measured_wire_bytes": 101.0},
    ]))
    out = tmp_path / "drift.json"
    rc = main(["--check-drift", "--fixture", str(fixture),
               "--out", str(out), "--format", "github"])
    assert rc == 1
    lines = capsys.readouterr().out.splitlines()
    assert len(lines) == 1 and lines[0].startswith("::warning ")
    assert "task2_dp" in lines[0] and "50.00%" in lines[0]
    report = json.loads(out.read_text())
    assert not report["ok"]
    assert [r["status"] for r in report["records"]] == ["WARN", "OK"]

    # Report-only mode never gates.
    assert main(["--fixture", str(fixture), "--out", str(out)]) == 0
    capsys.readouterr()


# ------------------------------------------------------------ obs_report


def test_obs_report_summarizes_run_dir(tmp_path, capsys):
    from tools.obs_report import main, report
    from tpudml.obs.drift import (
        build_drift_report,
        drift_from_pairs,
        write_drift_report,
    )

    w = MetricsWriter(tmp_path, run_name="rpt")
    w.add_scalar("Train Loss", 2.3, 0)
    w.add_scalar("Train Loss", float("nan"), 1)
    w.close()
    run_dir = w.run_dir
    golden_tracer().export(run_dir / "trace.json", pid=0)
    write_drift_report(
        build_drift_report(drift_from_pairs([
            {"entrypoint": "task2_dp", "static_wire_bytes": 100.0,
             "measured_wire_bytes": 100.0}])),
        str(run_dir / "obs" / "drift.json"))

    text = report(run_dir)
    assert "Train Loss" in text and "non-finite" in text
    assert "step/train_step" in text and "comm/psum" in text
    assert "task2_dp" in text and "OK" in text

    assert main([str(run_dir)]) == 0
    assert "metrics.jsonl" in capsys.readouterr().out
    assert main([str(run_dir / "nope")]) == 2
