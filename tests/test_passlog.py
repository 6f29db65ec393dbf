"""The pass log (`tpudml/obs/passlog.py`): what every `ServingEngine.run`
and `train_loop` keeps of its passes with no tracer installed.

Load-bearing properties:

- the arithmetic on hand-made span sequences: a serving pass's class (steady
  / admitting / idle, an admission counting against the pass AFTER it too),
  the ring's wrap-around at capacity with its memory unchanged, the eight
  longest passes of a class kept, p50 / max;
- a planted stall in `ServingEngine.run` (a `device_get` that sleeps once)
  comes back as one kept steady pass whose `fetch` holds the time, with a
  punctual heartbeat, as one WARNING on `tpudml.obs` and nothing on stdout;
  a hook that sleeps once in `train_loop` comes back under `hooks`;
- the log changes nothing it watches: the same tokens and events with the
  feed cut, the tracer's own spans beside it, one row a `serve/iter`.
"""

import json
import logging
import threading
import time

import jax
import numpy as np
import pytest

from tpudml.core.prng import seed_key
from tpudml.data.datasets import ArrayDataset
from tpudml.data.loader import DataLoader
from tpudml.models import LeNet, TransformerLM
from tpudml.obs import PassLog, Tracer, last_pass_log, passlog, span, use_tracer
from tpudml.optim import make_optimizer
from tpudml.serve import ServeConfig, ServingEngine, poisson_workload
from tpudml.serve import engine as engine_mod
from tpudml.train import train_loop

V = 48


def feed(log, passes):
    """Hand-made passes: each a list of (child span name, ms, counters), the
    last entry the `iter` itself. Times are laid end to end from 100 s."""
    t = 100.0
    for spans in passes:
        log.begin()
        t_pass = t
        for name, ms, args in spans[:-1]:
            log.end(name, t, t + ms / 1e3, args)
            t += ms / 1e3
        name, ms, args = spans[-1]
        assert name == "iter"
        log.end("iter", t_pass, t_pass + ms / 1e3, args)
        t = t_pass + ms / 1e3


def a_pass(step, ms=12.0, admits=0, fetch=True, idle=False, queue=0, chunks=2):
    spans = [("admit", 1.0, {"rid": step, "chunks": chunks})] * admits
    if idle:
        spans.append(("idle", ms - 1.0, None))
    elif fetch:
        spans += [("dispatch", 0.5, {"step": step}), ("fetch", ms - 2.0 - admits, {}),
                  ("commit", 0.25, {})]
    else:
        spans.append(("dispatch", 0.5, {"step": step}))
    return spans + [("iter", ms, {"step": step, "active": 3, "queue": queue})]


@pytest.fixture
def log():
    made = PassLog("serve", capacity=16)
    made.start()
    yield made
    made.stop()


# ------------------------------------------------------------ the arithmetic


@pytest.mark.parametrize("passes, classes", [
    # an admission counts against its own pass and the one after it: that
    # one's fetch waits for the prefill chunks queued in front of its step
    ([a_pass(0, admits=1, fetch=False), a_pass(1), a_pass(2), a_pass(3)],
     ["admitting", "admitting", "steady", "steady"]),
    ([a_pass(0), a_pass(1, admits=2), a_pass(2), a_pass(3)],
     ["steady", "admitting", "admitting", "steady"]),
    ([a_pass(0, idle=True), a_pass(0, admits=1, fetch=False), a_pass(1, idle=True)],
     ["idle", "admitting", "idle"]),
    # a pass that neither fetched nor slept (the queue drained by expiry)
    ([[("iter", 0.01, {"step": 4, "active": 0, "queue": 0})], a_pass(4)],
     ["admitting", "steady"]),
])
def test_serving_pass_classes(log, passes, classes):
    feed(log, passes)
    rows = log.rows()
    assert [log.classes[c] for c in rows["cls"]] == classes
    assert log.summary()["passes"] == len(passes)
    for name in log.classes:
        assert log.summary()["classes"][name]["passes"] == classes.count(name)


def test_row_holds_the_phases_and_the_counters(log):
    feed(log, [a_pass(7, ms=20.0, admits=2, queue=5, chunks=3)])
    (row,) = log.rows()
    assert row["step"] == 7 and row["queue"] == 5 and row["active"] == 3
    assert row["admits"] == 2 and row["chunks"] == 6
    assert row["ms"] == pytest.approx(20.0, rel=1e-4)
    assert row["admit_ms"] == pytest.approx(2.0, rel=1e-4)
    assert row["fetch_ms"] == pytest.approx(16.0, rel=1e-4)
    assert row["dispatch_ms"] == pytest.approx(0.5) and row["commit_ms"] == pytest.approx(0.25)
    assert row["idle_ms"] == 0.0 and row["arrive_ms"] == 0.0
    (kept,) = log.slow("admitting")
    assert kept["phases_ms"]["fetch"] == pytest.approx(16.0) and kept["chunks"] == 6
    assert {"cpu_ms", "vol_switches", "invol_switches", "major_faults", "gc_ms",
            "compiles", "compile_ms", "hiccup_ms", "start_s", "step"} <= set(kept)
    json.dumps(kept)  # the WARNING line and ServeReport carry it as JSON


def test_ring_wraps_at_capacity_and_its_memory_is_fixed(log):
    held = log._rows.nbytes
    address = log._rows.__array_interface__["data"][0]
    feed(log, [a_pass(i, ms=10.0 + i) for i in range(40)])
    assert log._rows.nbytes == held
    assert log._rows.__array_interface__["data"][0] == address
    rows = log.rows()
    assert len(rows) == 16 and list(rows["step"]) == list(range(24, 40))
    assert np.all(np.diff(rows["start_s"]) > 0)  # oldest first
    summary = log.summary()
    assert summary["passes"] == 40 and summary["capacity"] == 16
    assert summary["classes"]["steady"]["passes"] == 40  # counted over the whole run
    # the percentiles are of what the ring holds, the maximum of the run
    assert summary["classes"]["steady"]["p50_ms"] == pytest.approx(41.5)
    assert summary["classes"]["steady"]["max_ms"] == pytest.approx(49.0)


def test_the_eight_longest_of_a_class_are_kept_whole(log):
    order = np.random.default_rng(5).permutation(30)
    feed(log, [a_pass(int(i), ms=10.0 + i) for i in order]
         + [a_pass(99, ms=500.0, admits=1)])
    kept = log.slow("steady")
    # the pass behind the admitting one is admitting too, so steps 0..29 less none
    assert [k["step"] for k in kept] == list(range(29, 21, -1))
    assert [k["ms"] for k in kept] == sorted((k["ms"] for k in kept), reverse=True)
    assert [k["step"] for k in log.slow("admitting")] == [99]
    assert log.slow("idle") == []
    assert log.summary()["classes"]["idle"] == {
        "passes": 0, "p50_ms": None, "p99_ms": None, "max_ms": None}


def test_p50_and_max_by_class(log):
    feed(log, [a_pass(i, ms=ms) for i, ms in enumerate([10.0, 11.0, 12.0, 13.0, 90.0])]
         + [a_pass(5, idle=True, ms=50.0)])
    steady = log.summary()["classes"]["steady"]
    assert steady["p50_ms"] == pytest.approx(12.0) and steady["max_ms"] == pytest.approx(90.0)
    assert log.summary()["classes"]["idle"]["max_ms"] == pytest.approx(50.0)


def test_training_passes_are_step_or_other():
    log = PassLog("train", capacity=8)
    log.start()
    try:
        feed(log, [
            [("next_batch", 1.0, {}), ("step", 5.0, {}), ("hooks", 2.0, {}),
             ("iter", 8.5, {"step": 1})],
            [("next_batch", 1.0, {}), ("step", 5.0, {}), ("log_sync", 30.0, {}),
             ("iter", 36.5, {"step": 2})],
            [("next_batch", 0.5, {}), ("iter", 0.6, {"step": 3})],
        ])
    finally:
        log.stop()
    rows = log.rows()
    assert [log.classes[c] for c in rows["cls"]] == ["step", "step", "other"]
    assert list(rows["log_sync_ms"]) == [0.0, 30.0, 0.0]
    assert log.slow("step")[0]["phases_ms"] == {
        "next_batch": pytest.approx(1.0), "step": pytest.approx(5.0),
        "log_sync": pytest.approx(30.0), "hooks": 0.0}
    assert log.counters == () and "queue" not in rows.dtype.names


def test_slow_passes_warn_once_each_over_three_medians_and_50_ms(log, caplog):
    feed(log, [a_pass(i, ms=12.0) for i in range(10)]
         + [a_pass(10, ms=40.0), a_pass(11, ms=310.0)]      # 40 ms: over 3 x, under 50 ms
         + [a_pass(12, ms=900.0, admits=1)])                # not the regular class
    with caplog.at_level(logging.WARNING, logger="tpudml.obs"):
        assert log.warn_slow() == 1
    (record,) = caplog.records
    assert record.name == "tpudml.obs" and record.levelno == logging.WARNING
    row = json.loads(record.getMessage().split(": ", 1)[1])
    assert row["step"] == 11 and row["class"] == "steady"
    assert row["phases_ms"]["fetch"] == pytest.approx(308.0)


def test_the_heartbeat_counts_a_wake_up_that_is_overdue_now():
    heart = passlog._Heartbeat(0.010)  # never started: as if the process froze
    due = heart._due
    assert heart.take(due - 0.001) == 0.0
    assert heart.take(due + 0.250) == pytest.approx(0.250)
    heart._late = 0.5
    assert heart.take(due) == 0.5 and heart.take(due) == 0.0


# ------------------------------------------------------------------- serving


@pytest.fixture(scope="module")
def lm():
    model = TransformerLM(vocab_size=V, embed_dim=32, num_heads=4, num_layers=2,
                          max_len=64, rope=True, num_kv_heads=2)
    params, _ = model.init(jax.random.key(0))
    return model, params


def _engine(lm, **config):
    model, params = lm
    return ServingEngine(model, params, ServeConfig(
        **{"slots": 3, "max_len": 64, "prefill_chunk": 8, **config}))


def _requests(n=10, new_tokens=(3, 8), qps=200.0):
    reqs, _ = poisson_workload(n, qps, seed=11, vocab_size=V,
                               prompt_len=(2, 20), new_tokens=new_tokens)
    return reqs


def test_a_planted_stall_is_kept_whole_and_warned_of(lm, monkeypatch, caplog, capsys):
    engine = _engine(lm)
    reqs = _requests(2, new_tokens=(40, 40), qps=float("inf"))  # both arrive at 0
    engine.run(reqs)  # compiles here, not in the run that is read
    capsys.readouterr()
    fetches, real = [], jax.device_get

    def device_get(x):
        fetches.append(None)
        if len(fetches) == 20:  # both answers are decoding: a steady pass
            time.sleep(0.3)
        return real(x)

    monkeypatch.setattr(engine_mod.jax, "device_get", device_get)
    with caplog.at_level(logging.WARNING, logger="tpudml.obs"):
        report = engine.run(reqs)
    assert capsys.readouterr().out == ""
    stalled = [r for r in report.passes["slow"]["steady"] if r["ms"] >= 300.0]
    assert len(stalled) == 1
    (kept,) = stalled
    assert kept["phases_ms"]["fetch"] >= 300.0
    assert kept["ms"] - kept["phases_ms"]["fetch"] < 50.0
    assert kept["hiccup_ms"] < 150.0   # the host was alive: the heartbeat ran on time
    assert kept["compiles"] == 0 and kept["admits"] == 0 and kept["active"] == 2
    assert report.passes["classes"]["steady"]["max_ms"] == kept["ms"]
    assert report.latency_summary()["steady_pass_max_s"] == pytest.approx(kept["ms"] / 1e3)
    assert report.latency_summary()["steady_pass_p50_s"] < 0.1
    warned = [json.loads(r.getMessage().split(": ", 1)[1]) for r in caplog.records
              if r.name == "tpudml.obs"]
    assert [w for w in warned if w["ms"] >= 300.0] == [kept]
    # the benchmark's readers get no report: the log stays reachable
    assert last_pass_log("serve").summary() == report.passes
    assert not any(t.name == "tpudml-pass-log-heartbeat" for t in
                   threading.enumerate())


def test_one_row_a_pass_beside_the_tracers_own_spans(lm):
    tracer = Tracer()
    with use_tracer(tracer):
        report = _engine(lm).run(_requests())
    rows = last_pass_log("serve").rows()
    passes = [s for s in tracer.events if s.cat == "serve" and s.name == "iter"]
    admits = [s for s in tracer.events if s.cat == "serve" and s.name == "admit"]
    assert len(rows) == len(passes) == report.passes["passes"]
    assert list(rows["step"]) == [p.args["step"] for p in passes]
    assert list(rows["queue"]) == [p.args["queue"] for p in passes]
    assert list(rows["active"]) == [p.args["active"] for p in passes]
    assert rows["admits"].sum() == len(admits) == len(report.requests)
    assert rows["chunks"].sum() == sum(s.args["chunks"] for s in admits)
    assert np.count_nonzero(rows["fetch_ms"]) == report.decode_steps
    # a pass starts on the engine's clock, as RequestStats times are
    assert 0.0 <= rows["start_s"][0] and rows["start_s"][-1] <= report.wall_time
    assert np.all(np.diff(rows["start_s"]) >= 0)
    # The tracer's span of a pass encloses the log's row of it, so the row is
    # never the longer (a span's microseconds are cut, not rounded), and the
    # two time the same region: all but the few passes in which the scheduler
    # took the thread between the two clocks' readings (xdist workers share
    # the cores) agree to half a millisecond.
    over = np.array([p.dur_us / 1e3 - row["ms"] for row, p in zip(rows, passes)])
    assert np.all(over >= -0.002)
    assert np.median(over) < 0.5


@pytest.mark.parametrize("config", [
    {}, {"cache_layout": "paged", "page_size": 8}, {"step_time_s": 0.01}],
    ids=["dense", "paged", "virtual_clock"])
def test_same_tokens_and_events_with_the_feed_cut(lm, monkeypatch, config):
    with_log = _engine(lm, **config).run(_requests())
    monkeypatch.setattr(passlog, "active", lambda kind: None)
    without = _engine(lm, **config).run(_requests())
    assert without.passes["passes"] == 0 < with_log.passes["passes"]
    # Which pass first sees an arrival is wall-clock timing; who is
    # admitted and evicted, and what they are served, is not.
    assert sorted(e[:2] for e in without.events) == sorted(e[:2] for e in with_log.events)
    if "step_time_s" in config:
        assert without.events == with_log.events
        rows = last_pass_log("serve").rows()
        assert len(rows) == 0
    for rid, st in without.requests.items():
        assert st.tokens == with_log.requests[rid].tokens


def test_on_the_virtual_clock_a_pass_starts_in_step_time(lm):
    report = _engine(lm, step_time_s=0.01).run(_requests())
    rows = last_pass_log("serve").rows()
    fetched = rows[rows["fetch_ms"] > 0]
    # ``step`` steps were dispatched when the pass began; the idle skips add to it
    assert np.all(fetched["start_s"] >= fetched["step"] * 0.01 - 1e-9)
    assert rows["start_s"][-1] <= report.wall_time + 1e-9


def test_two_engines_on_two_threads_keep_two_logs(lm):
    reports = {}

    def serve(name, n):
        reports[name] = _engine(lm, step_time_s=0.01).run(_requests(n))

    threads = [threading.Thread(target=serve, args=(name, n))
               for name, n in (("a", 4), ("b", 9))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    for name, n in (("a", 4), ("b", 9)):
        slow = reports[name].passes["slow"]
        assert sum(r["admits"] for rs in slow.values() for r in rs) <= n
        alone = _engine(lm, step_time_s=0.01).run(_requests(n))
        assert reports[name].passes["passes"] == alone.passes["passes"]
    assert passlog._open == {}


def test_a_span_outside_a_loop_feeds_no_log():
    before = last_pass_log("serve")
    with span("iter", "serve", step=0) as it:
        assert not isinstance(it, passlog.LoggedSpan)
    assert last_pass_log("serve") is before


# ------------------------------------------------------------------ training


def _train(hooks=None):
    rng = np.random.default_rng(3)
    data = ArrayDataset(rng.normal(size=(24, 28, 28, 1)).astype(np.float32),
                        rng.integers(0, 10, size=(24,)).astype(np.int32))
    return train_loop(LeNet(), make_optimizer("adam", 1e-3), DataLoader(data, 4), 2,
                      seed_key(0), log_every=2, hooks=hooks)


def test_a_hook_that_sleeps_once_is_found_under_hooks(caplog):
    def hook(*, step, **_):
        if step == 9:
            time.sleep(0.3)

    with caplog.at_level(logging.WARNING, logger="tpudml.obs"):
        _, last = _train(hooks=[hook])
    passes = last["passes"]
    assert passes["kind"] == "train" and passes["passes"] == 14
    assert passes["classes"]["step"]["passes"] == 12 == last["steps"]
    assert passes["classes"]["other"]["passes"] == 2  # the exhausted loader, an epoch
    by_step = {r["step"]: r for r in passes["slow"]["step"]}
    # the first pass compiled the step, and says so
    assert by_step[1]["compiles"] >= 1
    assert by_step[1]["phases_ms"]["step"] >= by_step[1]["compile_ms"] > 0.0
    kept = by_step[9]
    assert kept["ms"] >= 300.0 and kept["compiles"] == 0
    assert kept["phases_ms"]["hooks"] >= 300.0
    assert kept["ms"] - kept["phases_ms"]["hooks"] < 100.0
    assert kept["hiccup_ms"] < 150.0
    warned = [json.loads(r.getMessage().split(": ", 1)[1]) for r in caplog.records
              if r.name == "tpudml.obs"]
    assert kept in warned
    rows = last_pass_log("train").rows()
    assert list(rows["step"][rows["cls"] == 0]) == list(range(1, 13))
    assert np.count_nonzero(rows["log_sync_ms"]) == 6


def test_train_loop_same_parameters_with_the_feed_cut(monkeypatch):
    with_log, last = _train()
    monkeypatch.setattr(passlog, "active", lambda kind: None)
    without, cut = _train()
    assert cut["passes"]["passes"] == 0 < last["passes"]["passes"]
    for a, b in zip(jax.tree.leaves(with_log.params), jax.tree.leaves(without.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
