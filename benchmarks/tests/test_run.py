"""The harness end to end at toy size on the CPU: the result line, the
refusal to run without a chip, the timed path broken underneath, and the
control one precision down. `run.run_cell` is everything of a run after the
look for a chip."""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from benchmarks import cells, run
from benchmarks.drivers import train_loop as train_driver
from benchmarks.tests import toy

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(cell, seed=5, seconds=0.5, tmp_path="/tmp"):
    return run.run_cell(cell, seed, seconds, False, jax.devices()[:cell.chips],
                        time.perf_counter(), str(tmp_path))


@pytest.mark.parametrize("make", [toy.train_cell, toy.serve_cell], ids=["train", "serve"])
def test_result_line_keys_and_correct(make, capsys):
    cell = make()
    result = _run(cell)
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(set(v) == {"value", "unit"} and v["value"] > 0
               for v in result["metrics"].values())
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    json.dumps(result)
    compared = [json.loads(line) for line in capsys.readouterr().out.splitlines()
                if line.startswith('{"compared"')]
    assert compared and all({"value", "limit", "ok"} <= set(row) for row in compared)


def test_refuses_to_run_without_a_chip():
    root = str(cells.ROOT)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    name = json.load(open(cells.ROOT / "BENCHMARK.json"))["workloads"][0]["name"]
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", name, "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=root, env=env, capture_output=True,
        text=True, timeout=300)
    assert done.returncode != 0
    assert not any(line.startswith('{"correct"') for line in done.stdout.splitlines())
    assert "tpu" in done.stderr


def test_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    import tpudml.train

    def idle_step(model, optimizer, **_):
        return lambda ts, x, y: (ts, {"loss": jnp.float32(5.5)})

    monkeypatch.setattr(tpudml.train, "make_lm_fused_train_step", idle_step)
    result = _run(toy.train_cell())
    assert result["correct"] is False


def test_step_that_leaves_out_half_the_batch_is_not_correct(monkeypatch):
    import tpudml.train

    real = tpudml.train.make_lm_fused_train_step

    def half_step(model, optimizer, **kw):
        step = real(model, optimizer, **kw)
        return lambda ts, x, y: step(ts, x[:2], y[:2])

    monkeypatch.setattr(tpudml.train, "make_lm_fused_train_step", half_step)
    cell = toy.train_cell()
    # at toy size the sound gaps are ~1e-7 (float32 against float32)
    cell.spec["check"]["limits"] = {"loss_gap": 1e-5, "first_grad_gap": 1e-3,
                                    "param_change_gap": 1e-2}
    assert _run(cell)["correct"] is False


@pytest.mark.parametrize("factor", [1.04, 0.96])
def test_learning_rate_off_by_four_percent_is_not_correct(monkeypatch, factor):
    """Judged by the committed cell's limits: the parameters' change is off by
    the same 4 %, over `param_change_gap`."""
    import dataclasses

    import tpudml.train

    real = tpudml.train.make_lm_fused_train_step

    def detuned(model, optimizer, **kw):
        return real(model, dataclasses.replace(optimizer, lr=optimizer.lr * factor), **kw)

    monkeypatch.setattr(tpudml.train, "make_lm_fused_train_step", detuned)
    result = _run(toy.train_cell())
    assert result["correct"] is False


def test_altered_token_is_not_correct(monkeypatch):
    import tpudml.serve.engine as engine

    real = engine.make_decode_step

    def off_by_one(model):
        step = real(model)

        def altered(params, caches, tokens, pos):
            nxt, logits, caches = step(params, caches, tokens, pos)
            return (nxt + 1) % model.vocab_size, logits, caches
        return altered

    monkeypatch.setattr(engine, "make_decode_step", off_by_one)
    assert _run(toy.serve_cell())["correct"] is False


def test_training_control_one_precision_down_fails_the_cells_limits():
    """The reference with weights and Adam moments stored in bfloat16, put in
    the program's place and judged by the limits of the committed cell: an
    update of lr = 3e-4 is under half a bfloat16 ulp of a LayerNorm gain."""
    cell = toy.train_cell()
    sound = train_driver.Program(cell, 7)
    first = sound.first_steps()
    ref = train_driver.reference(cell, 7, first["batches"])
    assert train_driver.judge(cell, first, ref).correct
    low = train_driver.reference(cell, 7, first["batches"],
                                 cell.spec["check"]["control"]["reference_store_dtype"])
    verdict = train_driver.judge(cell, low, ref)
    assert not verdict.correct
    assert "param_change_gap" in {r["name"] for r in verdict.rows if not r["ok"]}


def test_serving_control_one_precision_down_is_not_correct(capsys):
    """The program's own engine with int8 weights switched on (the cell's
    `check.controls`) serves tokens that lie further under the reference's
    best than the toy limits allow (the sound engine's lie at 0)."""
    cell = toy.serve_cell()
    cell.spec["engine"]["serve_config"].update(cell.spec["check"]["controls"]["weight_int8"])
    assert _run(cell, seconds=4.0)["correct"] is False
    rows = [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith('{"compared"')]
    assert "served_mean_gap" in {r["compared"] for r in rows if not r["ok"]}
