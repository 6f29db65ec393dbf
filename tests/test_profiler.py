"""Profiler + task-level checkpoint/resume/profile flag tests."""

import glob
import os

import jax.numpy as jnp
import numpy as np
import pytest

from tpudml.metrics.profiler import annotate, trace


def test_trace_disabled_is_noop(tmp_path):
    with trace(tmp_path / "prof", enabled=False):
        pass
    assert not (tmp_path / "prof").exists()


def test_trace_captures_events(tmp_path):
    with trace(tmp_path / "prof"):
        with annotate("tiny"):
            jnp.dot(jnp.ones((8, 8)), jnp.ones((8, 8))).block_until_ready()
    files = glob.glob(str(tmp_path / "prof" / "**" / "*"), recursive=True)
    assert any(os.path.isfile(f) for f in files)  # trace artifacts written


def test_task1_checkpoint_resume_cli(tmp_path):
    """--ckpt_dir/--ckpt_every/--resume through the real entrypoint."""
    from tasks.task1 import main

    common = [
        "--dataset", "synthetic", "--epochs", "1", "--optimizer", "adam",
        "--lr", "0.002", "--log_every", "0", "--batch_size", "256",
        "--log_dir", str(tmp_path / "logs"), "--ckpt_dir", str(tmp_path / "ckpt"),
        "--ckpt_every", "8",
    ]
    main(common)
    steps = sorted(
        int(p.split("_")[-1]) for p in os.listdir(tmp_path / "ckpt")
    )
    assert steps and steps[-1] == 16  # 4096/256 = 16 steps, final save incl.

    # --epochs is a TOTAL budget: resuming a finished 1-epoch run with
    # the same budget trains nothing further...
    metrics = main(common + ["--resume"])
    assert metrics["steps"] == 16
    # ...and raising the budget to 2 trains exactly the remaining epoch.
    metrics = main(common[:3] + ["2"] + common[4:] + ["--resume"])
    steps_after = sorted(
        int(p.split("_")[-1]) for p in os.listdir(tmp_path / "ckpt")
    )
    assert steps_after[-1] == 32  # resumed at 16, trained 16 more
    assert np.isfinite(metrics["loss"])


# Slow lane: jax.profiler's stop_trace has been observed to take 6+ min
# in this container when finalizing a full-epoch trace (training itself
# finishes in ~10 s; the hang is entirely inside the trace export) —
# that is most of the tier-1 time budget for one test. The trace API
# itself stays pinned fast by test_trace_captures_events above.
@pytest.mark.slow
def test_task1_profile_flag_writes_trace(tmp_path):
    from tasks.task1 import main

    main([
        "--dataset", "synthetic", "--epochs", "1", "--optimizer", "adam",
        "--lr", "0.002", "--log_every", "0", "--batch_size", "1024",
        "--log_dir", str(tmp_path / "logs"), "--profile",
    ])
    traces = glob.glob(str(tmp_path / "logs" / "**" / "profile" / "**"), recursive=True)
    assert any(os.path.isfile(f) for f in traces)
