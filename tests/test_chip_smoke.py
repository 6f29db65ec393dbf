"""``chip_smoke.py`` without the chip: it must refuse to run here, and its
phases — the first rehearsal of the on-chip-measurement guide, kept as a
test — must run to the end at toy widths on the CPU mesh."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

import chip_smoke

REPO = Path(__file__).resolve().parents[1]

TOY_LM = dict(vocab_size=64, embed_dim=32, num_heads=4, num_layers=2,
              num_kv_heads=2)
TOY_LM_ARGV = ["--vocab", "64", "--embed_dim", "32", "--num_heads", "4",
               "--num_kv_heads", "2", "--num_layers", "2"]


def test_refuses_to_run_without_a_chip():
    """No accelerator: non-zero exit within seconds, last line
    ``"ok": false``, and no phase after ``device`` ran."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is False and last["device"]["platform"] == "cpu"
    assert not any("phase" in line for line in lines), proc.stdout


@pytest.fixture
def on_cpu_mesh(monkeypatch, tmp_path):
    """What the ``device`` phase guarantees on the chip, stubbed for the
    CPU mesh: kernels dispatch to their reference math here, and the CPU
    backend reports no memory statistics."""
    monkeypatch.setattr(chip_smoke, "kernels_in_program", lambda text: True)
    monkeypatch.setattr(chip_smoke, "device_bytes_in_use",
                        lambda: [1] * len(jax.devices()))
    return ["--log_dir", str(tmp_path)]


def test_train_lm_phase_toy(on_cpu_mesh):
    rec = chip_smoke.train_lm_phase(TOY_LM, seq_len=32, batch=4, steps=6,
                                    parity_batch=2)
    assert rec["ok"] and len(rec["losses"]) == 6
    assert rec["losses"][-1] < rec["losses"][0]


def test_train_cli_phase_toy(on_cpu_mesh, monkeypatch):
    """The whole north-star trainer, on a 64-image slice of the synthetic
    set so the CPU pays one ResNet-18 compile and a handful of steps (too
    few to learn: the accuracy margin is the chip run's to hold)."""
    import tasks.north_star
    from tasks.common import load_splits
    from tpudml.data.datasets import ArrayDataset

    def tiny_splits(cfg):
        return tuple(ArrayDataset(s.images[:64], s.labels[:64], name=s.name)
                     for s in load_splits(cfg))

    monkeypatch.setattr(tasks.north_star, "load_splits", tiny_splits)
    monkeypatch.setattr(chip_smoke, "CLI_MIN_ACCURACY", 0.0)
    rec = chip_smoke.train_cli_phase(
        ["--model", "resnet18", "--dataset", "synthetic", "--epochs", "1",
         "--batch_size", "16", "--log_every", "0", "--n_devices", "1",
         "--f32"] + on_cpu_mesh)
    assert rec["ok"] and rec["steps"] == 4 and rec["world"] == 1


def test_serve_phase_toy(on_cpu_mesh):
    rec = chip_smoke.serve_phase(
        TOY_LM_ARGV + ["--max_len", "64", "--cache_kind", "bf16", "--slots",
                       "2", "--prefill_chunk", "8", "--n_requests", "6",
                       "--qps", "inf", "--prompt_len", "10", "30",
                       "--new_tokens", "3", "6", "--seed", "0"] + on_cpu_mesh,
        paged=["--paged", "--page_size", "8", "--prefix_sharing"])
    assert rec["ok"] and rec["checked"]["requests"] == 6
    assert rec["arms"]["dense"]["generated_tokens"] == \
        rec["arms"]["paged"]["generated_tokens"]


def test_multichip_phase_toy(on_cpu_mesh):
    """The guide's second rehearsal: the four-chip phase on four of the
    CPU mesh's virtual devices."""
    rec = chip_smoke.multichip_phase(
        TOY_LM_ARGV + ["--seq_len", "32", "--batch_size", "4", "--attn",
                       "flash", "--fused_ln", "--fused_xent", "--rope",
                       "--steps", "3", "--log_every", "1", "--lr", "3e-4",
                       "--seed", "0"] + on_cpu_mesh,
        n_devices=4)
    assert rec["ok"] and rec["global_batch"] == 4


def test_compile_cache_placement(monkeypatch, tmp_path):
    """Placed from outside when JAX_COMPILATION_CACHE_DIR is set (no
    directory set in code); one fixed in-checkout directory otherwise."""
    from tpudml.core.compile_cache import (
        DEFAULT_CACHE_DIR,
        enable_compile_cache,
    )

    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(DEFAULT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_CACHE_DIR)
        assert DEFAULT_CACHE_DIR == REPO / ".jax_cache"
        assert enable_compile_cache() == str(DEFAULT_CACHE_DIR)  # stable
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
