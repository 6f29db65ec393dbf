"""Scaffolding shared by the task entrypoints (device selection, dataset
splits) so launch semantics can't silently diverge between tasks."""

from __future__ import annotations

import jax

from tpudml.core.config import TrainConfig
from tpudml.data import load_dataset


def select_devices(cfg: TrainConfig) -> list:
    """Visible devices, honoring --n_devices on a single host.

    ``--n_devices N`` on one host uses the first N chips (``--n_devices 1``
    is the single-machine baseline of sections/task3.tex:23); in multi-process
    runs the world size is fixed by the launcher, so the flag is ignored.
    """
    devices = jax.devices()
    n = cfg.dist.num_processes if cfg.dist.explicit_world else None
    if n is not None and n <= len(devices) and jax.process_count() == 1:
        devices = devices[:n]
    return devices


def init_distributed(cfg: TrainConfig) -> None:
    """Multi-process init + same-program guard, in one place so no
    entrypoint can forget the guard: after the rendezvous, every process
    allgathers a hash of its rank-invariant config and fails fast on
    divergence (SURVEY.md §5.2 — a mismatched rank would otherwise
    deadlock in the first collective)."""
    from tpudml.core.compile_cache import enable_compile_cache
    from tpudml.core.dist import assert_same_program, distributed_init

    enable_compile_cache()
    distributed_init(cfg.dist)
    assert_same_program(cfg.fingerprint(), "task config")


def setup_checkpointing(cfg: TrainConfig, ts):
    """(train_state, hooks, manager) per the config's checkpoint fields.

    With ``--ckpt_dir`` set: ``--resume`` restores the LATEST VALID
    checkpoint into ``ts`` — restores verify per-leaf checksums and walk
    past corrupt/partial ``step_*`` dirs (every host reads the same files
    — the persistent form of the reference's rank-0 parameter broadcast,
    SURVEY.md §5.4; integrity semantics in docs/RESILIENCE.md) — and
    ``--ckpt_every N`` installs a rolling-save train_loop hook. The
    caller does the final save via the returned manager.
    """
    if not cfg.ckpt_dir:
        return ts, [], None
    from tpudml.checkpoint import CheckpointHook, CheckpointManager

    mgr = CheckpointManager(cfg.ckpt_dir)
    if cfg.resume:
        ts = mgr.restore_latest(ts)
    hooks = [CheckpointHook(mgr, every_n_steps=cfg.ckpt_every)] if cfg.ckpt_every else []
    return ts, hooks, mgr


def final_checkpoint(mgr, ts) -> None:
    """End-of-run save, skipped when the rolling hook already wrote this
    exact step (avoids re-gathering + rewriting identical bytes)."""
    if mgr is not None and mgr.latest_step() != int(ts.step):
        mgr.save(ts, int(ts.step))


def load_splits(cfg: TrainConfig):
    """(train, test) ArrayDatasets per the config's dataset selection."""
    train_set = load_dataset(
        cfg.data.dataset, cfg.data.data_dir, "train",
        synthetic_fallback=cfg.data.synthetic_fallback,
    )
    test_set = load_dataset(
        cfg.data.dataset, cfg.data.data_dir, "test",
        synthetic_fallback=cfg.data.synthetic_fallback,
    )
    return train_set, test_set
