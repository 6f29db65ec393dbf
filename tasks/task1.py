"""Task 1 — single-device optimizer lab.

Capability parity with the reference entrypoint (codes/task1/pytorch/
model.py:83-111): LeNet-style CNN on MNIST, hand-written GD/SGD/Adam
optimizers, TensorBoard-style loss logging every 20 iters, test-set top-1
accuracy. Reference hyperparameters: batch 200, 1 epoch, custom Adam with
lr = 5e-4·√batch (model.py:96-98) and no bias correction
(MyOptimizer.py:26-43).

TPU-first design: the whole per-batch body (forward, loss, backward,
optimizer update) is one jitted XLA program; device pinning
(``CUDA_VISIBLE_DEVICES``, model.py:110) is unnecessary — XLA owns the chip.

Run: ``python -m tasks.task1 [--optimizer adam_ref] [--epochs 1] ...``
"""

from __future__ import annotations

import math

from tasks.common import final_checkpoint, setup_checkpointing
from tpudml.core.compile_cache import enable_compile_cache
from tpudml.core.config import TrainConfig, build_parser, config_from_args
from tpudml.core.prng import seed_key
from tpudml.data import DataLoader, load_dataset
from tpudml.metrics import MetricsWriter
from tpudml.metrics.profiler import trace
from tpudml.models import LeNet
from tpudml.optim import make_optimizer
from tpudml.train import TrainState, evaluate, train_loop


def reference_defaults() -> TrainConfig:
    cfg = TrainConfig()
    cfg.epochs = 1
    cfg.optimizer = "adam_ref"
    cfg.lr = 5e-4 * math.sqrt(200)  # reference lr rule (task1 model.py:96-98)
    cfg.data.batch_size = 200
    return cfg


def run(cfg: TrainConfig) -> dict:
    enable_compile_cache()
    train_set = load_dataset(
        cfg.data.dataset, cfg.data.data_dir, "train",
        synthetic_fallback=cfg.data.synthetic_fallback,
    )
    test_set = load_dataset(
        cfg.data.dataset, cfg.data.data_dir, "test",
        synthetic_fallback=cfg.data.synthetic_fallback,
    )
    from tpudml.data.sampler import make_sampler

    sampler = make_sampler(
        cfg.data.division if cfg.data.shuffle else "sequential",
        len(train_set),
        1,
        0,
        shuffle=cfg.data.shuffle,
        seed=cfg.data.seed,
    )
    train_loader = DataLoader(
        train_set, cfg.data.batch_size, sampler, drop_remainder=cfg.data.drop_remainder
    )
    test_loader = DataLoader(test_set, cfg.data.batch_size, drop_remainder=False)

    model = LeNet(in_channels=train_set.images.shape[-1])
    optimizer = make_optimizer(cfg.optimizer, cfg.lr, cfg.momentum)
    writer = MetricsWriter(cfg.log_dir, run_name=f"task1-epoch{cfg.epochs}")
    ts = TrainState.create(model, optimizer, seed_key(cfg.seed))
    ts, hooks, ckpt_mgr = setup_checkpointing(cfg, ts)
    with trace(writer.run_dir / "profile", enabled=cfg.profile):
        ts, metrics = train_loop(
            model,
            optimizer,
            train_loader,
            cfg.epochs,
            seed_key(cfg.seed),
            writer=writer,
            log_every=cfg.log_every,
            state=ts,
            hooks=hooks,
            accum_steps=cfg.accum_steps,
        )
    final_checkpoint(ckpt_mgr, ts)
    acc = evaluate(model, ts, test_loader)
    print(f"Test accuracy: {acc * 100:.2f}%")
    writer.add_scalar("Test Accuracy", acc, int(ts.step))
    writer.close()
    metrics["test_accuracy"] = acc
    return metrics


def main(argv=None):
    args = build_parser(reference_defaults()).parse_args(argv)
    return run(config_from_args(args))


if __name__ == "__main__":
    main()
