"""Task 1 (second-framework track) — MLP via the high-level Model API.

Capability parity with the reference's MindSpore notebook path
(codes/task1/mindspore/model.ipynb): MNIST through a batched/shuffled
dataset pipeline (cell 2), the ForwardNN 784→512→…→32→10 MLP (cell 4),
``Model(net, loss, opt, {"Accuracy"})`` with ``LossMonitor`` callbacks and
sink-mode training (cells 5-7), then ``model.eval``. Sink mode maps to the
jitted XLA step — the notebook's graph-compiled data-sinking execution is
exactly this framework's native model (SURVEY.md §3.5).

Run: ``python -m tasks.task1_mlp [--epochs 10] [--optimizer sgd] ...``
"""

from __future__ import annotations

from tpudml.api import LossMonitor, Model
from tpudml.core.compile_cache import enable_compile_cache
from tpudml.core.config import TrainConfig, build_parser, config_from_args
from tpudml.data import DataLoader, load_dataset
from tpudml.metrics import MetricsWriter
from tpudml.models import ForwardMLP
from tpudml.optim import make_optimizer


def reference_defaults() -> TrainConfig:
    cfg = TrainConfig()
    cfg.epochs = 10  # notebook: model.train(10, ...)
    cfg.optimizer = "sgd"
    cfg.lr = 0.01
    cfg.data.batch_size = 32
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
    train_loader = DataLoader(train_set, cfg.data.batch_size)
    test_loader = DataLoader(test_set, cfg.data.batch_size, drop_remainder=False)

    model = Model(
        ForwardMLP(),
        optimizer=make_optimizer(cfg.optimizer, cfg.lr, cfg.momentum),
        metrics={"accuracy"},
        seed=cfg.seed,
    )
    callbacks = [LossMonitor(cfg.log_every)] if cfg.log_every else []
    model.train(cfg.epochs, train_loader, callbacks=callbacks)
    print(f"Training time: {model.train_time_s:.3f}s")
    results = model.eval(test_loader)
    print(results)

    writer = MetricsWriter(cfg.log_dir, run_name="task1-mlp")
    writer.add_scalar("Test Accuracy", results["Accuracy"], int(model.state.step))
    writer.close()
    return {"test_accuracy": results["Accuracy"], "train_time_s": model.train_time_s}


def main(argv=None):
    args = build_parser(reference_defaults()).parse_args(argv)
    return run(config_from_args(args))


if __name__ == "__main__":
    main()
