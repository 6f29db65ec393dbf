"""Typed configuration for tpu-dml.

Replaces the reference's per-entrypoint ``argparse`` flag sets and hardcoded
hyperparameter constants (reference: codes/task2/model.py:92-102,
codes/task4/model.py:142-151) and the docker-compose YAML that doubled as the
de-facto cluster config (codes/task2/docker-compose.yml). One dataclass tree
covers process topology, mesh shape, data division, and task hyperparameters;
every field can be overridden from CLI flags or environment variables.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
from dataclasses import dataclass, field
from typing import Any, Sequence


@dataclass
class DistributedConfig:
    """Process-level topology.

    JAX-distributed analogue of the reference's rendezvous contract
    (``MASTER_ADDR``/``MASTER_PORT`` env + ``init_process_group(backend,
    rank, world_size)``, reference: codes/task2/dist_utils.py:6-15).
    ``coordinator_address`` plays the role of master_addr:master_port;
    ``process_id``/``num_processes`` play rank/world_size. ``backend`` is
    advisory ("tpu", "cpu", "gpu") — on TPU the collectives ride ICI/DCN via
    XLA, there is no NCCL/gloo choice to make.
    """

    coordinator_address: str | None = None  # "host:port"; None = single-process
    num_processes: int = 1
    process_id: int = 0
    backend: str | None = None  # None = autodetect platform
    initialize_timeout_s: int = 300
    # Cross-process collective implementation for the CPU backend. XLA's
    # CPU client cannot run multi-process computations natively; JAX
    # wires MPI or gloo underneath via
    # ``jax_cpu_collectives_implementation``. None = auto: "gloo" whenever
    # the job is multi-process AND the platform is CPU (JAX_PLATFORMS=cpu
    # or backend="cpu"), nothing otherwise. "none" opts out explicitly.
    # Env: TPUDML_CPU_COLLECTIVES.
    cpu_collectives: str | None = None
    # True when the world size was given explicitly (--n_devices / env), so
    # single-host runs can distinguish "--n_devices 1" (use ONE device — the
    # single-machine baseline of sections/task3.tex:23) from the default
    # "use every available device".
    explicit_world: bool = False

    @classmethod
    def from_env(cls) -> "DistributedConfig":
        """Build from env vars, honoring the reference's names as fallbacks.

        Recognizes TPUDML_COORDINATOR / TPUDML_NUM_PROCESSES /
        TPUDML_PROCESS_ID first, then the reference's MASTER_ADDR/MASTER_PORT
        (+ RANK/WORLD_SIZE) for drop-in familiarity.
        """
        coord = os.environ.get("TPUDML_COORDINATOR")
        if coord is None:
            addr = os.environ.get("MASTER_ADDR")
            port = os.environ.get("MASTER_PORT")
            if addr and port:
                coord = f"{addr}:{port}"
        nproc = os.environ.get(
            "TPUDML_NUM_PROCESSES", os.environ.get("WORLD_SIZE")
        )
        return cls(
            coordinator_address=coord,
            num_processes=int(nproc) if nproc is not None else 1,
            process_id=int(os.environ.get("TPUDML_PROCESS_ID", os.environ.get("RANK", "0"))),
            backend=os.environ.get("TPUDML_BACKEND"),
            cpu_collectives=os.environ.get("TPUDML_CPU_COLLECTIVES"),
            explicit_world=nproc is not None,
        )


@dataclass
class MeshConfig:
    """Logical device mesh over which SPMD programs are sharded.

    ``axes`` maps axis name -> size; -1 means "all remaining devices". The
    canonical axis names used across the framework are ``data`` (DP),
    ``stage`` (inter-layer MP / pipeline), ``model`` (tensor parallel) and
    ``seq`` (sequence/context parallel).
    """

    axes: dict[str, int] = field(default_factory=lambda: {"data": -1})

    def axis_names(self) -> tuple[str, ...]:
        return tuple(self.axes.keys())


@dataclass
class DataConfig:
    """Dataset + division strategy.

    ``division`` selects the sampler mode required by the reference's task3
    (sections/task3.tex:19-24, sections/checking.tex:13): "partition" =
    random partition (shared seed, disjoint stride), "sampling" = random
    sampling (per-rank seed → independent shuffles, sampling with
    replacement across ranks).
    """

    dataset: str = "mnist"  # mnist | cifar10 | synthetic
    data_dir: str = "./data"
    batch_size: int = 200  # per-replica batch (reference task1: 200, task2/3/4: 32)
    division: str = "partition"  # partition | sampling
    shuffle: bool = True
    seed: int = 0
    drop_remainder: bool = True
    synthetic_fallback: bool = True  # use deterministic synthetic data if files absent


@dataclass
class TrainConfig:
    """Top-level training configuration for the task entrypoints."""

    epochs: int = 1
    lr: float = 1e-3
    momentum: float = 0.0
    optimizer: str = "adam"  # gd | sgd | adam | adam_ref
    aggregation: str = "allreduce"  # allreduce | allgather  (task2 contract)
    log_every: int = 20  # reference cadence: print/log every 20 iters
    bottleneck_rank: int | None = None  # straggler-injection target rank
    bottleneck_delay_s: float = 0.1  # reference: model-mp.py:47
    measure_comm: bool = False  # split-step comm-time accounting mode
    zero1: bool = False  # ZeRO-1 weight-update sharding on the DP engine
    sentinel: bool = False  # in-graph step sentinel (skip non-finite updates)
    obs: bool = False  # flight recorder: trace.json + in-graph StepStats
    accum_steps: int = 1  # gradient-accumulation micro-batches per step
    log_dir: str = "./logs"
    profile: bool = False  # capture a jax.profiler trace into the run dir
    ckpt_dir: str | None = None  # enable checkpointing under this directory
    ckpt_every: int = 0  # steps between rolling checkpoints (0 = end only)
    resume: bool = False  # restore the latest checkpoint before training
    seed: int = 0
    dist: DistributedConfig = field(default_factory=DistributedConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    data: DataConfig = field(default_factory=DataConfig)

    def fingerprint(self) -> str:
        """Rank-invariant program identity for the cross-process
        same-program check (``assert_same_program``): every field except
        the per-process ``dist`` block, rank-targeted fault injection, and
        host-local paths (log/ckpt dirs may legitimately be rank-templated
        without changing the SPMD program)."""
        d = dataclasses.asdict(self)
        for k in ("dist", "bottleneck_rank", "log_dir", "ckpt_dir"):
            d.pop(k, None)
        d["data"].pop("data_dir", None)
        return repr(dict(sorted(d.items())))


def _add_flag(
    parser: argparse.ArgumentParser, name: str, default: Any, annotation: str = ""
) -> None:
    typ = type(default)
    if typ is bool:
        parser.add_argument(f"--{name}", action=argparse.BooleanOptionalAction, default=default)
    elif default is None:
        # Optional fields: recover the parser type from the annotation so
        # e.g. --bottleneck_rank yields an int, not a str.
        typ = int if "int" in annotation else float if "float" in annotation else str
        parser.add_argument(f"--{name}", type=typ, default=None)
    else:
        parser.add_argument(f"--{name}", type=typ, default=default)


def build_parser(
    defaults: TrainConfig | None = None, extra: Sequence[str] = ()
) -> argparse.ArgumentParser:
    """CLI parser exposing the flat fields of TrainConfig plus the
    reference's historical flag names (``--n_devices``, ``--rank``,
    ``--master_addr``, ``--master_port``, ``--mode``) for parity
    (reference: codes/task2/model.py:92-102, codes/task4/model.py:142-151).
    """
    defaults = defaults or TrainConfig()
    p = argparse.ArgumentParser()
    for f in dataclasses.fields(TrainConfig):
        if f.name in ("dist", "mesh", "data"):
            continue
        _add_flag(p, f.name, getattr(defaults, f.name), str(f.type))
    taken = {f.name for f in dataclasses.fields(TrainConfig)}
    for f in dataclasses.fields(DataConfig):
        if f.name not in taken:  # e.g. `seed`: one --seed flag feeds both configs
            _add_flag(p, f.name, getattr(defaults.data, f.name), str(f.type))
    # Reference-parity flags.
    p.add_argument("--n_devices", type=int, default=None, help="world size (reference parity)")
    p.add_argument("--rank", type=int, default=None, help="process id (reference parity)")
    p.add_argument("--master_addr", type=str, default=None)
    p.add_argument("--master_port", type=str, default=None)
    p.add_argument("--mode", type=str, default=None, help="alias of --division (task4 parity)")
    p.add_argument("--plan", type=str, default=None, metavar="PLAN_JSON",
                   help="apply a planner-emitted plan.json (python -m "
                        "tpudml.plan): its engine_config fills every knob "
                        "left at its default (explicit flags win)")
    for name in extra:
        p.add_argument(name)
    return p


def config_from_args(args: argparse.Namespace) -> TrainConfig:
    """Materialize a TrainConfig from parsed CLI args + environment."""
    cfg = TrainConfig()
    for f in dataclasses.fields(TrainConfig):
        if f.name in ("dist", "mesh", "data"):
            continue
        if hasattr(args, f.name):
            setattr(cfg, f.name, getattr(args, f.name))
    for f in dataclasses.fields(DataConfig):
        if hasattr(args, f.name):
            setattr(cfg.data, f.name, getattr(args, f.name))
    cfg.data.seed = cfg.seed  # single --seed governs data division too
    cfg.dist = DistributedConfig.from_env()
    if getattr(args, "n_devices", None) is not None:
        cfg.dist.num_processes = args.n_devices
        cfg.dist.explicit_world = True
    if getattr(args, "rank", None) is not None:
        cfg.dist.process_id = args.rank
    if getattr(args, "master_addr", None) is not None and getattr(args, "master_port", None):
        cfg.dist.coordinator_address = f"{args.master_addr}:{args.master_port}"
    if getattr(args, "mode", None):
        # task4 historical values: "division" -> partition, "sampling" -> sampling
        cfg.data.division = {"division": "partition", "sampling": "sampling"}.get(
            args.mode, args.mode
        )
    # Planner output (python -m tpudml.plan). Same precedence contract as
    # the env knobs below: the plan's engine_config fills only the knobs
    # the user left at their defaults, so explicit flags always win.
    if getattr(args, "plan", None):
        from tpudml.plan.emit import load_plan

        ec = load_plan(args.plan)["engine_config"]
        defaults = TrainConfig()
        for name in ("zero1", "accum_steps", "sentinel", "obs", "aggregation"):
            if name in ec and getattr(cfg, name) == getattr(defaults, name):
                setattr(cfg, name, ec[name])
    # Fault-injection knobs exported by the launcher (tpudml.launch) ride the
    # environment so the task command line stays rank-agnostic. Precedence is
    # CLI > env: env fills only fields the user left at their defaults.
    if cfg.bottleneck_rank is None and os.environ.get("TPUDML_BOTTLENECK_RANK"):
        cfg.bottleneck_rank = int(os.environ["TPUDML_BOTTLENECK_RANK"])
        if cfg.bottleneck_delay_s == TrainConfig.bottleneck_delay_s:
            cfg.bottleneck_delay_s = float(
                os.environ.get("TPUDML_BOTTLENECK_DELAY_S", cfg.bottleneck_delay_s)
            )
    return cfg
