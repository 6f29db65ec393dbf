"""Cluster topology specification (the docker-compose.yml replacement)."""

from __future__ import annotations

import dataclasses
import json
import os
import re
import socket
from dataclasses import dataclass, field


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@dataclass
class ClusterSpec:
    """Everything the launcher needs to stand up an N-process job.

    The reference encodes this per-node in compose YAML — image, mount,
    rank flags, rendezvous DNS name (codes/task2/docker-compose.yml:4-45).
    Here it is one typed, JSON-serializable object; rendezvous is the JAX
    coordinator (``coordinator_address``) instead of MASTER_ADDR/PORT.
    """

    num_processes: int = 2
    coordinator_host: str = "127.0.0.1"
    coordinator_port: int = 0  # 0 → pick a free port at launch
    # "cpu" = simulated cluster on the host (the mp.spawn analogue);
    # None = inherit whatever platform the environment provides: ONE rank
    # per host of a real multi-host pod. A chip belongs to one process at
    # a time, so N ranks on one chip-bearing host would each claim it —
    # on a one- or four-chip host a single process drives every chip.
    platform: str | None = "cpu"
    devices_per_process: int = 1  # virtual host devices per rank (cpu sim)
    timeout_s: float | None = None  # whole-job wall-clock limit
    grace_s: float = 5.0  # SIGTERM → SIGKILL escalation delay
    # Elastic recovery: relaunch the whole job after a failure/timeout up
    # to this many times. Pair the command with --ckpt_dir/--resume so
    # each restart continues from the last checkpoint (SURVEY.md §5.3/5.4:
    # checkpoint/restart IS the recovery story).
    max_restarts: int = 0
    # Seeded exponential backoff between restart attempts: attempt k waits
    # restart_backoff_s * restart_backoff_factor**(k-1), plus a uniform
    # jitter of up to restart_backoff_jitter × that delay drawn from
    # random.Random(restart_backoff_seed) — deterministic per spec, but
    # decorrelated across jobs so a mass preemption doesn't produce a
    # thundering-herd reconnect. 0 (the default) restarts immediately,
    # preserving the pre-backoff behaviour.
    restart_backoff_s: float = 0.0
    restart_backoff_factor: float = 2.0
    restart_backoff_jitter: float = 0.0
    restart_backoff_seed: int = 0
    # Straggler/fault injection (task2 bottleneck-node experiment).
    bottleneck_rank: int | None = None
    bottleneck_delay_s: float = 0.1
    env: dict[str, str] = field(default_factory=dict)  # extra env, all ranks
    rank_env: dict[int, dict[str, str]] = field(default_factory=dict)

    def coordinator_address(self) -> str:
        if self.coordinator_port == 0:
            # Resolved once per launch; persisted so every rank agrees.
            self.coordinator_port = _free_port()
        return f"{self.coordinator_host}:{self.coordinator_port}"

    def environ_for_rank(self, rank: int) -> dict[str, str]:
        """Child-process environment for ``rank`` (layered over os.environ):
        the TPUDML_* rendezvous contract read by DistributedConfig.from_env,
        platform simulation knobs, and fault-injection exports."""
        env = dict(os.environ)
        env.update(self.env)
        env.update(self.rank_env.get(rank, {}))
        env.update(
            TPUDML_COORDINATOR=self.coordinator_address(),
            TPUDML_NUM_PROCESSES=str(self.num_processes),
            TPUDML_PROCESS_ID=str(rank),
        )
        if self.platform == "cpu":
            env["JAX_PLATFORMS"] = "cpu"
            # Strip any inherited device-count flag: the spec owns the
            # simulated topology (devices_per_process × num_processes).
            flags = re.sub(
                r"--xla_force_host_platform_device_count=\d+",
                "",
                env.get("XLA_FLAGS", ""),
            )
            env["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{self.devices_per_process}"
            ).strip()
        elif self.platform:
            env["JAX_PLATFORMS"] = self.platform
        if self.bottleneck_rank is not None:
            env["TPUDML_BOTTLENECK_RANK"] = str(self.bottleneck_rank)
            env["TPUDML_BOTTLENECK_DELAY_S"] = str(self.bottleneck_delay_s)
        return env

    # ------------------------------------------------------------- serde

    def to_json(self, path: str | os.PathLike) -> None:
        with open(path, "w") as f:
            json.dump(dataclasses.asdict(self), f, indent=2)

    @classmethod
    def from_json(cls, path: str | os.PathLike) -> "ClusterSpec":
        with open(path) as f:
            raw = json.load(f)
        raw["rank_env"] = {int(k): v for k, v in raw.get("rank_env", {}).items()}
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(raw) - known
        if unknown:
            raise ValueError(f"unknown ClusterSpec fields: {sorted(unknown)}")
        return cls(**raw)
