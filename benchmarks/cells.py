"""A cell, as data: `BENCHMARK.json` names it, and every file that belongs to
it is found by the names written there. Nothing here branches on a name."""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "benchmarks"


def _read(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict            # benchmarks/configs/<config>.json
    traffic: dict           # benchmarks/traffic/<traffic>.json
    spec: dict              # benchmarks/workloads/<cell>.json: driver, engine...
    end_to_end: list = field(default_factory=list)   # metric entries that apply
    per_layer: list = field(default_factory=list)

    @property
    def driver(self) -> str:
        return self.spec["driver"]


def _applies(metric: dict, cell: str, reported: set | None = None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return reported is None or metric.get("moves") in reported


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = _read(root / "BENCHMARK.json")
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (known: {known})")
    entry = entries[0]
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    base = root / "benchmarks"
    end_to_end = [m for m in bench["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in end_to_end}
    return Cell(
        name=name, chips=entry["chips"],
        config=_read(root / config["file"]),
        traffic=_read(base / "traffic" / f"{entry['traffic']}.json"),
        spec=_read(base / "workloads" / f"{name}.json"),
        end_to_end=end_to_end,
        per_layer=[m for m in bench["per_layer"] if _applies(m, name, reported)],
    )
