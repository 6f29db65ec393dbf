"""Pass log: what the program's loops keep of every pass with no trace on
(`tpudml/obs/passlog.py`), read back after the run.

A profiler trace sees 2-4 s of a run; the pass log sees all of it: one row a
`serve/iter` or `train/iter` with its start on the loop's clock, its
milliseconds, its class and its phases. The drivers hand the readers no
report, so the program keeps the last log of each loop kind reachable in the
process (`tpudml.obs.last_pass_log`). Two stages, like `program_spans.py`, so
that the arithmetic can be tested on a recorded log:

1. ``load(kind)`` turns the log into plain lists: ``{"kind", "classes",
   "rows": {column: [one value a pass, oldest first]}, "summary": the
   program's own (per class passes / p50 / p99 / max ms; "slow": the kept
   longest passes, whole)}``; None when the program has no pass log (an
   earlier commit) or ran no such loop.
2. Pure functions over that: the passes of a class from a time on, a column
   of them.

Every reader in `layer_metrics/` that reads it returns None without it.
"""

from __future__ import annotations


def load(kind: str) -> dict | None:
    try:
        from tpudml.obs import last_pass_log
    except ImportError:  # a program from before the pass log
        return None
    log = last_pass_log(kind)
    if log is None:
        return None
    rows = log.rows()
    return {"kind": kind, "classes": list(log.classes),
            "rows": {name: rows[name].tolist() for name in rows.dtype.names},
            "summary": log.summary()}


def passes(loaded: dict, cls: str, from_s: float = 0.0) -> list[int]:
    """Indices of the passes of class ``cls`` that start at or after
    ``from_s`` on the loop's clock."""
    rows, c = loaded["rows"], loaded["classes"].index(cls)
    return [i for i, (k, t) in enumerate(zip(rows["cls"], rows["start_s"]))
            if k == c and t >= from_s]


def column(loaded: dict, name: str, which: list[int]) -> list:
    values = loaded["rows"][name]
    return [values[i] for i in which]


def steady_after_ramp(ctx: dict, name: str) -> list | None:
    """Column ``name`` over the serving loop's steady passes that start at or
    after the cell's ``ramp_s`` (0 where its file has none): the passes of
    the window and the drain. None without a log or without such a pass."""
    loaded = load("serve")
    if loaded is None:
        return None
    which = passes(loaded, "steady", float(ctx["cell"].spec.get("ramp_s", 0.0)))
    return column(loaded, name, which) or None
