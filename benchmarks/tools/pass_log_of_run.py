#!/usr/bin/env python3
"""One run of one cell (`run.py`, in this process) and then what the
program's pass logs hold of it, as JSON:

    python3 benchmarks/tools/pass_log_of_run.py <out.json> --workload <cell> \
        --seed <n> --seconds <s> --trace <0|1>

`run.py` reads the pass log only for a traced run's per-layer metrics; the
stall this log exists for comes once in tens of UNTRACED runs (PERF.md §7),
and the log lives in the process that ran. This writes, for each loop kind
that ran, `pass_log.load(kind)` (every pass's row, the per-class summary, the
kept slow passes), after a traced run the device's idle gaps by
program span (`idle_by_span`), and what the machine says of the process's
supply of CPU before and after the run (the cgroup's throttling counters, the
VM's steal time: a pass whose heartbeat was late by the pass's length is a
process that did not run, and these say who held it). stdout is `run.py`'s own, its last line the
result; what is written here is named on stderr. Also the way to cut
`benchmarks/tests/recorded_pass_log.json` from a chip run."""

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


HOST_FILES = ("/sys/fs/cgroup/cpu.stat", "/sys/fs/cgroup/cpu.max", "/sys/fs/cgroup/cpu/cpu.stat",
              "/proc/pressure/cpu", "/proc/loadavg")


def host_counters() -> dict:
    """The files of `HOST_FILES` that are there, and the first line of
    `/proc/stat` (user nice system idle iowait irq softirq STEAL ...)."""
    found = {}
    for name in HOST_FILES + ("/proc/stat",):
        try:
            text = Path(name).read_text()
        except OSError:
            continue
        found[name] = text.splitlines()[0] if name == "/proc/stat" else text
    return found


def main(out: str, *argv: str) -> int:
    from benchmarks import cells, idle_by_span, pass_log, run

    before = host_counters()
    code = run.main(list(argv))
    record = {"argv": list(argv), "host": {"before": before, "after": host_counters()}}
    for kind in ("serve", "train"):
        loaded = pass_log.load(kind)
        if loaded is not None:
            record[kind] = loaded
    if "--trace" in argv and argv[argv.index("--trace") + 1] == "1":
        cell = argv[argv.index("--workload") + 1]
        idle = idle_by_span.load(str(cells.BENCH / ".trace" / cell))
        if idle is not None:
            named = idle_by_span.by_span(idle["gaps"], idle["spans"], idle["modules"])
            record["idle"] = {**idle, "by_span": idle_by_span.table(named),
                              "engaged_idle_share": idle_by_span.engaged_idle_share(idle)}
    path = Path(out)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(record) + "\n")
    print(f"pass_log_of_run: wrote {out} ({', '.join(sorted(record))})", file=sys.stderr)
    return code


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
