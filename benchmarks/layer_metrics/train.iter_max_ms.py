"""The longest `train/iter` of the window's `train_loop` call after its first
step, in milliseconds, from the program's pass log; its phases
(`next_batch`, `step`, `log_sync`, `hooks`) are in the kept pass
(`metrics["passes"]["slow"]`). The two passes in whose hook the benchmark
itself starts and stops the profiler (the cell file's `trace.start_step`-th
step of the window and the one `trace.steps` later) are left out: they time
the profiler. Nothing where the program keeps no pass log."""

from benchmarks import pass_log


def read(ctx):
    loaded = pass_log.load("train")
    if loaded is None:
        return None
    at = ctx["cell"].spec.get("trace", {})
    start = at.get("start_step")
    profiled = set() if start is None else {start, start + at.get("steps", 0)}
    steps = pass_log.passes(loaded, "step")
    first = loaded["rows"]["step"][0]  # the window's n-th step is step first + n - 1
    ms = [m for m, s in zip(pass_log.column(loaded, "ms", steps[1:]),
                            pass_log.column(loaded, "step", steps[1:]))
          if s - first + 1 not in profiled]
    return max(ms) if ms else None
