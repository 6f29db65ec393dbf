"""The worst lateness, in milliseconds, of the pass log's 10 ms heartbeat
inside a steady pass from the cell's `ramp_s` on: how long this run's host
kept a thread that wanted to run from running. To be held against the
spread of the run's tails: a frozen process shows here, a slow device does
not."""

from benchmarks import pass_log


def read(ctx):
    late = pass_log.steady_after_ramp(ctx, "hiccup_ms")
    return max(late) if late else None
