"""The longest steady pass of the run from the cell's `ramp_s` on, in
milliseconds, from the program's pass log: a decode step and a little when
the run was healthy, hundreds to thousands when it stalled (the program kept
that pass whole: `ServeReport.passes["slow"]`, and a WARNING on stderr)."""

from benchmarks import pass_log


def read(ctx):
    ms = pass_log.steady_after_ramp(ctx, "ms")
    return max(ms) if ms else None
