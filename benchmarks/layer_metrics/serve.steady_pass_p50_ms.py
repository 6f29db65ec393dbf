"""Median milliseconds of a steady pass of `ServingEngine.run` (a `serve/iter`
that fetched a decode step with no admission in it or in the pass before),
over every such pass from the cell's `ramp_s` on, from the program's pass
log: the whole run's counterpart of `serve.decode_device_ms` +
`serve.host_gap_ms`, which see the traced 2-4 s. Nothing where the program
keeps no pass log."""

import statistics

from benchmarks import pass_log


def read(ctx):
    ms = pass_log.steady_after_ramp(ctx, "ms")
    return statistics.median(ms) if ms else None
