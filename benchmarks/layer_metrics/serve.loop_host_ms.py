"""Median host milliseconds of one steady pass of `ServingEngine.run` (a
`serve/iter` that ran a decode step and admitted nobody), less its
`serve/fetch`, where the host waits for the device: the host's own work per
pass, timed from inside the program. To be held against `serve.host_gap_ms`,
the same pass seen from the device: well under it, the gap is launch latency
on the device's side; well over it, host work hidden under the running step."""

import statistics

from benchmarks import program_spans


def read(ctx):
    spans = program_spans.of_cell(ctx)
    passes = spans and program_spans.loop_host_s(spans)
    return 1e3 * statistics.median(passes) if passes else None
