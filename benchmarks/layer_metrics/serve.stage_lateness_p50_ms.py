"""Median milliseconds between a request's arrival time and the moment the
engine's loop staged it (`late_us` on `serve/arrive`): the loop is inside a
decode step when most requests fall due, and that wait is not queueing. A
window of a few seconds holds a few dozen arrivals: a median, not a tail."""

import statistics

from benchmarks import program_spans


def read(ctx):
    spans = program_spans.of_cell(ctx)
    late = spans and program_spans.stat(program_spans.named(spans, "serve/arrive"), "late_us")
    return statistics.median(late) / 1e3 if late else None
