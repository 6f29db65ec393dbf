"""Median milliseconds of `train/step`: the call in `train_loop` that
dispatches one step (the host's side of it; the device runs behind)."""

import statistics

from benchmarks import program_spans


def read(ctx):
    spans = program_spans.of_cell(ctx)
    steps = spans and program_spans.named(spans, "train/step")
    return 1e3 * statistics.median(s[2] for s in steps) if steps else None
