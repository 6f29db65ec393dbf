"""Median milliseconds of `serve/commit`: the engine's bookkeeping loop over
the slots after a decode step (tokens to the ledgers, evictions)."""

import statistics

from benchmarks import program_spans


def read(ctx):
    spans = program_spans.of_cell(ctx)
    commits = spans and program_spans.named(spans, "serve/commit")
    return 1e3 * statistics.median(s[2] for s in commits) if commits else None
