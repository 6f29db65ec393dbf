"""Share of the decode steps dispatched with the step before still unfetched
(mean of `ahead` on `serve/dispatch`), in percent: how often the serving loop
had the device's next program queued before it waited for the last one. 0
where the loop fetches first (a step whose next inputs are data); nothing
where the program's `serve/dispatch` has no such counter."""

import statistics

from benchmarks import program_spans


def read(ctx):
    spans = program_spans.of_cell(ctx)
    ahead = spans and program_spans.stat(program_spans.named(spans, "serve/dispatch"), "ahead")
    return 100.0 * statistics.mean(ahead) if ahead else None
