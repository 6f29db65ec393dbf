"""Share of the traced window, in percent, in which no operation ran on the
device AND the engine was not inside a `serve/idle` span (its wait for the
next arrival): the idle time the host has to answer for, where
`device_idle.serve` also counts an engine with nothing to do
(`benchmarks/idle_by_span.py`). Nothing where the trace holds no program
span."""

from benchmarks import idle_by_span


def read(ctx):
    loaded = idle_by_span.of_cell(ctx)
    if loaded is None or not loaded["spans"]:
        return None
    return idle_by_span.engaged_idle_share(loaded)
