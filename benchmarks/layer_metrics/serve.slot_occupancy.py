"""Mean share of the engine's slots that hold a request when a decode step
is dispatched (`active` on `serve/dispatch` over `slots`), in percent."""

import statistics

from benchmarks import program_spans


def read(ctx):
    spans = program_spans.of_cell(ctx)
    active = spans and program_spans.stat(program_spans.named(spans, "serve/dispatch"), "active")
    slots = ctx["cell"].spec.get("engine", {}).get("serve_config", {}).get("slots")
    return 100.0 * statistics.mean(active) / slots if active and slots else None
