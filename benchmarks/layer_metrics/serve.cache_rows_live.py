"""Mean share of the cache rows a dense decode step reads (`slots` x
`max_len`) that hold a token (`rows` on `serve/dispatch`: the sum over active
slots of their position), in percent: useful over attempted for the decode
attention's read."""

import statistics

from benchmarks import program_spans


def read(ctx):
    spans = program_spans.of_cell(ctx)
    rows = spans and program_spans.stat(program_spans.named(spans, "serve/dispatch"), "rows")
    cfg = ctx["cell"].spec.get("engine", {}).get("serve_config", {})
    if not rows or not cfg.get("slots") or not cfg.get("max_len"):
        return None
    return 100.0 * statistics.mean(rows) / (cfg["slots"] * cfg["max_len"])
