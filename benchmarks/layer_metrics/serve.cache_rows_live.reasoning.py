"""Mean share of the full layers' cache rows (`slots` x `max_len` a full
layer: what the dense decode step reads of them) that hold a token
(`rows_full` on `serve/dispatch`: position + 1 summed over the active slots
and over the full layers), in percent: useful over attempted for the full
layers' read. The window layers' rings are all live once a slot has 128 tokens."""

import statistics

from benchmarks import counts_mimo, program_spans


def read(ctx):
    spans = program_spans.of_cell(ctx)
    rows = spans and program_spans.stat(program_spans.named(spans, "serve/dispatch"),
                                        "rows_full")
    cell = ctx["cell"]
    cfg = cell.spec.get("engine", {}).get("serve_config", {})
    if not rows or not cfg.get("slots") or not cfg.get("max_len"):
        return None
    layers = counts_mimo.layer_counts(cell.config)["full"]
    return 100.0 * statistics.mean(rows) / (cfg["slots"] * cfg["max_len"] * layers)
