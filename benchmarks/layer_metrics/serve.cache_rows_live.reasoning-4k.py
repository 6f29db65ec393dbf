"""Mean share of the full layer's cache rows (`slots` x `max_len`: what each of
the eight dense reads of it covers) that hold a token (`rows_full` on
`serve/dispatch`: position + 1 summed over the active slots, the one full layer),
in percent: useful over attempted for every read of the shared cache. The rings
are all live once a slot has 512 tokens."""

import statistics

from benchmarks import counts_phi4flash, program_spans


def read(ctx):
    spans = program_spans.of_cell(ctx)
    rows = spans and program_spans.stat(program_spans.named(spans, "serve/dispatch"),
                                        "rows_full")
    cell = ctx["cell"]
    cfg = cell.spec.get("engine", {}).get("serve_config", {})
    if not rows or not cfg.get("slots") or not cfg.get("max_len"):
        return None
    layers = counts_phi4flash.layer_counts(cell.config)["F"]
    return 100.0 * statistics.mean(rows) / (cfg["slots"] * cfg["max_len"] * layers)
