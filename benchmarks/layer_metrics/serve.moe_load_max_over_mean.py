"""Mean over decode steps of the busiest held expert's tokens
(`expert_load_max` on `serve/commit`) over the mean tokens of a touched
expert (`moe_held` / `experts_touched`): 1 is even routing, the straggler
expert's excess is what is over it."""

import statistics

from benchmarks import program_spans


def read(ctx):
    spans = program_spans.of_cell(ctx)
    commits = spans and program_spans.named(spans, "serve/commit")
    ratios = [s[3]["expert_load_max"] * s[3]["experts_touched"] / s[3]["moe_held"]
              for s in commits or []
              if s[3].get("moe_held") and s[3].get("experts_touched")
              and "expert_load_max" in s[3]]
    return statistics.mean(ratios) if ratios else None
