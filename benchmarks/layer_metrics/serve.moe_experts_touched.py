"""Mean share of the held experts that a decode step touches
(`experts_touched` on `serve/commit`: held experts with at least one token of
an active slot, summed over the expert layers; over held experts x expert
layers), in percent: how much of the expert weights a step has to read.

A lower bound the program does not follow yet: `SigmoidMoE` runs every held
expert over every token, so its decode step reads all of them whatever this
says. What a step that skipped idle experts would save is 100 minus this; the
'touched' term of `counts_hybrid.decode_step_bytes` (`serve.decode_hbm`'s
bytes in this cell) is the same floor, not what the program moves."""

import statistics

from benchmarks import program_spans


def read(ctx):
    spans = program_spans.of_cell(ctx)
    touched = spans and program_spans.stat(
        program_spans.named(spans, "serve/commit"), "experts_touched")
    cfg = ctx["cell"].config
    held = cfg.get("n_routed_experts", 0) * cfg.get("hybrid_override_pattern", "").count("E")
    return 100.0 * statistics.mean(touched) / held if touched and held else None
