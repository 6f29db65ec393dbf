"""Share of the active slots' (token, expert) pairs that fall on experts
held here (`moe_held` over `moe_routed` on `serve/commit`, summed over the
window's decode steps), in percent: 50 where half the experts are held and
routing is even."""

from benchmarks import program_spans


def read(ctx):
    spans = program_spans.of_cell(ctx)
    commits = spans and program_spans.named(spans, "serve/commit")
    routed = sum(program_spans.stat(commits or [], "moe_routed"))
    held = sum(program_spans.stat(commits or [], "moe_held"))
    return 100.0 * held / routed if routed else None
