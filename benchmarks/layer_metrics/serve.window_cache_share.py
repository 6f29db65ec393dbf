"""The window layers' share of the cache bytes a decode step reads, in
percent, from the program's counters on `serve/dispatch`: the dense step reads
every allocated row, so `cache_bytes_window` over `cache_bytes_window` +
`cache_bytes_full`. Five of seven attention layers are window layers; what
they cost of the cache is what the ring holds of it."""

from benchmarks import program_spans


def read(ctx):
    spans = program_spans.of_cell(ctx)
    steps = [s[3] for s in program_spans.named(spans or [], "serve/dispatch")
             if "cache_bytes_window" in s[3] and "cache_bytes_full" in s[3]]
    window = sum(s["cache_bytes_window"] for s in steps)
    total = window + sum(s["cache_bytes_full"] for s in steps)
    return 100.0 * window / total if total else None
