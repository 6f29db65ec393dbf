"""`serve.tpot_p95_ms` (95th percentile over the window's requests of the mean
gap between a request's tokens) where it is no end-to-end metric: in
`mimo-v2.5.serve-reasoning` a prefill chunk takes several decode steps' time and
a pass may admit a few, so which answers live through which admissions moves
the tail (PERF.md §2: the bound is proven on one cell only)."""


def read(ctx):
    tpot = ctx["host"].get("tpot_s")
    if not tpot:
        return None
    from benchmarks.stats import percentile

    return 1e3 * percentile(tpot, 95)
