"""`serve.tpot_p95_ms` (95th percentile over the window's requests of the mean
gap between a request's tokens) where it is no end-to-end metric: in
`nemotron-3-nano-30b-a3b.serve-chat` six seeds spread it by 1.6 %, over half
its bound (PERF.md, PR 30). A prefill chunk there takes longer than a decode
step and a pass admits up to eleven of them, so which short answers live
through which admissions moves the tail, and the host's share of a pass moves
it again."""


def read(ctx):
    tpot = ctx["host"].get("tpot_s")
    if not tpot:
        return None
    from benchmarks.stats import percentile

    return 1e3 * percentile(tpot, 95)
