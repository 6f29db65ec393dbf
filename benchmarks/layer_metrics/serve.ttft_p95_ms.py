"""95th percentile over all the window's requests of first token minus the
time the request was due. What a user feels, but not an end-to-end metric of
this benchmark: two runs of one seed differ by 4 % (the first token waits for
the decode step in progress, 132 ms of random phase), which admits no bound
under 10 % (PERF.md, PR 26)."""


def read(ctx):
    ttft = ctx["host"].get("ttft_s")
    if not ttft:
        return None
    from benchmarks.stats import percentile

    return 1e3 * percentile(ttft, 95)
