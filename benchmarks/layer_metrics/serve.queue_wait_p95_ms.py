"""95th percentile over the window's requests of admission start minus the
time the request was due (the engine's admission loop)."""


def read(ctx):
    waits = ctx["host"].get("queue_waits_s")
    if not waits:
        return None
    from benchmarks.stats import percentile

    return 1e3 * percentile(waits, 95)
