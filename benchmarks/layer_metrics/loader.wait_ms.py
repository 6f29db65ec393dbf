"""Median host milliseconds per step inside `next()` of the benchmark's feed
(the program's loader behind the program's prefetch). Host clock; read in the
traced run so that it sits beside the device numbers of the same run."""

import statistics


def read(ctx):
    waits = ctx["host"].get("loader_waits_s")
    return 1e3 * statistics.median(waits) if waits else None
