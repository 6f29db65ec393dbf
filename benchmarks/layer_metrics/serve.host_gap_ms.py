"""Median device-idle milliseconds between two consecutive decode programs
with no prefill program between them: the engine's host loop between steps
(fetch the tokens, bookkeeping over the slots, dispatch)."""

import re
import statistics


def read(ctx):
    programs = ctx["cell"].spec.get("programs", {})
    decode = programs.get("decode")
    if not decode:
        return None
    t = ctx["trace"]
    events = sorted((s, s + d, name) for name, ds in t.programs.items()
                    for s, d in zip(t.program_starts[name], ds))
    gaps = [b[0] - a[1] for a, b in zip(events, events[1:])
            if re.search(decode, a[2]) and re.search(decode, b[2])]
    return 1e3 * statistics.median(gaps) if gaps else None
