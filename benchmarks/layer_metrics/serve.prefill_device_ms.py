"""Median device milliseconds of one prefill-chunk program."""


def read(ctx):
    pattern = ctx["cell"].spec.get("programs", {}).get("prefill")
    seconds = pattern and ctx["trace"].median_program_s(pattern)
    return 1e3 * seconds if seconds else None
