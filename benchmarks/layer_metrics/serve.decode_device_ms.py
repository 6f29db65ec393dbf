"""Median device milliseconds of the decode-step program."""


def read(ctx):
    pattern = ctx["cell"].spec.get("programs", {}).get("decode")
    seconds = pattern and ctx["trace"].median_program_s(pattern)
    return 1e3 * seconds if seconds else None
