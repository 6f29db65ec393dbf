"""Median device milliseconds of one train-step program in the trace (the
"XLA Modules" event of the program the cell's `programs.train_step` names)."""


def read(ctx):
    pattern = ctx["cell"].spec.get("programs", {}).get("train_step")
    seconds = pattern and ctx["trace"].median_program_s(pattern)
    return 1e3 * seconds if seconds else None
