"""95th percentile over the loop's passes of the device milliseconds of
prefill programs launched in that pass (those that start between the pass's
`serve/iter` and the next one's): what admission adds to the gap between two
tokens of every decoding request. The program's span and the device's
programs are held together here, on the one clock of the trace."""

from benchmarks import program_spans


def read(ctx):
    pattern = ctx["cell"].spec.get("programs", {}).get("prefill")
    spans = program_spans.of_cell(ctx)
    if not pattern or not spans:
        return None
    from benchmarks.stats import percentile

    t = ctx["trace"]
    stalls = program_spans.prefill_stall_s(spans, t.programs, t.program_starts, pattern)
    return 1e3 * percentile(stalls, 95) if stalls else None
