"""The cross layers' re-reads of the full layer's cache as a share of the bytes a
decode step must move, in percent, from the program's counters on `serve/dispatch`:
(`rows_read_full` - `rows_full`) rows of K and V over
`counts_phi4flash.decode_step_bytes` of the same step, summed over the traced
steps. One cache, written once, read by eight layers: what sharing the memory
leaves of the reads. A program without `rows_read_full` gives None."""

from benchmarks import program_spans
from benchmarks.drivers import serve_phi4flash as drv


def read(ctx):
    cell = ctx["cell"]
    spans = program_spans.of_cell(ctx)
    steps = [s[3] for s in program_spans.named(spans or [], "serve/dispatch")
             if all(key in s[3] for key in drv.STEP_COUNTERS)]
    total = sum(drv.step_bytes(cell.config, cell.spec, s) for s in steps)
    shared = sum(drv.shared_bytes(cell.config, cell.spec, s) for s in steps)
    return 100.0 * shared / total if total else None
