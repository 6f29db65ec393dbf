"""Share of the model's pattern entries that a prefill chunk runs, in percent:
`trunk_prefilled` on `serve/admit` over the entries of the pattern
(`counts_phi4flash.prefill_entries`: a mixer and a feed-forward a published
layer). The engine never prefills a prompt's last token, so a chunk stops where
the last per-slot state is written: behind the full layer's K and V. A program
whose `serve/admit` has no such counter gives None."""

import statistics

from benchmarks import counts_phi4flash, program_spans


def read(ctx):
    spans = program_spans.of_cell(ctx)
    ran = spans and program_spans.stat(program_spans.named(spans, "serve/admit"),
                                       "trunk_prefilled")
    if not ran:
        return None
    return 100.0 * statistics.mean(ran) / counts_phi4flash.prefill_entries(ctx["cell"].config)[1]
