"""`serve.decode_hbm` under a name of `phi-4-mini-flash-reasoning.serve-reasoning-4k`'s own: that cell does
not report `serve.tpot_p95_ms`, which the metric of that name moves. Same reader. Its
bytes here are `counts_phi4flash.decode_step_bytes`: what a step must move by its own
counters (the weights held, the embedding among them; the full cache's live rows once
for each of the eight layers that read them; at most 512 rows of each ring; the active
slots' recurrent state, read and written; the rows written), a floor under what the
program reads (every allocated row of the full cache, eight times)."""

import importlib.util
from pathlib import Path


def read(ctx):
    spec = importlib.util.spec_from_file_location(
        "layer_metric_aliased", Path(__file__).with_name("serve.decode_hbm.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(ctx)
