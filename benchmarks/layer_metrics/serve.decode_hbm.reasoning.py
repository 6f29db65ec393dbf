"""`serve.decode_hbm` under a name of `mimo-v2.5.serve-reasoning`'s own: that cell
does not report `serve.tpot_p95_ms`, which the metric of that name moves, so the
harness does not read it there. Same reader, same numbers. Its bytes here are
`counts_mimo.decode_step_bytes`: what a step must move by its own counters (the
weights held, the live rows of the full layers at K 192 and V 128, at most 128
rows of the window layers, the rows written), a floor under what the program
reads (every allocated row of the full layers, a key in 256 lanes)."""

import importlib.util
from pathlib import Path


def read(ctx):
    spec = importlib.util.spec_from_file_location(
        "layer_metric_aliased", Path(__file__).with_name("serve.decode_hbm.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(ctx)
