"""`serve.decode_hbm` under a name of `nemotron-3-nano-30b-a3b.serve-chat`'s own:
that cell does not report `serve.tpot_p95_ms` (PERF.md, PR 30), which the
metric of that name moves, so the harness does not read it there. Same reader,
same numbers. Its bytes here are `counts_hybrid.decode_step_bytes`: what a step
must move by its own counters (the held experts touched, the active slots'
state and K/V rows written), a floor under what the dense experts read."""

import importlib.util
from pathlib import Path


def read(ctx):
    spec = importlib.util.spec_from_file_location(
        "layer_metric_aliased", Path(__file__).with_name("serve.decode_hbm.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(ctx)
