"""`serve.tpot_p95_ms.reasoning` under a name of `phi-4-mini-flash-reasoning.serve-reasoning-4k`'s own: a metric that lists its cells is
read where it is listed. Same reader, same numbers: the tail of the gap between a
request's tokens, no end-to-end metric here for the reason given there (a prefill
chunk takes about a decode step's time, and a pass may admit a few)."""

import importlib.util
from pathlib import Path


def read(ctx):
    spec = importlib.util.spec_from_file_location(
        "layer_metric_aliased", Path(__file__).with_name("serve.tpot_p95_ms.reasoning.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(ctx)
