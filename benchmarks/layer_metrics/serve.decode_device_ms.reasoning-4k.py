"""`serve.decode_device_ms` under a name of `phi-4-mini-flash-reasoning.serve-reasoning-4k`'s own: that
cell does not report `serve.tpot_p95_ms`, which the metric of that name moves, so the
harness does not read it there. Same reader, same numbers."""

import importlib.util
from pathlib import Path


def read(ctx):
    spec = importlib.util.spec_from_file_location(
        "layer_metric_aliased", Path(__file__).with_name("serve.decode_device_ms.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(ctx)
