"""The `decode_attn_shared` kernel's share of its roofline: `ops/decode_attn.py` over
a cross layer's read of the full layer's cache: seven calls a step, each the same rows as `decode_attn`'s one,
by its name in the device trace; the reader and the counts are
`decode_attn_roofline.reasoning-4k.py`'s."""

import importlib.util
from pathlib import Path


def read(ctx):
    spec = importlib.util.spec_from_file_location(
        "layer_metric_aliased", Path(__file__).with_name("decode_attn_roofline.reasoning-4k.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read_kernel(ctx, "decode_attn_shared")
