"""The `decode_attn` kernel's share of its roofline (`ops/decode_attn.py` over a
full layer's cache, by its name in the device trace): the least time the chip
could take for what a call MUST do (`counts_mimo.decode_attn_counts`: the live
rows of one layer's cache at the head's own widths, from the traced steps' own
counters) over the time the calls took. Memory bounds it. The kernel reads every
allocated row and a key in 256 lanes, so its share is low where the cache is
mostly empty. `read_kernel` serves `decode_attn_window_roofline.py` too."""

from benchmarks import counts_mimo, kernel_ops


def read_kernel(ctx, kernel: str):
    cell, host = ctx["cell"], ctx["host"]
    kind = cell.spec.get("kernels", {}).get(kernel)
    active = host.get("decode_active")
    if kind is None or not active:
        return None
    which = "window" if kind["window"] else "full"
    rows = host[f"decode_rows_{which}"] / counts_mimo.layer_counts(cell.config)[which]
    ops, moved = counts_mimo.decode_attn_counts(
        cell.config, kind["window"], rows, active,
        {"f32": 4, "bf16": 2}[cell.spec["engine"]["serve_config"]["cache_kind"]])
    return kernel_ops.roofline_share(ctx, kernel, ops, moved)


def read(ctx):
    return read_kernel(ctx, "decode_attn")
