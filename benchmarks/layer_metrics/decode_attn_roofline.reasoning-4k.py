"""The `decode_attn` kernel's share of its roofline in
`phi-4-mini-flash-reasoning.serve-reasoning-4k` (`ops/decode_attn.py` over the
full layer's own cache, a pair of K/V heads a 128-lane row, by its name in the
device trace): the least time the chip could take for what a call MUST do
(`counts_phi4flash.decode_attn_counts`: the live rows of one read at the heads'
own widths, from the traced steps' own counters) over the time the calls took.
Memory bounds it. The kernel reads every allocated row, so its share is low
where the cache is mostly empty. `read_kernel` serves the shared and the window
reads' metrics too: the cell file's `kernels` says whose rows a kernel reads
(`rows`: the full layer's or a ring's)."""

from benchmarks import counts_phi4flash, kernel_ops


def read_kernel(ctx, kernel: str):
    cell, host = ctx["cell"], ctx["host"]
    kind = cell.spec.get("kernels", {}).get(kernel)
    active = host.get("decode_active")
    if kind is None or not active or "rows" not in kind:
        return None
    layers = counts_phi4flash.layer_counts(cell.config)["F" if kind["rows"] == "full" else "W"]
    rows = host[f"decode_rows_{kind['rows']}"] / layers
    ops, moved = counts_phi4flash.decode_attn_counts(
        cell.config, rows, active,
        {"f32": 4, "bf16": 2}[cell.spec["engine"]["serve_config"]["cache_kind"]])
    return kernel_ops.roofline_share(ctx, kernel, ops, moved)


def read(ctx):
    return read_kernel(ctx, "decode_attn")
