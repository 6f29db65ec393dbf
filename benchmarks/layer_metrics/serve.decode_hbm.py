"""The decode program's share of the memory roof: the bytes one step has to
read (benchmarks/counts.py: matmul weights at their stored type + the cache
rows the step reads, which for the dense layout is every row of every slot)
over peak HBM bytes/s, over the median device time of the decode program."""


def read(ctx):
    pattern = ctx["cell"].spec.get("programs", {}).get("decode")
    seconds = pattern and ctx["trace"].median_program_s(pattern)
    step_bytes = ctx["host"].get("decode_step_bytes")
    if not seconds or not step_bytes:
        return None
    return 100.0 * step_bytes / ctx["peaks"]["hbm_bytes_per_s"] / seconds
