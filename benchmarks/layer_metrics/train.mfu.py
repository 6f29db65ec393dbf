"""The step program's share of the compute roof: model FLOPs of one step
(benchmarks/counts.py, recomputation not counted) over the median device time
of the step program, over chips x peak bf16 FLOP/s."""


def read(ctx):
    pattern = ctx["cell"].spec.get("programs", {}).get("train_step")
    seconds = pattern and ctx["trace"].median_program_s(pattern)
    flops = ctx["host"].get("step_flops")
    if not seconds or not flops:
        return None
    roof = ctx["n_devices"] * ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops / seconds / roof
