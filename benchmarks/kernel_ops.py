"""A kernel's device time by its name in the trace, and its share of the roof:
what the `<kernel>_roofline` readers of `layer_metrics/` share. A Pallas kernel
is one operation of its program in the device trace, named by the kernel's
`name=` (`tracing.op_family` takes a trailing number off). A trace without the
kernel (an earlier commit, another cell) gives None."""

from __future__ import annotations

from pathlib import Path

from benchmarks import tracing

ROOT = Path(__file__).resolve().parents[1]
_loaded: dict = {}  # trace directory -> its events: one parse a run


def _events(ctx: dict) -> dict | None:
    trace_dir = str(ROOT / "benchmarks" / ".trace" / ctx["cell"].name)
    if trace_dir not in _loaded:
        _loaded.clear()
        try:
            _loaded[trace_dir] = tracing.load_events(trace_dir)
        except (FileNotFoundError, ValueError):
            _loaded[trace_dir] = None
    return _loaded[trace_dir]


def kernel_seconds(ctx: dict, kernel: str) -> tuple[float, int] | None:
    """(device seconds, calls) of the operations named ``kernel`` that lie
    wholly inside the traced window, on the first device; None when the trace
    has none."""
    events = _events(ctx)
    if not events or not events["devices"]:
        return None
    lo, hi = tracing._window(events)
    ops = events["devices"][sorted(events["devices"])[0]]["ops"]
    mine = [d for name, s, d in ops
            if tracing.op_family(name) == kernel and s >= lo and s + d <= hi]
    return (sum(mine), len(mine)) if mine else None


def roofline_share(ctx: dict, kernel: str, ops_per_call: float,
                   bytes_per_call: float) -> float | None:
    """Percent: the least time the chip could take for the kernel's calls
    (the larger of operations over peak FLOP/s and bytes over peak bytes/s, a
    call) over the time they took."""
    found = kernel_seconds(ctx, kernel)
    if not found or not ctx.get("peaks"):
        return None
    seconds, calls = found
    least = max(ops_per_call / ctx["peaks"]["bf16_flops_per_s"],
                bytes_per_call / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * calls * least / seconds
