"""The reduction from a trace to numbers: interval arithmetic by hand, then
the recorded trace kept beside this file (`recorded_trace.json`: what
`tracing.load_events` read from a chip run of this benchmark, cut to a few
steps by `tools/dump_trace.py`)."""

import json
from pathlib import Path

import pytest

from benchmarks import tracing

RECORDED = Path(__file__).with_name("recorded_trace.json")


def test_union_subtract_total():
    merged = tracing.union([(5, 7), (0, 2), (1, 3), (3, 3), (6, 9)])
    assert merged == [(0, 3), (5, 9)]
    assert tracing.total(merged) == 7
    assert tracing.subtract([(0, 10)], merged) == [(3, 5), (9, 10)]
    assert tracing.subtract(merged, [(2, 6)]) == [(0, 2), (6, 9)]
    assert tracing.subtract(merged, []) == merged
    assert tracing.clip(merged, 1, 6) == [(1, 3), (5, 6)]


def _events():
    """Two devices, two programs of three ops each, two of them overlapping,
    and a host span over the gap between programs."""
    def device(shift):
        ops = [["fusion.1", 0.0 + shift, 0.010], ["all-gather.2", 0.008 + shift, 0.004],
               ["fusion.3", 0.012 + shift, 0.008],
               ["fusion.1", 0.030 + shift, 0.010], ["all-gather.2", 0.038 + shift, 0.004],
               ["fusion.3", 0.042 + shift, 0.008]]
        modules = [["jit_step", 0.0 + shift, 0.020], ["jit_step", 0.030 + shift, 0.020]]
        return {"ops": ops, "modules": modules}
    return {"devices": {"/device:TPU:0": device(0.0), "/device:TPU:1": device(0.0)},
            "host": [["bench:loader.next", 0.021, 0.008]]}


def test_reduce_by_hand():
    s = tracing.reduce(_events())
    assert s.n_devices == 2
    assert s.window_s == pytest.approx(0.050)
    assert s.busy_s == pytest.approx(0.040)           # 2 x (10 + 2 not overlapped + 8) ms
    assert s.median_program_s("^jit_step$") == pytest.approx(0.020)
    assert s.count_programs("step") == 2 and s.median_program_s("nothing") is None
    assert s.device_ops[0] == ["fusion", pytest.approx(0.036)]
    assert s.device_ops[1] == ["all-gather", pytest.approx(0.008)]
    assert tracing.op_family("%copy.1807 = bf16[8,1024]{1,0} copy(bf16[8,1024] %x)") == "copy"
    assert s.idle_gaps == [["bench:loader.next", pytest.approx(0.010)]]


def test_gap_without_a_span_is_named_by_its_neighbours():
    events = _events()
    events["host"] = []
    assert tracing.reduce(events).idle_gaps[0][0] == "jit_step -> jit_step"


def test_trace_without_device_ops_is_an_error():
    with pytest.raises(ValueError):
        tracing.reduce({"devices": {}, "host": []})


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace kept yet")
def test_recorded_trace():
    events = json.loads(RECORDED.read_text())
    expected = events.pop("expected")
    s = tracing.reduce(events)
    assert s.n_devices == expected["n_devices"]
    assert s.window_s == pytest.approx(expected["window_s"], rel=1e-9)
    assert s.busy_s == pytest.approx(expected["busy_s"], rel=1e-9)
    assert 0 < s.busy_s <= s.window_s
    for pattern, (count, median) in expected["programs"].items():
        assert s.count_programs(pattern) == count
        assert s.median_program_s(pattern) == pytest.approx(median, rel=1e-9)
    assert [name for name, _ in s.idle_gaps[:2]] == expected["top_gaps"]
    assert sum(t for _, t in s.idle_gaps) <= s.window_s - s.busy_s + 1e-9
