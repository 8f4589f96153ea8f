"""The trace reduction on a small trace recorded on a TPU v5e (the first
20 ms of two jobs of a 40 x 600 path, trimmed by `testdata/trim_trace.py`
to the first 400 device operations and the benchmark's host spans), and
its interval arithmetic on made-up events."""
from pathlib import Path

import pytest

from bench import trace_reduce

TRACE = Path(__file__).resolve().parents[1] / "testdata" / "v5e_slice.xplane.pb"


def test_recorded_trace():
    red = trace_reduce.reduce_trace(str(TRACE))
    assert red is not None
    assert len(red["devices"]) == 1
    assert 0 < red["busy_s"] <= red["window_s"] < 1.0
    ops = red["device_ops"]
    assert 0 < len(ops) <= trace_reduce.TOP
    assert all(a[1] >= b[1] > 0 for a, b in zip(ops, ops[1:]))
    assert all(" = " not in name for name, _ in ops)
    # innermost operations only: their device time fits in the busy time
    assert sum(s for _, s in ops) <= red["busy_s"] * (1 + 1e-9)
    gaps = red["idle_gaps"]
    assert all(name.startswith("bench.") or name == "(no span)"
               for name, _ in gaps)
    assert sum(s for _, s in gaps) <= red["window_s"] - red["busy_s"] + 1e-9


def test_find_xplane(tmp_path):
    with pytest.raises(FileNotFoundError):
        trace_reduce.find_xplane(str(tmp_path))
    p = tmp_path / "plugins" / "profile" / "x" / "host.xplane.pb"
    p.parent.mkdir(parents=True)
    p.write_bytes(b"")
    assert trace_reduce.find_xplane(str(tmp_path)) == str(p)


def test_interval_arithmetic():
    assert trace_reduce._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [
        [0, 3], [5, 8]]
    assert trace_reduce._clip([(0, 4), (6, 9), (10, 12)], 2, 8) == [
        (2, 4), (6, 8)]
    # a while op holding two body ops: only the body ops are leaves
    ops = [(0, 10, "while"), (1, 2, "a"), (3, 9, "b"), (11, 12, "c")]
    assert [o[2] for o in trace_reduce._leaves(ops)] == ["a", "b", "c"]


def test_op_name():
    text = ("%fusion.2292 = f32[22283]{0:T(1024)S(1)} fusion(f32[4,22283,85]"
            "{2,1,0:T(8,128)} %get-tuple-element.55294), kind=kLoop")
    assert trace_reduce.op_name(text) == "fusion.2292 f32[22283]"
    tup = ("%fusion.2335 = (f32[231858]{0:T(1024)}, f32[90]{0:T(128)}) "
           "fusion(f64[231858,90]{1,0} %p), kind=kLoop")
    assert trace_reduce.op_name(tup) == "fusion.2335 f32[231858]"
    assert trace_reduce.op_name("copy.3") == "copy.3"
