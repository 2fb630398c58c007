"""The trace reduction on a synthetic trace with known intervals: busy
as the union of device operations, time per program, and idle gaps
named after the host span that covers them."""
from pathlib import Path

import pytest

from chipbench import trace_reduce as tr

DATA = Path(__file__).parent / "data" / "trace_small.pbtxt"


@pytest.fixture(scope="module")
def reduced():
    trace = tr.read_text_proto(DATA.read_text())
    return trace, tr.reduce(trace, step_modules={"jit__run"})


def test_reads_device_and_host_planes(reduced):
    trace, _ = reduced
    assert sorted(trace.ops) == [0]
    assert len(trace.ops[0]) == 5
    assert len(trace.modules[0]) == 3
    assert {h[2] for h in trace.host} >= {"bench.window", "runner.run"}


def test_busy_is_the_union_of_operations(reduced):
    _, red = reduced
    assert red["window_s"] == pytest.approx(10000e-9)
    assert red["busy_s"] == pytest.approx(4500e-9)
    assert red["idle_share"] == pytest.approx(0.55)


def test_time_per_program(reduced):
    _, red = reduced
    assert red["module_s"] == pytest.approx({"jit__run": 3500e-9,
                                             "jit_stack": 1000e-9})
    assert red["step_s"] == pytest.approx(3500e-9)
    assert red["top_ops"] == [
        ["fusion.1", pytest.approx(2500e-9)],
        ["%copy.2 u8[4,16] copy(u8[4,16] %p)", pytest.approx(1000e-9)],
        ["concatenate.3", pytest.approx(1000e-9)],
        ["while.9", pytest.approx(0.0)]]


def test_idle_gaps_named_by_host_span(reduced):
    _, red = reduced
    assert red["idle_gaps"] == [
        ["runner.run", pytest.approx(3000e-9)],
        ["TransferFromDevice", pytest.approx(2000e-9)],
        ["no host span", pytest.approx(500e-9)],
    ]


@pytest.mark.parametrize("intervals, lo, hi, busy, idle", [
    ([(0, 2), (1, 3), (5, 6)], 0, 10, [(0, 3), (5, 6)],
     [(3, 5), (6, 10)]),
    ([(-5, 1), (9, 20)], 0, 10, [(0, 1), (9, 10)], [(1, 9)]),
    ([], 0, 4, [], [(0, 4)]),
    ([(0, 4), (1, 2)], 0, 4, [(0, 4)], []),
])
def test_union_and_gaps(intervals, lo, hi, busy, idle):
    u = tr.union(intervals, lo, hi)
    assert u == busy
    assert tr.gaps(u, lo, hi) == idle


def test_self_time_of_nested_events():
    got = tr.self_times([(0, 10, "loop"), (1, 4, "a"), (2, 3, "b"),
                         (5, 9, "a"), (12, 13, "c")])
    assert got == {"loop": 3, "a": 6, "b": 1, "c": 1}


def test_missing_window_is_an_error():
    trace = tr.Trace(ops={0: [(0, 1, "x")]}, host=[(0, 1, "other")])
    with pytest.raises(ValueError, match="bench.window"):
        tr.reduce(trace)
