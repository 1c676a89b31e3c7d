"""The trace reduction: device busy union, per-op time, collective exposure.

Unit cases on hand-made operations, and the whole reduction on a small
trace recorded on a TPU v5e (``data/small_trace``): a traced
``kmeans_mnist784.stream`` run of a few calls.
"""
import sys
from pathlib import Path

import re

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace as T  # noqa: E402

SMALL = Path(__file__).resolve().parent / "data" / "small_trace"


def op(name, s, e, module="jit_f"):
    return T.Op(name, module, s, e)


def test_union_merges_overlaps_and_keeps_gaps():
    assert T.union([(5, 9), (0, 2), (1, 3), (9, 10)]) == [(0, 3), (5, 10)]
    assert T.busy_ns([op("a", 0, 2), op("b", 1, 3), op("c", 5, 10)]) == 8


def test_clip_to_window():
    got = T.clip([op("a", 0, 10), op("b", 20, 30)], 5, 25)
    assert [(o.start, o.end) for o in got] == [(5, 10), (20, 25)]


def test_exposed_collective_time():
    tr = T.Trace({0: [op("fusion.1", 0, 10), op("all-reduce.3", 5, 20),
                      op("fusion.2", 15, 18)],
                  1: [op("fusion.1", 0, 4)]}, [], 0, 30)
    # all-reduce on device 0 spans 5..20; compute covers 5..10 and 15..18
    assert T.exposed_collective_ns(tr, 0) == 7
    assert T.exposed_collective_ns(tr, 1) is None


@pytest.mark.parametrize("name,want", [
    ("%all-reduce.3 = f32[65536,128]{1,0} all-reduce(f32[65536,128]{1,0} %fusion.1)", True),
    ("%all-reduce-start = f32[8]{0} all-reduce-start(f32[8]{0} %x)", True),
    ("all-gather.2", True),
    # an op that reads a collective's result names it as an operand only
    ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %all-reduce.3), kind=kLoop", False),
    ("%spmm.1 = f32[2048,128]{1,0} custom-call(f32[2048,3277]{1,0} %copy)", False),
])
def test_collective_is_told_by_its_opcode(name, want):
    assert T.is_collective(name) is want


def test_op_names_lose_their_hlo_text():
    assert T._base("jit_spmm(4651680184421729991)") == "jit_spmm"
    assert T._base("%sort.6 = (s32[8]{0}, s32[8]{0}) sort(s32[8]{0} %copy.15)") == "sort"


def test_idle_gaps_named_by_host_span():
    tr = T.Trace({0: [op("f", 0, 10), op("g", 20, 30)]},
                 [("bench.partial_fit", 9, 21), ("outer", 0, 40)], 0, 40)
    gaps = dict(T.idle_gaps(tr))
    assert gaps == {"bench.partial_fit": 10e-9, "outer": 10e-9}


@pytest.fixture(scope="module")
def small():
    return T.load(str(SMALL))


def test_recorded_trace(small):
    assert sorted(small.devices) == [0]
    busy = T.busy_by_device(small)[0]
    assert 0 < busy <= small.window_ns
    ops = T.window_ops(small, 0)
    assert all(small.t0 <= o.start < o.end <= small.t1 for o in ops)
    sketch = T.time_matching(small, 0, re.compile(r"_sketch_impl"))
    assert 0 < sketch < busy
    top = T.top_ops(small)
    assert top and sum(s for _, s in top) <= busy / 1e9 * 1.000001
    gaps = T.idle_gaps(small)
    assert abs(sum(s for _, s in T.idle_gaps(small, n=10**6)) * 1e9
               - (small.window_ns - busy)) < 1e3
    assert gaps[0][1] >= gaps[-1][1]
