"""The trace reduction on a hand-built event list: one device, two executes,
a while loop with children, and idle gaps inside and between executes."""

import pytest

from benchmarks.harness import xtrace
from benchmarks.harness.xtrace import Trace

MS = 1e6   # ns

# host: two executes of 100 ms, 10 ms apart; the program's gather span
# covers the last 20 ms of each
MARKS = [("execute", 0 * MS, 100 * MS), ("spmd.gather", 80 * MS, 20 * MS),
         ("execute", 110 * MS, 100 * MS), ("spmd.gather", 190 * MS, 20 * MS)]
# device: per execute a 60 ms while loop holding two 20 ms sorts, then a
# 15 ms gather op; idle for the 5 ms before the loop and from 80 ms on
OPS = []
for t0 in (0, 110):
    OPS += [("while.1", (t0 + 5) * MS, 60 * MS),
            ("sort.2", (t0 + 10) * MS, 20 * MS),
            ("sort.2", (t0 + 35) * MS, 20 * MS),
            ("fusion.3", (t0 + 65) * MS, 15 * MS)]
# an event before the window must not count
OPS.append(("fusion.3", -50 * MS, 10 * MS))
TRACE = Trace(device_ops={"/device:TPU:0": OPS}, host_marks=MARKS)


def test_busy_union_does_not_count_nested_events_twice():
    assert xtrace.busy_ns(OPS[:4]) == 75 * MS


def test_self_times_take_children_out_of_their_parent():
    got = xtrace.self_times(OPS[:4])
    assert got == {"while.1": 20 * MS, "sort.2": 40 * MS, "fusion.3": 15 * MS}


def test_reduce_window_busy_idle_and_breakdown():
    got = xtrace.reduce(TRACE, "execute", "between-executes")
    assert got["window_s"] == pytest.approx(0.210)
    assert got["busy_s"] == pytest.approx(0.150)
    assert got["busiest_busy_s"] == pytest.approx(0.150)
    ops = dict(map(tuple, got["breakdown"]["device_ops"]))
    assert ops == pytest.approx({"sort.2": 0.080, "while.1": 0.040,
                                 "fusion.3": 0.030})
    gaps = dict(map(tuple, got["breakdown"]["idle_gaps"]))
    # 2 x 5 ms before the loop (inside execute), 2 x 20 ms under the
    # gather span (the innermost mark), 10 ms between the executes
    assert gaps == pytest.approx({"spmd.gather": 0.040, "execute": 0.010,
                                  "between-executes": 0.010})
    assert 100 * (1 - got["busiest_busy_s"] / got["window_s"]) == \
        pytest.approx(100 * 60 / 210)


def test_two_devices_average_and_busiest():
    half = [(n, s, d) for n, s, d in OPS if s < 100 * MS and s >= 0]
    trace = Trace(device_ops={"/device:TPU:0": OPS, "/device:TPU:1": half},
                  host_marks=MARKS)
    got = xtrace.reduce(trace, "execute", "between-executes")
    assert got["devices"] == 2
    assert got["busiest_busy_s"] == pytest.approx(0.150)
    assert got["busy_s"] == pytest.approx((0.150 + 0.075) / 2)


def test_nothing_to_read_gives_nothing():
    assert xtrace.reduce(Trace(host_marks=MARKS), "execute", "x") == {}
    assert xtrace.reduce(Trace(device_ops={"d": OPS}), "execute", "x") == {}


def test_short_op_name():
    hlo = ("%fusion.7 = (u32[4096]{0:T(1024)}, u32[4096]{0}) fusion(u32[8]"
           "{0:T(1024)S(1)} %a, s32[4096]{0} %b), kind=kCustom, calls=%f.1")
    assert xtrace.short_op_name(hlo) == \
        "fusion.7 u32[4096],u32[4096]<-u32[8],s32[4096]"
    assert xtrace.short_op_name("while.3") == "while.3"
