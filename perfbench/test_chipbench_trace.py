"""The reduction from a trace to device numbers, on hand-built events."""
from pytest import approx

import pb_trace


def test_union_merges_overlaps_and_drops_empty():
    assert pb_trace.union([(5, 7), (0, 2), (1, 3), (4, 4), (6, 9)]) == [
        (0, 3), (5, 9)]


def test_gaps_inside_a_window():
    assert pb_trace.gaps([(0, 3), (5, 9)], -1, 12) == [(-1, 0), (3, 5),
                                                         (9, 12)]
    assert pb_trace.gaps([(0, 3), (5, 20)], 1, 12) == [(3, 5)]


def test_short_op_name():
    assert pb_trace.short("%fusion.3 = f32[8]{0} fusion(%p)") == "%fusion.3"


#: the device clock runs 1000 ns behind the host's
OFFSET = 1000


def _trace():
    """Host window 0..100 ns; a drain 5-35 and a fan-out 50-80. Three
    executions complete inside the window (a server one, a client one and
    one between spans), one after it."""
    d = -OFFSET
    modules = [("jit_agg(1)", 10 + d, 30 + d, 1), ("jit_step(2)", 55 + d,
                                                    70 + d, 2),
               ("jit_eval(3)", 40 + d, 45 + d, 3),
               ("jit_late(4)", 150 + d, 160 + d, 4)]
    ops = [("%fusion.1 = f32[]", 10 + d, 25 + d),
           ("%copy.2 = f32[]", 20 + d, 30 + d),
           ("%fusion.1 = f32[]", 60 + d, 70 + d),
           ("%reduce.3 = f32[]", 40 + d, 45 + d),
           ("%late = f32[]", 150 + d, 160 + d)]
    completed = {1: 31, 2: 71, 3: 46, 4: 161}
    host = {"pb.window": [(0, 100)], "pb.server": [(5, 35)],
            "pb.client": [(50, 80)]}
    return {"/device:TPU:0": {"modules": modules, "ops": ops}}, host, \
        completed


def test_busy_idle_and_busy_inside_server_spans():
    r = pb_trace.reduce(*_trace())
    assert r["window_s"] == approx(100e-9)
    assert r["busy_s"] == approx(35e-9)            # 10-30, 40-45, 60-70
    assert r["busy_in_server_s"] == approx(20e-9)  # the drain's execution
    idle = r["idle_by_host_s"]
    # the device window is the host's moved by the offset and the smallest
    # completion delay (1 ns): -1001..-901. Its gaps, on the host clock:
    # -1..10 (mid 4.5: server), 30..40 (loop), 45..60 (mid 52.5: client),
    # 70..99 (loop)
    assert idle["server"] == approx(11e-9)
    assert idle["loop"] == approx(39e-9)
    assert idle["client"] == approx(15e-9)


def test_top_ops_are_named_by_program_and_operation():
    r = pb_trace.reduce(*_trace())
    names = [op for op, _ in r["device_ops"]]
    assert names[0] == "jit_agg/%fusion.1"
    assert set(names[1:3]) == {"jit_agg/%copy.2", "jit_step/%fusion.1"}
    assert "jit_late/%late" not in names
    assert [g[0] for g in r["idle_gaps"]][0] == "loop"
    assert r["idle_gaps"][0][1] == approx(29e-9)


def test_busy_averages_over_devices():
    devices, host, completed = _trace()
    devices["/device:TPU:1"] = {
        "modules": [("jit_x(9)", -OFFSET, 100 - OFFSET, 9)],
        "ops": [("%all = f32[]", -OFFSET, 100 - OFFSET)]}
    completed[9] = 100
    r = pb_trace.reduce(devices, host, completed)
    assert r["busy_s"] == approx((35e-9 + 100e-9) / 2)
