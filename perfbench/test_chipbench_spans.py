"""The reduction of the program's spans, on hand-built events."""
import pytest
from pytest import approx

import pb_spans
from pb_spans import Span

#: the device clock runs 1000 ns behind the host's
OFFSET = 1000


def _trace():
    """Host window 0..100 ns: a drain (``pb.server`` 5-35) whose device
    execution (10-30) runs two fedagg kernels and a copy, an evaluation
    (40-45) and a fan-out (``pb.client`` 50-80), with the program's spans
    inside; one execution and one span after the window."""
    d = -OFFSET
    modules = [("jit_agg(1)", 10 + d, 30 + d, 1), ("jit_step(2)", 55 + d,
                                                    70 + d, 2),
               ("jit_eval(3)", 40 + d, 45 + d, 3),
               ("jit_late(4)", 150 + d, 160 + d, 4)]
    ops = [("%fedagg_norms.1 = (f32[16,128]) custom-call()", 10 + d, 18 + d),
           ("%copy.2 = f32[]", 18 + d, 23 + d),
           ("_fedagg_axpy.3", 23 + d, 30 + d),
           ("%fusion.1 = f32[]", 60 + d, 70 + d),
           ("%reduce.3 = f32[]", 40 + d, 45 + d),
           ("%fedagg_norms.9 = f32[]", 150 + d, 160 + d)]
    completed = {1: 31, 2: 71, 3: 46, 4: 161}
    host = {"pb.window": [(0, 100)], "pb.server": [(5, 35)],
            "pb.client": [(50, 80)]}
    spans = [Span("loop.drain", 4, 48, {"B": 1}),
             Span("server.drain", 5, 34, {"B": 1, "path": "seq"}),
             Span("server.flatten", 5, 9, {"h2d_bytes": 0}),
             Span("server.kernels", 9, 10, {}),
             Span("server.sync", 10, 31, {"reads": 4}),
             Span("server.book", 31, 32, {}),
             Span("server.unflatten", 32, 34, {}),
             Span("loop.eval", 35, 45, {"reads": 2}),
             Span("client.fanout", 50, 80, {"jobs": 1, "engine": "loop"}),
             Span("client.stage", 51, 54, {"h2d_bytes": 1000}),
             Span("client.sync", 70, 72, {"reads": 1, "d2h_bytes": 4}),
             Span("server.sync", 150, 160, {"reads": 4})]
    return ({"/device:TPU:0": {"modules": modules, "ops": ops}}, host,
            completed, spans)


def test_span_sums_inside_the_window():
    r = pb_spans.reduce(*_trace())
    assert r["drains"] == 1 and r["updates"] == 1
    assert r["span_s"]["server.sync"] == approx(21e-9)   # not 150-160
    assert r["span_s"]["client.stage"] == approx(3e-9)
    assert r["server_reads"] == 4
    assert r["host_bytes"] == 1004


def test_fedagg_device_time_is_the_named_kernels_in_drains():
    # 8 + 7 ns of the drain's execution; its copy, and the kernel that
    # completes after the window, do not count
    assert pb_spans.reduce(*_trace())["fedagg_device_s"] == approx(15e-9)


def test_idle_gaps_are_labelled_with_the_innermost_program_span():
    # device gaps on the host clock (smallest completion delay 1 ns):
    # -1..10 (middle 4.5 + 1: server.flatten), 30..40 (35 + 1:
    # loop.eval), 45..60 (52.5 + 1: client.stage), 70..99 (no span)
    r = pb_spans.reduce(*_trace())
    assert r["idle_gaps"] == [["loop", approx(29e-9)],
                              ["client:client.stage", approx(15e-9)],
                              ["server:server.flatten", approx(11e-9)],
                              ["loop:loop.eval", approx(10e-9)]]
    assert r["idle_by_span_s"] == approx({
        "loop": 29e-9, "client:client.stage": 15e-9,
        "server:server.flatten": 11e-9, "loop:loop.eval": 10e-9})
    assert pb_spans.server_child_share(r) == 1.0


def test_idle_in_the_drain_outside_its_children_is_not_a_child():
    devices, host, completed, spans = _trace()
    spans = [s for s in spans if s.name != "server.flatten"]
    r = pb_spans.reduce(devices, host, completed, spans)
    assert ["server:server.drain", approx(11e-9)] in r["idle_gaps"]
    assert pb_spans.server_child_share(r) == 0.0
    # a program without spans: the coarse label alone, no child's
    r = pb_spans.reduce(devices, host, completed, [])
    assert ["server", approx(11e-9)] in r["idle_gaps"]
    assert pb_spans.server_child_share(r) == 0.0


def test_innermost_pieces_of_nested_spans():
    spans = [Span("a", 0, 10, {}), Span("b", 2, 4, {}), Span("c", 3, 4, {}),
             Span("d", 6, 8, {}), Span("e", 12, 13, {})]
    assert pb_spans.innermost(spans) == [
        (0, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 6, "a"), (6, 8, "d"),
        (8, 10, "a"), (12, 13, "e")]
    pieces = pb_spans.innermost(spans)
    assert pb_spans.span_at(3.5, pieces) == "c"
    assert pb_spans.span_at(11, pieces) is None


@pytest.mark.parametrize("op,kernel", [
    ("%fedagg_norms.1 = (f32[16,128]) custom-call()", "fedagg_norms"),
    ("_fedagg_norms_batched.1", "fedagg_norms_batched"),
    ("%fedagg_apply_batched_q", "fedagg_apply_batched_q"),
    ("%fusion.1 = f32[]", None),
    ("%fedagg_normsx.2", None),
    ("_flat_aggregate.2", None)])
def test_kernel_names(op, kernel):
    assert pb_spans.kernel_of(op) == kernel


def test_per_layer_numbers():
    got = pb_spans.per_layer(pb_spans.reduce(*_trace()))
    assert got == approx({
        "server_stage_ms": 6e-6,          # flatten 4 + unflatten 2 ns
        "server_sync_ms": 21e-6,
        "server_syncs_per_update": 4.0,
        "fedagg_device_ms": 15e-6,
        "client_stage_ms": 3e-6,
        "host_bytes_per_update": 1004.0})


def test_per_layer_reads_nothing_without_program_spans():
    # a program that marks no spans: every metric reads None
    devices, host, completed, _ = _trace()
    none = dict.fromkeys(pb_spans.per_layer(None))
    assert len(none) == 6
    assert pb_spans.per_layer(
        pb_spans.reduce(devices, host, completed, [])) == none
