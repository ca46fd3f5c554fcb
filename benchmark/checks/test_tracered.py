"""The trace reduction on a trace built by hand."""
import pytest

from lib.tracered import (COLLECTIVE, CONV, DOT, OTHER, NO_SPAN, Op, Span,
                          Trace, merge, self_times, subtract)


def hand_trace():
    """Two steps in a window of 1000 ns on two devices.

    Device 0: conv [0,100) other [100,150) | gap 50 | a loop [200,400)
    that encloses dot [210,300) and other [300,380) | collective
    [400,460) | gap | conv [500,600) overlapped by a collective on a
    second line [550,650) | idle to 1000.
    Device 1: one conv [0,400)."""
    d0 = [Op(0, 100, "a_conv", CONV), Op(100, 50, "a_relu", OTHER),
          Op(200, 200, "loop", OTHER), Op(210, 90, "b_dot", DOT),
          Op(300, 80, "b_add", OTHER), Op(400, 60, "allreduce", COLLECTIVE),
          Op(500, 100, "c_conv", CONV), Op(550, 100, "gather", COLLECTIVE),
          Op(-50, 30, "before", OTHER), Op(990, 100, "tail", OTHER)]
    d1 = [Op(0, 400, "a_conv", CONV)]
    spans = [Span(0, 1000, "bench.window"), Span(140, 70, "bench.calls"),
             Span(150, 20, "bench.inner"), Span(640, 300, "bench.wait")]
    return Trace({0: d0, 1: d1}, spans, (0, 1000), steps=2)


def test_interval_arithmetic():
    assert merge([(5, 9), (0, 3), (2, 4), (9, 9)]) == [[0, 4], [5, 9]]
    assert subtract([[0, 10], [20, 30]], [[5, 25]]) == 10
    assert subtract([[0, 10]], []) == 10
    assert subtract([[0, 10]], [[0, 10]]) == 0


def test_self_time_of_a_loop_leaves_out_its_body():
    ops = [Op(200, 200, "loop", OTHER), Op(210, 90, "dot", DOT),
           Op(300, 80, "add", OTHER)]
    got = {op.name: ns for op, ns in self_times(ops)}
    assert got == {"loop": 30, "dot": 90, "add": 80}


def test_busy_idle_and_window_clip():
    t = hand_trace()
    # device 0: [0,150) [200,460) [500,650) [990,1000) = 150+260+150+10
    assert t.busy_ns(0) == 570 and t.busy_ns(1) == 400
    assert t.fullest() == 0
    assert t.window_s() == pytest.approx(1e-6)
    assert t.busy_s() == pytest.approx((570 + 400) / 2 / 1e9)
    assert t.idle_share() == pytest.approx(0.43)
    assert t.device_ms_per_step() == pytest.approx(570 / 1e6 / 2)


def test_time_by_kind_is_self_time_averaged_over_devices():
    t = hand_trace()
    # matmul kinds: dev0 100 + 90 + 100, dev1 400
    assert t.kind_ms_per_step({CONV, DOT}) == pytest.approx(
        (290 + 400) / 2 / 1e6 / 2)
    # all others on dev0: relu 50, loop self 30, add 80, two collectives
    # 60 + 100, tail 10 (clipped); dev1 none
    assert t.kind_ms_per_step({CONV, DOT}, invert=True) == pytest.approx(
        (50 + 30 + 80 + 160 + 10) / 2 / 1e6 / 2)


def test_exposed_collective_time_is_what_compute_does_not_cover():
    t = hand_trace()
    # allreduce [400,460) all exposed; gather [550,650) hidden to 600
    assert t.exposed_collective_ms_per_step() == pytest.approx(
        (60 + 50) / 1e6 / 2)
    one = Trace({0: [Op(0, 10, "c", CONV)]}, [], (0, 10), 1)
    assert one.exposed_collective_ms_per_step() is None


def test_idle_gaps_go_to_the_innermost_host_span():
    gaps = dict(hand_trace().idle_gaps())
    # [150,200) middle 175 -> bench.calls (bench.inner ends at 170);
    # [460,500) -> bench.window; [650,990) middle 820 -> bench.wait
    assert gaps == {"bench.calls": pytest.approx(50e-9),
                    "bench.window": pytest.approx(40e-9),
                    "bench.wait": pytest.approx(340e-9)}
    bare = Trace({0: [Op(0, 10, "c", CONV)]}, [], (0, 30), 1)
    assert dict(bare.idle_gaps()) == {NO_SPAN: pytest.approx(20e-9)}


def test_breakdown_names_the_heaviest_operations_by_the_step():
    top = hand_trace().breakdown()["device_ops"]
    assert top[0] == ["a_conv", pytest.approx(100e-9 / 2)] or \
        top[0][0] in ("a_conv", "c_conv", "gather")
    assert len(top) <= 10
    assert dict(top)["loop"] == pytest.approx(30e-9 / 2)


def test_every_metric_of_the_benchmark_reads_the_hand_trace():
    """Each per-layer metric's file names a reader that returns a number
    or nothing from a context like a run's; a roofline or an idle share
    is never 0 for want of something to read."""
    from lib import spec
    bench = spec.benchmark()
    ctx = {"trace": hand_trace(), "device": {"count": 2,
                                            "memory_peak_bytes": 7e9},
           "window": {"steps": 2, "host_s": 0.004, "seconds": 1e-6},
           "counters": {"setup": {"loads": 2, "compiles": 0, "traces": 0},
                        "window": {"loads": 0, "compiles": 0, "traces": 0}},
           "costs": {"model_flops": 1e6,
                     "matmul": {"flops": 1e6, "bytes": 1e3}},
           "peaks": spec.peaks("TPU v5 lite")}
    for cell in bench["workloads"]:
        for m in spec.Cell(cell["name"], bench).per_layer:
            value = spec.reader(m["reader"]).read(ctx, **m.get("args", {}))
            if m["name"].startswith("kernel.flash_roofline"):
                assert value is None        # no attention cost, no scope
            else:
                assert value is not None and value == value, m["name"]
    with pytest.raises(spec.SpecError):
        spec.peaks("TPU v9")
