"""bench/trace_reduce.py on a trace recorded on a TPU v5e
(``data/small.xplane.pb``, made by ``record_trace.py``: three calls of a
jitted fused-routing kernel plus a matmul, with a host pause before
each, inside the ``bench/window`` span), checked against plain sums over
the same events, and on hand-made operations."""
from pathlib import Path

import pytest

from bench import trace_reduce as tr
from bench.trace_reduce import Op

TRACE = Path(__file__).parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def planes():
    return tr.read_planes(str(TRACE))


def test_recorded_trace_has_one_chip_and_the_window(planes):
    devices, host = planes
    assert list(devices) == [0]
    assert [h.name for h in host].count("bench/window") == 1
    assert [h.name for h in host].count("host/pause") == 3


def test_busy_and_kernel_time_are_the_plain_sums(planes):
    devices, host = planes
    win = next(h for h in host if h.name == "bench/window")
    ops = [o for o in devices[0] if o.end > win.start and o.start < win.end]
    red = tr.reduce(str(TRACE))
    assert red.window_s == pytest.approx((win.end - win.start) / 1e9)
    # the ops of this trace do not overlap: busy is their plain sum
    assert red.busy_s == pytest.approx(
        sum(o.end - o.start for o in ops) / 1e9)
    kern = [o for o in ops if o.name.startswith("routed_attention_fused")]
    assert len(kern) == 3
    assert red.kernel_s("routed_attention_fused") == pytest.approx(
        sum(o.end - o.start for o in kern) / 1e9)
    assert red.matching_s('custom_call_target="tpu_custom_call"') == \
        pytest.approx(red.kernel_s("routed_attention_fused"))
    assert red.top_ops[0][0].startswith("routed_attention_fused")
    assert 0 < red.busy_s < red.window_s


def test_idle_gaps_are_named_by_the_host_span(planes):
    red = tr.reduce(str(TRACE))
    # the longest idle stretches are the host's pauses between calls
    assert [name for name, _ in red.idle_gaps[:3]] == ["host/pause"] * 3
    assert sum(s for _, s in red.idle_gaps) <= red.window_s - red.busy_s \
        + 1e-12


def test_containers_count_as_busy_but_not_as_operations():
    ops = [Op("while.1", 0, 100, container=True), Op("fusion.1", 10, 40),
           Op("fusion.2", 50, 90)]
    red = tr.reduce_ops({0: ops}, [Op("bench/window", 0, 200)])
    assert red.busy_s == pytest.approx(100e-9)
    assert set(red.op_s) == {"fusion.1", "fusion.2"}
    assert red.window_s == pytest.approx(200e-9)


def test_exposed_collective_time_and_chip_mean():
    chip0 = [Op("fusion.1", 0, 50), Op("all-reduce.1", 40, 100)]
    chip1 = [Op("fusion.1", 0, 100), Op("all-reduce.1", 40, 100)]
    red = tr.reduce_ops({0: chip0, 1: chip1}, [Op("bench/window", 0, 100)])
    assert red.chips == 2
    assert red.collective_s == pytest.approx(60e-9)
    # chip 0: 50 of the 60 ns have no other op; chip 1: none
    assert red.collective_exposed_s == pytest.approx(25e-9)
    assert red.busy_s == pytest.approx(100e-9)
    assert red.kernel_s("fusion") == pytest.approx(75e-9)


def test_instruction_names():
    op = tr.device_op("%routed_attention_fused.41 = (f32[16,32]) "
                      "custom-call(s32[3] %x), custom_call_target="
                      "\"tpu_custom_call\"", 0, 1)
    assert op.name == "routed_attention_fused.41" and not op.container
    assert tr.device_op("%while.13 = (s32[]) while(%t)", 0, 1).container


def test_union_and_covered():
    m = tr.union([(5, 9), (0, 2), (1, 3), (8, 12)])
    assert m == [(0, 3), (5, 12)]
    assert tr.covered(m, 2, 6) == 2
    assert tr.covered(m, 3, 5) == 0
