"""The trace reduction on a small trace recorded on a TPU v5e: three calls
of the program's jitted accuracy model (24 designs through the fused
kernel), each after a 20 ms host sleep, inside the generator's slice span.

The expected values were read by hand from the trace's raw events: the
three ``XLA Modules`` events (115346, 115548 and 115350 ns), the three
kernel events in ``XLA Ops`` (59102, 59103 and 59103 ns) and the host
span ``bench_traced_slice`` (64548693 ns)."""
import os

import pytest

import trace_reduce

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "small_trace.xplane.pb")
KERNEL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.Summary(trace_reduce.load(TRACE))


def test_window_is_the_slice_span(summary):
    assert summary.window_s == pytest.approx(64548693e-9, abs=1e-9)


def test_busy_is_the_union_of_device_ops(summary):
    modules = (115346 + 115548 + 115350) * 1e-9
    assert len(summary.busy_s) == 1
    # the ops fill their modules but for the launch edges
    assert modules * 0.99 < summary.busy_s[0] <= modules
    assert summary.mean_busy_s == summary.busy_s[0]


def test_kernel_time(summary):
    assert [len(e) for e in summary.kernel_events(KERNEL)] == [3]
    assert summary.kernel_s(KERNEL) == pytest.approx(
        (59102 + 59103 + 59103) * 1e-9, abs=1e-12)
    top = summary.top_ops(3)
    assert top[0][0].endswith("custom-call f32[24,32,32] tpu_custom_call")
    assert top[0][1] == pytest.approx(177308e-9, abs=1e-12)


def test_idle_gaps_named_by_host_work(summary):
    gaps = summary.idle_gaps(3)
    assert [g[0] for g in gaps] == ["host_sleep"] * 3
    # slice start to the first op; between the calls
    assert [round(g[1] * 1e3, 1) for g in gaps] == [21.8, 21.5, 19.1]
    assert sum(e - s for s, e in summary.gaps()) * 1e-9 == pytest.approx(
        summary.window_s - summary.busy_s[0], abs=1e-9)


def test_short_names():
    text = ('%closed_call.46 = f32[24,32,32]{2,1,0:T(8,128)S(1)} custom-call'
            '(s32[24]{0:T(128)S(1)} %fusion.1075), custom_call_target='
            '"tpu_custom_call"')
    assert trace_reduce.short_name(text) == \
        "%closed_call.46 custom-call f32[24,32,32] tpu_custom_call"
    assert trace_reduce.short_name(
        "%while.13 = (s32[]{:T(128)}, s32[32,500]) while((s32[]) %t)") == \
        "%while.13 while (tuple)"
