"""The trace reduction on a small trace recorded on one TPU v5e: three
launches of the fused ``dp_clip`` kernel (K=2, B=128, D=4,096) and of a
small jitted matmul, each inside a ``bench.span<i>`` host span."""
from pathlib import Path

import pytest

from bench import trace_reduce

TRACE = Path(__file__).parent / "data" / "probe.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return trace_reduce.reduce_file(str(TRACE))


def test_one_device_and_the_window_of_the_spans(red):
    assert red.devices == 1
    assert red.window_s == pytest.approx(0.02809028, rel=1e-6)
    assert 0 < red.busy_s < red.window_s


def test_kernel_time_is_its_six_custom_calls(red):
    calls = {n: s for n, s in red.ops.items()
             if "dp_clip" in n and "custom-call" in n}
    assert len(calls) == 2                       # the two passes, 3 launches each
    assert red.kernel_seconds() == pytest.approx(sum(calls.values()))
    assert red.kernel_seconds() == pytest.approx(4.6568e-05, rel=1e-6)


def test_program_time_by_name(red):
    assert red.program_seconds("cohort_step") == pytest.approx(1.6393e-05,
                                                               rel=1e-6)
    assert red.program_seconds("no_such_program") == 0.0
    assert red.collective_seconds() == 0.0


def test_breakdown_lists_the_costliest_ops_and_gaps(red):
    b = red.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert b["device_ops"][0][0].startswith("dp_clip_mean_noise_cohort")
    gaps = [s for _, s in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert {n for n, _ in b["idle_gaps"]} <= {"bench.span0", "bench.span1",
                                              "bench.span2", "none"}


def test_busy_is_the_union_of_overlapping_ops():
    assert trace_reduce._union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
    assert trace_reduce._clip([[0, 3], [5, 6]], 1, 5) == [[1, 3]]


def test_op_names_drop_layouts():
    text = ("%multiply_reduce_fusion.38 = (f32[128]{0:T(128)S(1)}, "
            "f32[128,5,40,64]{0,2,3,1:T(8,128)S(1)}) fusion(f32[128] %a), "
            "kind=kOutput")
    assert trace_reduce.op_name(text) == (
        "multiply_reduce_fusion.38 fusion (f32[128], f32[128,5,40,64])")
