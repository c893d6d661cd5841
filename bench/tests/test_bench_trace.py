from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import tracing as T

FIXTURE = Path(__file__).parent / "data" / "fixture.xplane.pb"


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=list(stats.items()))


def profile():
    """Window 0..100 ns; device ops at 10-30 (kernel), 25-40, 60-70; host
    spans: a step call over 0-45 and a wait over 45-100."""
    device = NS(name="/device:TPU:0", lines=[
        NS(name="Steps", events=[ev("step", 0, 100)]),
        NS(name=T.OPS_LINE, events=[ev("fusion.1", 25, 15),
                                    ev("kern", 10, 20, hlo_category="custom-call tpu_custom_call"),
                                    ev("copy", 60, 10)])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev(T.WINDOW, 0, 100), ev("bench.step_call", 0, 45), ev("bench.wait", 45, 55),
        ev("PjitFunction(f)", 1, 2)])])
    return NS(planes=[host, device, NS(name="/device:TPU:0 SparseCore", lines=[])])


def test_reduce_busy_kernel_ops_and_gaps():
    r = T.reduce_profile(profile())
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(40e-9)  # 10-40 and 60-70
    assert r["kernel_s"] == pytest.approx(20e-9)
    assert r["device_ops"][0] == ["kern", pytest.approx(20e-9)]
    # gaps 0-10 (in the step call), 40-60 and 70-100 (in the wait)
    assert r["idle_gaps"] == [["bench.wait (2 gaps)", pytest.approx(50e-9)],
                              ["bench.step_call (1 gaps)", pytest.approx(10e-9)]]


def test_short_name_keeps_instruction_and_shape():
    name = "%fused_engine.16 = f32[128,6,6,4,512]{4,3,2,1,0:T(4,128)} custom-call(f32[1]{0} %p)"
    assert T.short_name(name) == "fused_engine.16 f32[128,6,6,4,512]"
    assert T.short_name("copy") == "copy"


def test_reduce_clips_to_window_and_needs_device_ops():
    p = profile()
    p.planes[0].lines[0].events[0] = ev(T.WINDOW, 20, 30)  # window 20..50
    r = T.reduce_profile(p)
    assert r["window_s"] == pytest.approx(30e-9)
    assert r["busy_s"] == pytest.approx(20e-9)
    assert r["kernel_s"] == pytest.approx(10e-9)
    p.planes[1].lines[1].events = []
    with pytest.raises(ValueError):
        T.reduce_profile(p)


def test_reduce_recorded_chip_trace():
    """A trace recorded on a TPU v5e by ``record_fixture.py``: one Pallas
    kernel and one XLA matmul, three calls each, 5 ms sleeps between."""
    r = T.reduce_file(FIXTURE)
    assert r["chips"] == 1
    assert r["kernel_s"] == pytest.approx(7.087e-6)
    assert 0 < r["kernel_s"] < r["busy_s"] < 0.01 * r["window_s"]
    assert r["device_ops"][0][0] == "kernel_call.1 f32[512,512]"
    assert r["idle_gaps"][0][0].startswith("bench.fixture.sleep")
    assert r["idle_gaps"][0][1] > 0.015
