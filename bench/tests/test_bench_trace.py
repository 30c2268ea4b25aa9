"""Trace reduction: busy time is the union of device op intervals inside
the window, kernel time sums the kernel's op events per output shape,
idle gaps are named by the host span that covers them."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import trace_reduce  # noqa: E402

DEV = "/device:TPU:0"
HOST = "/host:CPU"
US = 1000


def _events():
    return [
        (HOST, "python", "bench:window_start", 100 * US, 0),
        (HOST, "python", "bench:await_frames", 350 * US, 300 * US),
        (HOST, "python", "bench:window_end", 1100 * US, 0),
        # before the window: not counted
        (DEV, "XLA Ops", "fusion.1", 0, 50 * US),
        # two overlapping ops count once: 200..300
        (DEV, "XLA Ops", "custom-call.2", 200 * US, 80 * US),
        (DEV, "XLA Ops", "fusion.3", 250 * US, 50 * US),
        # 700..800, then one op that runs past the window end
        (DEV, "XLA Ops", "fusion.4", 700 * US, 100 * US),
        (DEV, "XLA Ops", "fusion.5", 1050 * US, 100 * US),
    ]


def test_busy_is_the_union_inside_the_window():
    r = trace_reduce.reduce(_events())
    assert r["window_s"] == pytest.approx(1000e-6)
    assert r["busy_s"] == pytest.approx((100 + 100 + 50) * 1e-6)
    assert r["kernels"] == {}


def test_idle_gaps_are_named_by_the_host_span_over_them():
    r = trace_reduce.reduce(_events())
    gaps = {round(s * 1e6): n for n, s in r["idle_gaps"]}
    assert gaps[400] == "bench:await_frames"       # 300..700
    assert gaps[100] == "host:runtime"             # 100..200
    assert gaps[250] == "host:runtime"             # 800..1050
    assert r["device_ops"][0][0] in ("custom-call.2", "fusion.4")


def test_no_device_events_reads_nothing():
    r = trace_reduce.reduce([e for e in _events() if e[0] == HOST])
    assert r["busy_s"] == 0.0 and r["kernels"] == {}


def test_kernel_ops_are_read_with_their_output_shapes():
    evs = _events() + [
        (DEV, "XLA Ops", '%frame_diff.1 = f32[16,4,8]{2,1,0} custom-call('
         'u8[16,3,128,256] %cur), custom_call_target="tpu_custom_call"',
         500 * US, 10 * US)]
    r = trace_reduce.reduce(evs)
    assert r["kernels"] == {"frame_diff": {"f32[16,4,8]": [1, 10e-6]}}


# 0.6 s of the window of a traced samsara-fleet.busy run on one TPU v5e
# (op texts shortened; the window markers moved to the slice's ends)
RECORDED = os.path.join(BENCH, "tests", "data", "trace_events.json")


def test_recorded_chip_trace():
    with open(RECORDED) as f:
        evs = [tuple(e) for e in json.load(f)]
    r = trace_reduce.reduce(evs)
    assert r["window_s"] == pytest.approx(0.6)
    assert 0 < r["busy_s"] < 0.01 * r["window_s"]
    fd = r["kernels"]["frame_diff"]["f32[16,4,8]"]
    assert fd[0] == 15 and 0 < fd[1] < r["busy_s"]
    assert r["kernels"]["fused_preprocess"]["f32[16,3,32,112]"][0] == 8
    assert r["idle_gaps"][0][0] == "bench:await_frames"
