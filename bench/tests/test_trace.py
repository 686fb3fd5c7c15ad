"""Reduction of a trace to busy time, idle share, kernel and program
time and the breakdown."""

import os

import pytest

from core import kernels
from core.trace import Trace

DATA = os.path.join(os.path.dirname(__file__), "data")

# window 0..100 ns; ops overlap (10-30 and 20-40 are busy 10-40 once),
# one op straddles the window's end, one lies outside it
SMALL = Trace(
    (0.0, 100.0),
    [{"ops": [("fusion.1", 10.0, 20.0), ("fusion.2", 20.0, 20.0),
              ("flash_decode.4", 50.0, 10.0), ("fusion.1", 90.0, 30.0),
              ("flash_decode.4", 92.0, 3.0), ("fusion.3", 150.0, 10.0)],
      "modules": [("jit_lane(1)", 10.0, 30.0), ("jit_step(2)", 50.0, 10.0),
                  ("jit_other(3)", 90.0, 10.0)],
      "labels": {"flash_decode.4": "%flash_decode.4 = bf16[8] custom-call()"}}],
    [("window", 0.0, 100.0), ("decode", 40.0, 15.0),
     ("prefill", 60.0, 30.0)],
)


def test_busy_is_the_union_inside_the_window():
    # 10-40, 50-60, 90-100
    assert SMALL.busy_s() == pytest.approx(50e-9)
    assert SMALL.window_s == pytest.approx(100e-9)
    assert SMALL.idle_share() == pytest.approx(50.0)


def test_busy_is_averaged_over_devices():
    two = Trace(SMALL.window, SMALL.devices + [
        {"ops": [("x", 0.0, 100.0)], "modules": []}], SMALL.host)
    assert two.busy_s() == pytest.approx(75e-9)


def test_program_time_by_module_prefix():
    assert SMALL.program_s("jit_lane") == pytest.approx(30e-9)
    assert SMALL.program_s("jit_nothing") == 0.0


def test_breakdown_names_gaps_by_the_innermost_host_span():
    b = SMALL.breakdown()
    ops = dict(b["device_ops"])
    # fusion.1: 20 ns inside plus 30 ns straddling the end, whole
    assert ops["fusion.1"] == pytest.approx(50e-9)
    # an op with a recorded HLO text is listed under it
    assert ops["%flash_decode.4 = bf16[8] custom-call()"] == pytest.approx(
        13e-9)
    assert "fusion.3" not in ops
    # idle: 0-10 (host), 40-50 (decode), 60-90 (prefill)
    assert b["idle_gaps"] == [["prefill", pytest.approx(30e-9)],
                              ["host", pytest.approx(10e-9)],
                              ["decode", pytest.approx(10e-9)]]


def test_json_round_trip():
    again = Trace.from_json(SMALL.to_json())
    assert again.busy_s() == SMALL.busy_s()
    assert again.breakdown() == SMALL.breakdown()


def test_kernel_time_is_its_named_operations():
    assert kernels.time_s(SMALL, "flash_decode", 2) == pytest.approx(13e-9)
    # a kernel off the path, or a trace that lost calls, reads nothing
    assert kernels.time_s(SMALL, "flash_decode", 3) is None
    assert kernels.time_s(SMALL, "flash_attention", 2) is None
