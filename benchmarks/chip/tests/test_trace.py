"""The trace reduction on a synthetic trace with known busy intervals, and
on a small trace recorded on the chip."""
import pathlib
import sys

import pytest

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH_DIR))

import devtrace  # noqa: E402

# One 2.27 s prefill step of the first traced chip run (TPU v5 lite,
# qwen2_1_5b, 16 slots at max_len 2304), trimmed to that step.
RECORDED = pathlib.Path(__file__).resolve().parent / "data" / "trace_rag_poisson.json.gz"


def test_synthetic_busy_and_idle():
    # one device: ops cover [100, 115] and [120, 130] ns of a window that the
    # step spans make [100, 140]; the gap [115, 120] lies in a step, the gap
    # [130, 140] in a harvest inside a step
    trace = {
        "devices": {"/device:TPU:0": [
            ["fusion.1", 100.0, 10.0], ["PagedAttn", 105.0, 10.0], ["fusion.7", 120.0, 10.0],
            ["fusion.2", 150.0, 10.0],  # after the window: ignored
        ]},
        "modules": {"/device:TPU:0": [["jit_step", 100.0, 16.0], ["jit_step", 118.0, 14.0]]},
        "host": [["bench.step", 100.0, 30.0], ["bench.step", 131.0, 9.0],
                 ["bench.harvest", 130.0, 10.0]],
    }
    r = devtrace.reduce(trace, mark=("PagedAttn",))
    assert r["steps"] == 2
    assert r["window_s"] == pytest.approx(40e-9)
    assert r["busy_s"] == pytest.approx(25e-9)
    assert r["idle_share"] == pytest.approx(15 / 40)
    assert r["op_s"] == {"fusion": pytest.approx(20e-9), "PagedAttn": pytest.approx(10e-9)}
    assert dict(r["idle_gaps"]) == {"harvest": pytest.approx(10e-9), "step": pytest.approx(5e-9)}
    assert r["device_ops"][0] == ["fusion", pytest.approx(20e-9)]
    # only the first program ran the marked kernel
    assert r["program_s"] == {"PagedAttn": pytest.approx(16e-9)}


def test_device_plane_cut_short():
    """Where the device plane stops early (the profiler's buffers filled),
    the window holds only the steps that began before its last operation."""
    trace = {
        "devices": {"/device:TPU:0": [["x", 0.0, 8.0], ["x", 10.0, 8.0]]},
        "host": [["bench.step", 0.0, 10.0], ["bench.step", 10.0, 10.0],
                 ["bench.step", 20.0, 10.0], ["bench.step", 30.0, 10.0]],
    }
    r = devtrace.reduce(trace)
    assert r["steps"] == 2
    assert r["window_s"] == pytest.approx(20e-9)
    assert r["busy_s"] == pytest.approx(16e-9)


def test_two_devices_average():
    trace = {
        "devices": {"a": [["x", 0.0, 10.0]], "b": [["x", 0.0, 5.0]]},
        "host": [["bench.step", 0.0, 10.0]],
    }
    r = devtrace.reduce(trace)
    assert r["busy_s"] == pytest.approx(7.5e-9)
    assert r["op_s"]["x"] == pytest.approx(7.5e-9)


def test_nothing_to_read():
    assert devtrace.reduce({"devices": {}, "host": [["bench.step", 0.0, 1.0]]}) is None
    assert devtrace.reduce({"devices": {"a": [["x", 0.0, 1.0]]}, "host": []}) is None


def test_recorded_chip_trace():
    r = devtrace.reduce(devtrace.from_json(str(RECORDED)))
    assert r["window_s"] == pytest.approx(2.273168317)
    assert r["busy_s"] == pytest.approx(2.264393145)
    assert r["idle_share"] == pytest.approx(0.003860326547037607)
    assert r["gap_count"] == 50
    ops = dict(r["device_ops"])
    assert ops["PrefillAttn"] == pytest.approx(2.11678086)  # 28 layer calls
    assert ops["PagedAttn"] == pytest.approx(0.086212927)
    assert sum(r["op_s"].values()) <= r["busy_s"] * (1 + 1e-9)
    assert dict(r["idle_gaps"]) == {"step": pytest.approx(0.008775172)}
