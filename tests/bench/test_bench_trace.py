"""The reduction from a profiler trace to busy, idle and kernel time."""
import json

import pytest

import trace_reduce
from conftest import BENCH


def test_summary_of_a_hand_made_trace():
    ms = 1_000_000
    trace = {
        "devices": {"/device:TPU:0": [
            ["fusion.1", 0 * ms, 2 * ms, False],       # before the window
            ["fusion.2", 11 * ms, 3 * ms, False],
            ["custom-call.7", 13 * ms, 4 * ms, True],  # overlaps fusion.2
            ["fusion.2", 30 * ms, 5 * ms, False],
        ]},
        "spans": [["bench.window", 10 * ms, 40 * ms],
                  ["rm.step", 10 * ms, 30 * ms],
                  ["rm.carve", 20 * ms, 5 * ms],
                  ["engine.wave", 25 * ms, 15 * ms]],
    }
    s = trace_reduce.summarize(trace)
    assert s["window_s"] == pytest.approx(0.040)
    assert s["busy_s"] == pytest.approx(0.011)           # 11-17 and 30-35
    assert s["kernel_s"] == pytest.approx(0.004)
    assert dict(s["device_ops"]) == pytest.approx(
        {"fusion.2": 0.008, "custom-call.7": 0.004})
    # gaps: 10-11 rm.step, 17-30 split by its middle (23.5: rm.carve),
    # 35-50 by its middle (42.5: harness)
    assert dict(s["idle_gaps"]) == pytest.approx(
        {"rm.step": 0.001, "rm.carve": 0.013, "harness": 0.015})


def test_no_window_or_no_device_work_reads_nothing():
    assert trace_reduce.summarize({"devices": {}, "spans": [
        ["bench.window", 0, 10]]}) is None
    assert trace_reduce.summarize({"devices": {"/device:TPU:0": [
        ["a", 0, 5, False]]}, "spans": []}) is None


def test_summary_of_a_recorded_chip_trace():
    """The first 100 ms of a traced window of capability4096-psa.steady on
    one v5e: the carve, then the first solve of a multilevel wave."""
    trace = json.loads((BENCH / "testdata" / "trace_capability.json")
                       .read_text())
    s = trace_reduce.summarize(trace)
    assert s["window_s"] == pytest.approx(0.1)
    assert s["busy_s"] == pytest.approx(0.003808618)
    assert s["kernel_s"] == pytest.approx(0.003118736)
    assert dict(s["idle_gaps"]) == pytest.approx(
        {"engine.wave": 0.06498827, "rm.carve": 0.031203112})
    # busy and idle tile the window on one chip
    assert s["busy_s"] + sum(v for _, v in s["idle_gaps"]) == pytest.approx(
        s["window_s"])
    top = dict(s["device_ops"])
    assert top["qap_delta_pallas_batch.6"] == pytest.approx(s["kernel_s"])
    assert len(s["device_ops"]) == 10


def test_op_names_are_cut_from_the_hlo_text():
    hlo = ('%qap_delta_pallas_batch.6 = f32[480,1,1]{2,1,0} custom-call('
           's32[1920]{0} %reshape.335), custom_call_target="tpu_custom_call"')
    assert trace_reduce.op_name(hlo) == "qap_delta_pallas_batch.6"
    assert trace_reduce.op_name("fusion.3") == "fusion.3"
