"""The trace reduction, on a slice of a trace recorded on a TPU v5e and on
hand-made traces."""
import json
import pathlib
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import devtrace as T  # noqa: E402

RECORDED = json.loads((HERE / "tests" / "data" / "trace_sim_v5e.json")
                      .read_text())


def _trace(events, host=()):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_step(1)", 0.0, 1e9]]},
            {"name": "XLA Ops", "events": [list(e) for e in events]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [list(e) for e in host]}]}]}


def test_busy_is_the_union_on_the_recorded_trace():
    evs = T.device_events(RECORDED)["/device:TPU:0"]
    assert evs, "the recorded slice holds device ops"
    lo = int(min(s for _, s, _ in evs))
    hi = int(max(s + d for _, s, d in evs))
    timeline = np.zeros(hi - lo + 1, bool)
    for _, s, d in evs:
        timeline[int(s) - lo:int(s + d) - lo] = True
    assert T.busy_s(RECORDED) == pytest.approx(timeline.sum() / 1e9, abs=2e-9)


def test_only_the_ops_line_counts():
    # the module line spans the whole second; only the ops line is busy time
    tr = _trace([["%fusion.1 = f32[] fusion()", 0.0, 100.0],
                 ["%fusion.2 = f32[] fusion()", 50.0, 100.0],
                 ["%copy.3 = f32[] copy()", 400.0, 10.0]])
    assert T.busy_s(tr) == pytest.approx(160e-9)


def test_kernel_time_is_matched_on_the_op_name():
    tr = _trace([
        ["%dystop_aggregate_panel.1 = f32[8,512] custom-call(%p.0)", 0.0, 300.0],
        ["%dystop_aggregate_panel = f32[8,512] custom-call(%p.1)", 1e3, 200.0],
        ["%copy.5 = f32[8,512] copy(%dystop_aggregate_panel.1)", 2e3, 50.0]])
    assert T.kernel_s(tr, "dystop_aggregate_panel") == pytest.approx(500e-9)
    assert T.kernel_s(tr, "flash_attention") is None


def test_breakdown_names_ops_and_gaps():
    tr = _trace([["%fusion.1 = f32[] fusion()", 0.0, 100.0],
                 ["%fusion.2 = f32[] fusion()", 1000.0, 100.0],
                 ["%copy.3 = f32[] copy()", 5000.0, 10.0]],
                host=[["PjitFunction(step)", 0.0, 6000.0],
                      ["plan_round", 1200.0, 3700.0]])
    b = T.breakdown(tr)
    assert b["device_ops"][0] == ["fusion", pytest.approx(200e-9)]
    assert [g[0] for g in b["idle_gaps"]] == ["plan_round", "PjitFunction(step)"]
    assert b["idle_gaps"][0][1] == pytest.approx(3900e-9)


def test_breakdown_counts_a_loop_body_once():
    tr = _trace([["%while.7 = (f32[]) while(%t.1)", 0.0, 1000.0],
                 ["%fusion.1 = f32[] fusion()", 10.0, 300.0],
                 ["%dystop_aggregate_panel.2 = f32[] custom-call()", 400.0,
                  500.0],
                 ["%copy.3 = f32[] copy()", 2000.0, 10.0]])
    b = T.breakdown(tr)
    assert [op for op, _ in b["device_ops"]] == [
        "dystop_aggregate_panel", "fusion", "copy"]
    assert T.busy_s(tr) == pytest.approx(1010e-9)


def test_breakdown_of_the_recorded_trace_is_bounded():
    b = T.breakdown(RECORDED)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)


def test_no_device_plane_reads_nothing():
    tr = {"planes": [{"name": "/host:CPU", "lines": []}]}
    assert T.busy_s(tr) == 0.0
    assert T.kernel_s(tr, "dystop_aggregate_panel") is None
    assert T.breakdown(tr) == {"device_ops": [], "idle_gaps": []}
