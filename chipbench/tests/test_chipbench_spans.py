"""The readers of the program's spans and counters: on a slice of a trace
recorded on a TPU v5e with the program's ``dystop/`` host spans, on
hand-made traces, and on histories with and without the trace's fields.

The slice (``data/trace_sim_spans_v5e.json``) is the ``sim-n100-steady``
window call from the start of its ``dystop/setup`` span to just past its
first snapshot, with times from that start; to keep it small, device ops
that touch are merged into one event named after the first."""
import json
import pathlib
import sys
import types

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import devtrace  # noqa: E402
import hostspans as HS  # noqa: E402
import run as RUN  # noqa: E402

RECORDED = json.loads((HERE / "tests" / "data" / "trace_sim_spans_v5e.json")
                      .read_text())


def reader(name):
    return RUN.load_module(HERE / "metrics" / f"{name}.py",
                           "metric_" + name.replace(".", "_")).read


def _trace(ops, spans):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [list(e) for e in ops]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [list(e) for e in spans]}]}]}


# device busy [100, 200) and [600, 700); the call runs from 50 to 900
HAND = _trace(
    [["%fusion.1 = f32[] fusion()", 100.0, 100.0],
     ["%copy.2 = f32[] copy()", 600.0, 100.0]],
    [["dystop/setup", 0.0, 40.0],           # the probe call's, left out
     ["dystop/setup", 50.0, 60.0],
     ["dystop/plan", 200.0, 150.0],
     ["dystop/plan", 300.0, 100.0],         # overlaps the one before
     ["dystop/enqueue", 450.0, 50.0],
     ["PjitFunction(step)", 500.0, 100.0],  # not a program span
     ["dystop/eval", 800.0, 100.0]])


def test_idle_is_split_by_the_span_open_on_the_host():
    # idle in [50, 900): [50,100) [200,600) [700,900) = 650 ns; setup
    # covers 50 of it, plan 200, enqueue 50, eval 100; [400,450),
    # [500,600) and [700,800) lie under no program span
    assert HS.call_extent(HS.program_spans(HAND)) == (50.0, 900.0)
    assert HS.idle_share(HAND, {"plan"}) == pytest.approx(100 * 200 / 650)
    assert reader("sim.idle_in_plan_share")({"trace": HAND}) == \
        pytest.approx(100 * 200 / 650)
    for name in ("sim.idle_unspanned_share", "lm.idle_unspanned_share"):
        assert reader(name)({"trace": HAND}) == pytest.approx(100 * 250 / 650)


def test_a_trace_without_program_spans_reads_nothing():
    bare = _trace([["%fusion.1 = f32[] fusion()", 100.0, 100.0]],
                  [["PjitFunction(step)", 0.0, 500.0]])
    assert HS.idle_share(bare) is None
    assert reader("sim.idle_unspanned_share")({"trace": bare}) is None
    no_device = {"planes": HAND["planes"][1:]}
    assert HS.idle_share(no_device) is None


def _timeline_share(trace, names):
    """The same share by a sweep over every boundary in the extent: each
    elementary segment is busy, or under an open span, where some interval
    covers it."""
    spans = HS.program_spans(trace)
    lo, hi = HS.call_extent(spans)
    devs = devtrace.device_events(trace)
    ops = [(s, s + d) for _, s, d in devs[sorted(devs)[0]]]
    opened = [(s, e) for name, s, e in spans
              if names is None or name in names]
    cuts = np.unique(np.clip([lo, hi] + [t for iv in ops + opened
                                         for t in iv], lo, hi))

    def cover(intervals):
        depth = np.zeros(len(cuts) + 1)
        for s, e in intervals:
            depth[np.searchsorted(cuts, s)] += 1
            depth[np.searchsorted(cuts, e)] -= 1
        return np.cumsum(depth)[:len(cuts) - 1] > 0

    width = np.diff(cuts)
    idle = ~cover(ops)
    return 100.0 * width[idle & cover(opened)].sum() / width[idle].sum()


def test_the_recorded_trace_reads_as_the_timeline_does():
    spans = HS.program_spans(RECORDED)
    assert {"setup", "plan", "pack", "stage", "enqueue", "drain", "eval",
            "snapshot"} <= {name for name, _, _ in spans}
    plan = reader("sim.idle_in_plan_share")({"trace": RECORDED})
    assert plan == pytest.approx(_timeline_share(RECORDED, {"plan"}),
                                 abs=1e-3)
    unspanned = reader("sim.idle_unspanned_share")({"trace": RECORDED})
    assert 100 - unspanned == pytest.approx(
        _timeline_share(RECORDED, None), abs=1e-3)
    assert 0 < plan < 100 and 0 < unspanned < 100


def _session(**fields):
    return {"session": types.SimpleNamespace(
        history=types.SimpleNamespace(**fields))}


def test_span_and_counter_readers():
    ctx = _session(round_active=[3, 1, 0, 2], snapshot_wall_s=0.002,
                   enqueue_wall_s=0.0008,
                   counts={"dispatches": 2, "train_rows": 16})
    assert reader("sim.snapshot_ms_per_round")(ctx) == pytest.approx(0.5)
    assert reader("sim.enqueue_ms_per_round")(ctx) == pytest.approx(0.2)
    assert reader("sim.rounds_per_dispatch")(ctx) == 2.0
    assert reader("lm.train_row_fill")(ctx) == pytest.approx(37.5)


def test_a_history_without_the_trace_reads_nothing():
    ctx = _session(round_active=[3, 1])
    for name in ("sim.snapshot_ms_per_round", "sim.enqueue_ms_per_round",
                 "sim.rounds_per_dispatch", "lm.train_row_fill"):
        assert reader(name)(ctx) is None, name
