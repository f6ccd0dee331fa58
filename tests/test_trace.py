"""The drive loops' one trace (core/trace.py): span totals, counters, the
``dystop/`` spans in a profiler trace, the named scopes in the compiled
programs, and that none of it moves a value.

One small sim and one small LM federation are run twice each, with and
without a profiler session; the tests read those runs.
"""
import glob
import os

import jax
import numpy as np
import pytest

from repro.core import trace as T
from repro.core.protocol import DySTop
from repro.dfl import lm_worker as LW
from repro.dfl import simulator as SIM
from repro.dfl import worker as WK
from repro.dfl.simulator import History, SimConfig, run_simulation
from repro.kernels.config import KernelConfig
from repro.models import registry as R

SPANS = ("setup", "plan", "pack", "stage", "enqueue", "drain", "eval",
         "snapshot")


def _shapes(args):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        if hasattr(a, "shape") else a, args)


def _host_span_names(trace_dir):
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(found[-1])
    return {e.name for pl in data.planes if pl.name.startswith("/host:")
            for ln in pl.lines for e in ln.events
            if e.name.startswith(T.PREFIX)}


def _profiled(fn, trace_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        return fn()
    finally:
        jax.profiler.stop_trace()


@pytest.fixture(scope="module")
def sim_runs(tmp_path_factory):
    """The same small sim (checkpointing, so snapshots run) without and
    with a profiler session; the mega-round's argument shapes are kept."""
    root = tmp_path_factory.mktemp("sim")
    seen = []
    real = WK.mega_round_step

    def spy(*args, **kw):
        seen.append((_shapes(args), kw))
        return real(*args, **kw)

    def run(name):
        cfg = SimConfig(n_workers=16, n_rounds=24, phi=0.5, lr=0.1,
                        eval_every=6, hidden=16, n_samples=1200, dim=8,
                        checkpoint_every=12,
                        checkpoint_dir=str(root / name))
        return run_simulation(DySTop(V=10.0, t_thre=8, max_neighbors=4), cfg)

    mp = pytest.MonkeyPatch()
    mp.setattr(WK, "mega_round_step", spy)
    try:
        plain = run("plain")
        traced = _profiled(lambda: run("traced"), root / "trace")
    finally:
        mp.undo()
    return {"plain": plain, "traced": traced, "dir": root / "trace",
            "mega": seen[0], "real": real}


@pytest.fixture(scope="module")
def lm_runs(tmp_path_factory):
    root = tmp_path_factory.mktemp("lm")
    seen = []
    real = LW.LMEngine._mega

    def spy(self, *key):
        fn = real(self, *key)

        def call(*args):
            seen.append((fn, _shapes(args)))
            return fn(*args)
        return call

    def run():
        return LW.run_lm_federation(
            DySTop(V=3.0, t_thre=3, max_neighbors=3),
            R.get_smoke_config("smollm-135m"),
            LW.LMRunConfig(n_workers=4, n_rounds=12, batch=2, seq=8,
                           eval_every=4, seed=1, checkpoint_every=6,
                           checkpoint_dir=str(root / "ckpt")))

    mp = pytest.MonkeyPatch()
    mp.setattr(LW.LMEngine, "_mega", spy)
    try:
        plain = run()
        traced = _profiled(run, root / "trace")
    finally:
        mp.undo()
    return {"plain": plain, "traced": traced, "dir": root / "trace",
            "mega": seen[0]}


class _Clock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


def test_span_self_times_nest_into_the_history(monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(T, "_clock", clock)
    h = History()
    tr = T.Trace(h)
    with tr.span("snapshot"):
        clock.now += 1.0
        with tr.span("drain"):
            clock.now += 2.0
            with tr.span("no_such_field"):
                clock.now += 4.0
        clock.now += 0.5
    with tr.span("drain"):
        clock.now += 0.25
    tr.finish()
    # a parent keeps its duration less its children's; a span without a
    # field still takes its time from its parent
    assert h.snapshot_wall_s == 1.5
    assert h.drain_wall_s == 2.25
    assert h.wall_s == 7.75
    tr.count("dispatches")
    tr.count("dispatches", 2)
    assert h.counts == {"dispatches": 3}


def test_a_span_open_twice_is_refused():
    tr = T.Trace(History())
    with tr.span("plan"):
        with pytest.raises(RuntimeError, match="already open"):
            with tr.span("plan"):
                pass
    with tr.span("plan"):      # closed again, the span reopens
        pass


def test_compiles_are_charged_to_the_innermost_span():
    h = History()
    tr = T.Trace(h)
    with tr.span("plan"):
        with tr.span("enqueue"):
            jax.jit(lambda x: x * 3 + 1)(np.arange(7.0))
    assert h.counts.get("compiles/enqueue", 0) >= 1
    assert "compiles/plan" not in h.counts


@pytest.mark.parametrize("plane", ["sim", "lm"])
def test_counters_match_the_history(plane, sim_runs, lm_runs):
    h = sim_runs["traced"] if plane == "sim" else lm_runs["traced"][1]
    c = h.counts
    assert c["train_rows"] >= sum(h.round_active)
    assert c["mix_rows"] >= sum(h.round_active)
    assert 0 < c["scan_dispatches"] <= c["dispatches"] <= len(h.round_active)
    assert c["h2d_bytes"] > 0


@pytest.mark.parametrize("plane", ["sim", "lm"])
def test_mix_narrow_rounds_counts_the_vpu_body(plane, monkeypatch):
    """``mix_narrow_rounds``: every mixing round of an N = 4 LM fleet on the
    Pallas backend runs the panel kernel's VPU body (u <= 4 < 8); no round
    of a sim at ``min_bucket`` 8 does, its buckets keep the MXU body."""
    mod = LW if plane == "lm" else SIM
    real = mod.count_dispatch
    seen = []

    def spy(trace, rounds, k_mix, *args, **kw):
        seen.append((rounds, k_mix))
        return real(trace, rounds, k_mix, *args, **kw)

    monkeypatch.setattr(mod, "count_dispatch", spy)
    pallas = KernelConfig(backend="pallas")
    if plane == "lm":
        _, h = LW.run_lm_federation(
            DySTop(V=3.0, t_thre=3, max_neighbors=3),
            R.get_smoke_config("smollm-135m"),
            LW.LMRunConfig(n_workers=4, n_rounds=8, batch=2, seq=8,
                           eval_every=4, seed=1, kernels=pallas))
    else:
        h = run_simulation(DySTop(V=10.0, t_thre=8, max_neighbors=4),
                           SimConfig(n_workers=16, n_rounds=12, hidden=16,
                                     n_samples=1200, dim=8, eval_every=6,
                                     min_bucket=8, kernels=pallas))
    mixing = sum(rounds for rounds, k_mix in seen if k_mix)
    assert mixing > 0 and h.counts["mix_rows"] > 0
    assert h.counts["mix_narrow_rounds"] == (mixing if plane == "lm" else 0)


@pytest.mark.parametrize("depth", [1, 2])
def test_sim_counters_at_every_pipeline_depth(depth):
    h = run_simulation(DySTop(V=10.0, t_thre=8, max_neighbors=4),
                       SimConfig(n_workers=16, n_rounds=12, hidden=16,
                                 n_samples=1200, dim=8, eval_every=6,
                                 pipeline_depth=depth))
    assert len(h.round_active) == 12
    assert 0 < h.counts["dispatches"] <= 12
    assert h.counts["train_rows"] >= sum(h.round_active)
    assert h.enqueue_wall_s > 0 and h.pack_wall_s > 0


@pytest.mark.parametrize("plane", ["sim", "lm"])
def test_top_level_spans_partition_the_call(plane, sim_runs, lm_runs):
    h = sim_runs["plain"] if plane == "sim" else lm_runs["plain"][1]
    spans = sum(v for k, v in h.to_dict().items()
                if k.endswith("_wall_s") and k != "wall_s")
    assert 0 < spans <= h.wall_s
    assert h.wall_s - spans < 0.05 * (h.wall_s - h.setup_wall_s)


@pytest.mark.parametrize("plane", ["sim", "lm"])
def test_profiler_holds_the_program_spans(plane, sim_runs, lm_runs):
    runs = sim_runs if plane == "sim" else lm_runs
    want = {T.PREFIX + s for s in SPANS + (("stream",) if plane == "lm"
                                           else ())}
    assert want <= _host_span_names(runs["dir"])


def test_sim_profiling_moves_no_value(sim_runs):
    a, b = sim_runs["plain"], sim_runs["traced"]
    for f in ("rounds", "sim_time", "comm_gb", "staleness_avg",
              "staleness_max", "round_durations", "round_active",
              "acc_global", "acc_local", "loss_global"):
        assert getattr(a, f) == getattr(b, f), f
    assert ({k: v for k, v in a.counts.items() if "/" not in k}
            == {k: v for k, v in b.counts.items() if "/" not in k})


def test_lm_profiling_moves_no_value(lm_runs):
    (fa, a), (fb, b) = lm_runs["plain"], lm_runs["traced"]
    for f in ("rounds", "round_active", "round_durations", "round_loss",
              "loss_global"):
        assert getattr(a, f) == getattr(b, f), f
    np.testing.assert_array_equal(np.asarray(fa.pbuf), np.asarray(fb.pbuf))
    np.testing.assert_array_equal(np.asarray(fa.obuf), np.asarray(fb.obuf))


def _op_names(hlo_text):
    return " ".join(part.split('"')[1] for part in
                    hlo_text.split("op_name=")[1:])


def test_sim_mega_round_holds_the_named_scopes(sim_runs):
    shapes, kw = sim_runs["mega"]
    names = _op_names(sim_runs["real"].lower(*shapes, **kw).compile()
                      .as_text())
    for scope in ("mega_round", "sample", "mix", "sgd", "write_back"):
        assert f"/{scope}/" in names, scope


def test_lm_mega_round_holds_the_named_scopes(lm_runs):
    fn, shapes = lm_runs["mega"]
    names = _op_names(fn.lower(*shapes).compile().as_text())
    for scope in ("mega_round", "gather", "mix", "fwd_bwd", "adam",
                  "write_back"):
        assert f"/{scope}/" in names, scope
