"""Scenario/fault-injection plane (core.scenarios) + checkpoint/resume.

The cardinal invariants under test:
  1. overlays are rng-free — a scenario's pre-event rounds are bit-identical
     to the no-scenario run, and every preset replays bit-identically across
     fused/legacy engines, any ``scan_horizon``, and ``mesh_shards`` 1 vs 2;
  2. graceful degradation — churned-out workers go idle, rejoiners get a
     staleness reset, all-neighbors-down pulls collapse to self-weight;
  3. a run resumed from a mid-run snapshot finishes with a bit-identical
     control plane and f32-equal learning curve versus the uninterrupted run.
"""
import dataclasses

import numpy as np
import pytest

from repro.checkpoint import io as CIO
from repro.core.baselines import AsyDFL
from repro.core.planner import HorizonPlanner
from repro.core.protocol import DySTop
from repro.core.scenarios import (Blackout, Churn, Degrade, Mobility,
                                  SCENARIO_PRESETS, ScenarioSchedule,
                                  Straggle, get_scenario, resolve_scenario)
from repro.dfl.lm_worker import LMRunConfig
from repro.dfl.network import NetworkConfig
from repro.dfl.simulator import SimConfig, run_simulation

from tests.test_planner import _env

_CONTROL_FIELDS = ("rounds", "sim_time", "comm_gb", "staleness_avg",
                   "staleness_max", "round_durations", "round_active")
_MODEL_FIELDS = ("acc_global", "acc_local", "loss_global")


# --------------------------------------------------------------------------- #
# event / schedule validation (satellite: actionable construction errors)
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("bad", [
    lambda: Churn(worker=0, leave_t=0),
    lambda: Churn(worker=0, leave_t=5, rejoin_t=5),
    lambda: Blackout(t_start=3, t_end=3),
    lambda: Blackout(t_start=1, t_end=5, workers=()),
    lambda: Degrade(t_start=1, t_end=5, factor=0.0),
    lambda: Degrade(t_start=1, t_end=5, factor=1.5),
    lambda: Straggle(t_start=1, t_end=5, workers=(0,), factor=1.0),
    lambda: Straggle(t_start=1, t_end=5, workers=()),
    lambda: Mobility(t_start=1, t_end=5, workers=(0,), range_scale=0.0),
    lambda: Mobility(t_start=1, t_end=5, workers=(0,), rate_factor=1.7),
])
def test_event_validation_rejects_nonsense(bad):
    with pytest.raises(ValueError):
        bad()


def test_compile_rejects_out_of_range_worker_ids():
    sched = ScenarioSchedule((Churn(worker=9, leave_t=2),))
    with pytest.raises(ValueError, match="n_workers=4"):
        sched.compile(4)


def test_mobility_needs_geometry():
    sched = ScenarioSchedule((Mobility(t_start=1, t_end=5, workers=(0,)),))
    with pytest.raises(ValueError, match="dist"):
        sched.compile(8)


def test_unknown_preset_is_actionable():
    with pytest.raises(ValueError, match="churn20"):
        get_scenario("nope", 16, 40)
    with pytest.raises(ValueError, match="ScenarioSchedule"):
        resolve_scenario(3.14, 16, 40)


@pytest.mark.parametrize("name", SCENARIO_PRESETS)
def test_presets_are_pure_functions(name):
    a = get_scenario(name, 20, 60)
    b = get_scenario(name, 20, 60)
    assert a == b and a.events and a.name == name


# --------------------------------------------------------------------------- #
# overlay semantics
# --------------------------------------------------------------------------- #


def test_overlay_churn_down_and_rejoin_flags():
    comp = ScenarioSchedule((Churn(worker=2, leave_t=3, rejoin_t=7),)).compile(5)
    assert comp.overlay(2).forced_down is None
    for t in (3, 6):
        fd = comp.overlay(t).forced_down
        assert fd is not None and fd[2] and fd.sum() == 1
    ov7 = comp.overlay(7)
    assert ov7.forced_down is None
    assert ov7.rejoined is not None and ov7.rejoined[2]
    assert comp.overlay(8).rejoined is None
    assert comp.boundaries == frozenset({3, 7})


def test_overlay_blackout_and_degrade_compose():
    sched = ScenarioSchedule((
        Blackout(t_start=2, t_end=4, workers=(0,)),
        Degrade(t_start=3, t_end=6, factor=0.5),
        Degrade(t_start=3, t_end=6, factor=0.5, workers=(1,)),
    ))
    comp = sched.compile(3)
    ov = comp.overlay(3)
    assert not ov.link_ok[0, 1] and not ov.link_ok[2, 0]
    assert ov.link_ok[1, 2]
    # degradations multiply: fleet-wide 0.5 x worker-1-touching 0.5
    assert ov.rate_scale[1, 2] == 0.25 and ov.rate_scale[0, 2] == 0.5
    assert comp.overlay(5).link_ok is None          # blackout over
    assert comp.overlay(6) is comp.overlay(10)      # shared empty overlay


def test_overlay_straggle_scales_compute():
    comp = ScenarioSchedule(
        (Straggle(t_start=1, t_end=4, workers=(1,), factor=8.0),)).compile(3)
    cs = comp.overlay(2).compute_scale
    np.testing.assert_array_equal(cs, [1.0, 8.0, 1.0])
    assert comp.overlay(4).compute_scale is None


def test_overlay_mobility_drops_far_links_only():
    dist = np.array([[0.0, 10.0, 90.0],
                     [10.0, 0.0, 50.0],
                     [90.0, 50.0, 0.0]])
    comp = ScenarioSchedule(
        (Mobility(t_start=1, t_end=3, workers=(0,), range_scale=0.5,
                  rate_factor=0.25),)).compile(3, dist=dist, comm_range_m=100.0)
    ov = comp.overlay(1)
    assert not ov.link_ok[0, 2] and not ov.link_ok[2, 0]   # 90 > 50
    assert ov.link_ok[0, 1]                                 # 10 <= 50
    assert ov.rate_scale[0, 1] == 0.25                      # kept but degraded
    assert ov.rate_scale[1, 2] == 1.0                       # untouched pair


# --------------------------------------------------------------------------- #
# planner integration: determinism, degradation, shard invariance
# --------------------------------------------------------------------------- #


def _planner(env, scenario=None, n=24, n_rounds=40, mesh_shards=1, **kw):
    comp = resolve_scenario(scenario, n, n_rounds, dist=env["net"].dist,
                            comm_range_m=env["net"].cfg.comm_range_m)
    return HorizonPlanner(DySTop(V=10.0, t_thre=8, max_neighbors=4),
                          tau_bound=5, bandwidth_budget=8.0,
                          link_timeout_s=5.0, sync_link_timeout_s=30.0,
                          mesh_shards=mesh_shards, scenario=comp, **env, **kw)


def test_pre_event_rounds_bit_identical_to_no_scenario():
    """Overlays never consume rng: before the first event fires, a scenario
    run's trajectory is byte-identical to the clean run's."""
    n = 24
    sched = ScenarioSchedule((Churn(worker=1, leave_t=12, rejoin_t=20),
                              Blackout(t_start=15, t_end=18)))
    p_clean = _planner(_env(n, seed=2), None, n)
    p_scen = _planner(_env(n, seed=2), sched, n)
    for t in range(1, 12):
        a, b = p_clean.plan_round(), p_scen.plan_round()
        np.testing.assert_array_equal(a.active, b.active)
        np.testing.assert_array_equal(a.W, b.W)
        assert a.duration == b.duration


def test_churned_out_worker_is_fully_idle_and_rejoins_reset():
    n = 24
    sched = ScenarioSchedule((Churn(worker=3, leave_t=4, rejoin_t=12),))
    pl = _planner(_env(n, seed=1), sched, n)
    for _ in range(20):
        p = pl.plan_round()
        if 4 <= p.t < 12:
            assert not p.active[3]
            assert not p.links[3].any() and not p.links[:, 3].any()
            assert p.W[3, 3] == 1.0 and p.W[3].sum() == 1.0   # idle identity
        if p.t == 12:
            # reset happened before the round's bookkeeping: tau restarted
            assert pl.st.tau[3] <= 1 and pl.st.queue[3] == 0.0


def test_blackout_degrades_to_self_weight_not_stall():
    n = 24
    sched = ScenarioSchedule((Blackout(t_start=3, t_end=8),))
    pl = _planner(_env(n, seed=4), sched, n)
    for _ in range(10):
        p = pl.plan_round()
        if 3 <= p.t < 8:
            assert p.n_transfers == 0
            act = np.nonzero(p.active)[0]
            assert act.size > 0              # WAA still activates workers
            for i in act:
                assert p.W[i, i] == 1.0      # Eq. 4 identity-row fallback


def test_degrade_window_stretches_durations_not_rng():
    """Same seed, with and without a fleet-wide Degrade: round 1's DECISIONS
    are identical (the overlay is a post-transform — rng draws match), only
    its sampled durations stretch.  Later rounds legitimately diverge (longer
    durations feed the readiness clocks), but the degraded run's simulated
    clock must fall behind."""
    n = 24
    sched = ScenarioSchedule((Degrade(t_start=1, t_end=21, factor=0.1),))
    pa = _planner(_env(n, seed=5), None, n)
    pb = _planner(_env(n, seed=5), sched, n)
    a, b = pa.plan_round(), pb.plan_round()
    np.testing.assert_array_equal(a.active, b.active)
    np.testing.assert_array_equal(a.links, b.links)
    assert b.duration >= a.duration - 1e-12
    for _ in range(19):
        pa.plan_round()
        pb.plan_round()
    assert pb.sim_clock > pa.sim_clock


@pytest.mark.parametrize("preset", SCENARIO_PRESETS)
def test_scenario_control_plane_shard_count_invariant(preset):
    """mesh_shards only affects dispatch shapes (mix_cols resolution), never
    the control trajectory: shards=2 plans == shards=1 plans, per preset."""
    n, T = 16, 30
    p1 = _planner(_env(n, seed=6), preset, n, n_rounds=T, mesh_shards=1)
    p2 = _planner(_env(n, seed=6), preset, n, n_rounds=T, mesh_shards=2)
    for _ in range(T):
        a, b = p1.plan_round(), p2.plan_round()
        np.testing.assert_array_equal(a.active, b.active)
        np.testing.assert_array_equal(a.links, b.links)
        np.testing.assert_array_equal(a.W, b.W)
        assert a.duration == b.duration and a.n_transfers == b.n_transfers


# --------------------------------------------------------------------------- #
# run_simulation: preset replay across engines/horizons + resume
# --------------------------------------------------------------------------- #


def _cfg(**kw):
    base = dict(n_workers=12, n_rounds=30, phi=0.5, lr=0.1, eval_every=10,
                seed=0, hidden=32, n_samples=3000, dim=16)
    base.update(kw)
    return SimConfig(**base)


@pytest.mark.parametrize("preset", ["churn20", "blackout"])
def test_preset_replays_bit_identically_across_engines(preset):
    """Every horizon shares the scenario trajectory bit-for-bit, control
    plane and learning curve alike."""
    mech = lambda: DySTop(V=10.0, t_thre=8, max_neighbors=4)
    h1 = run_simulation(mech(), _cfg(scenario=preset, scan_horizon=1))
    h8 = run_simulation(mech(), _cfg(scenario=preset, scan_horizon=8))
    for f in _CONTROL_FIELDS + _MODEL_FIELDS:
        assert getattr(h1, f) == getattr(h8, f), f


def test_simulation_resume_is_bit_identical(tmp_path):
    """Kill-free half of the chaos check: resume from a mid-run snapshot and
    finish with the uninterrupted run's exact trajectory (the real-SIGKILL
    cycle rides scripts/chaos_check.py)."""
    mech = lambda: DySTop(V=10.0, t_thre=8, max_neighbors=4)
    ref = run_simulation(mech(), _cfg(scenario="churn20"))
    ck = _cfg(scenario="churn20", checkpoint_every=10,
              checkpoint_dir=str(tmp_path))
    run_simulation(mech(), ck)
    first = CIO.list_checkpoints(tmp_path)[0]
    res = run_simulation(mech(), ck, resume_from=str(first))
    for f in _CONTROL_FIELDS + _MODEL_FIELDS:
        assert getattr(ref, f) == getattr(res, f), f


def test_resume_rejects_config_mismatch(tmp_path):
    mech = lambda: DySTop(V=10.0, t_thre=8, max_neighbors=4)
    ck = _cfg(checkpoint_every=10, checkpoint_dir=str(tmp_path))
    run_simulation(mech(), ck)
    other = _cfg(seed=99, checkpoint_every=10, checkpoint_dir=str(tmp_path))
    with pytest.raises(ValueError, match="resume config mismatch"):
        run_simulation(mech(), other, resume_from=str(tmp_path))


def test_resume_from_empty_dir_is_actionable(tmp_path):
    with pytest.raises(FileNotFoundError, match="no"):
        run_simulation(DySTop(), _cfg(), resume_from=str(tmp_path))


# --------------------------------------------------------------------------- #
# config validation (satellite: reject nonsense at construction)
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("kw", [
    {"failure_prob": -0.1}, {"failure_prob": 1.5}, {"failure_persist": 2.0},
    {"link_timeout_s": 0.0}, {"sync_link_timeout_s": -3.0}, {"lr": 0.0},
    {"n_workers": 0}, {"scan_horizon": 0}, {"checkpoint_every": -1},
    {"checkpoint_every": 5},                 # missing checkpoint_dir
])
def test_simconfig_rejects_out_of_range(kw):
    with pytest.raises(ValueError):
        SimConfig(**kw)


@pytest.mark.parametrize("kw", [
    {"failure_prob": -0.1}, {"failure_persist": 1.01},
    {"link_timeout_s": 0.0}, {"sync_link_timeout_s": 0.0},
    {"n_workers": 0}, {"batch": 0}, {"checkpoint_every": 3},
])
def test_lmrunconfig_rejects_out_of_range(kw):
    with pytest.raises(ValueError):
        LMRunConfig(**kw)


@pytest.mark.parametrize("kw", [
    {"dynamics_drop_prob": -0.01}, {"dynamics_drop_prob": 1.01},
    {"gain_fluctuation": -1.0}, {"n_workers": 0}, {"comm_range_m": 0.0},
    {"bandwidth_hz": -1.0},
])
def test_networkconfig_rejects_out_of_range(kw):
    with pytest.raises(ValueError):
        NetworkConfig(**kw)


def test_configs_accept_boundary_values():
    SimConfig(failure_prob=0.0, failure_persist=1.0)
    LMRunConfig(failure_prob=1.0)
    NetworkConfig(dynamics_drop_prob=0.0)
    NetworkConfig(dynamics_drop_prob=1.0)


# --------------------------------------------------------------------------- #
# checkpoint-directory helpers
# --------------------------------------------------------------------------- #


def test_checkpoint_dir_helpers(tmp_path):
    assert CIO.latest_checkpoint(tmp_path) is None
    assert CIO.latest_checkpoint(tmp_path / "missing") is None
    for t in (30, 10, 20, 40):
        CIO.save_checkpoint(CIO.checkpoint_path(tmp_path, t),
                            {"x": np.arange(3)}, extra={"round": t})
    (tmp_path / "ckpt_round000099.tmp-123.npz").write_bytes(b"turd")
    names = [p.name for p in CIO.list_checkpoints(tmp_path)]
    assert names == [f"ckpt_round{t:06d}.npz" for t in (10, 20, 30, 40)]
    assert CIO.latest_checkpoint(tmp_path).name == "ckpt_round000040.npz"
    CIO.prune_checkpoints(tmp_path, keep=2)
    names = [p.name for p in CIO.list_checkpoints(tmp_path)]
    assert names == ["ckpt_round000030.npz", "ckpt_round000040.npz"]
    assert not list(tmp_path.glob("*.tmp-*"))
