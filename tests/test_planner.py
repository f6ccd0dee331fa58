"""Horizon scheduler + mega-round dispatcher: planner-vs-sequential oracle,
scan-vs-single-round trajectory equality, and pack/scan numerics.

The control plane is model-value-independent, so:
  1. H ``HorizonPlanner.plan`` rounds must match H sequential
     ``Mechanism.round`` calls EXACTLY (activation sets, links, W rows,
     staleness counters, durations) — the planner is a pure replay;
  2. ``run_simulation`` histories must be identical (control plane AND
     learning curves, bit-for-bit) at ANY ``scan_horizon`` — horizons only
     change how many rounds ride in one ``lax.scan`` dispatch;
  3. ``scan_horizon=1`` must dispatch through the per-round ``round_step``
     path (the PR 1 fused engine, kept as the oracle).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aggregation import (bucket_size, mixing_matrix, mixing_rows,
                                    padded_rows)
from repro.core.baselines import AsyDFL, GossipFL
from repro.core.planner import HorizonPlanner, PlannedRound
from repro.core.protocol import DySTop, RoundContext
from repro.core.staleness import StalenessState
from repro.data.partition import dirichlet_partition
from repro.data.synthetic import make_classification, train_test_split
from repro.dfl import flat_state as FS
from repro.dfl import worker as WK
from repro.dfl.network import (EdgeNetwork, NetworkConfig,
                               heterogeneous_compute_times)
from repro.dfl.simulator import SimConfig, run_simulation


def _env(n=24, seed=0, phi=0.5):
    """A small but real planner environment (network, partition, costs)."""
    rng = np.random.default_rng(seed)
    full = make_classification(2000, 16, seed=seed)
    data, _ = train_test_split(full, 0.2, seed=seed)
    parts, class_counts = dirichlet_partition(data, n, phi, seed=seed)
    data_sizes = np.array([len(p) for p in parts], np.float64)
    net = EdgeNetwork(NetworkConfig(n_workers=n), rng)
    h_i = heterogeneous_compute_times(n, 1.0, rng, sigma=0.75)
    model_bytes = 27000.0
    return dict(h_i=h_i, in_range=net.in_range(),
                exp_link_time=net.expected_link_time(model_bytes),
                model_bytes=model_bytes, class_counts=class_counts,
                data_sizes=data_sizes, net=net, rng=rng)


def _sequential_reference(mechanism, env, n, horizon, *, tau_bound=5,
                          failure_prob=0.0, failure_persist=0.5):
    """The pre-planner per-round loop semantics, re-implemented independently
    (same rng consumption order: failure draws, mechanism, channels)."""
    rng = env["rng"]
    st = StalenessState.create(n, tau_bound)
    pull_counts = np.zeros((n, n), np.float64)
    time_since_act = np.zeros(n, np.float64)
    budget = np.full(n, 8.0, np.float64)
    down = np.zeros(n, bool)
    out = []
    for t in range(1, horizon + 1):
        if failure_prob > 0:
            down = ((down & (rng.random(n) < failure_persist))
                    | (~down & (rng.random(n) < failure_prob)))
        up_range = env["in_range"] & ~down[None, :] & ~down[:, None]
        h_cmp = np.maximum(env["h_i"] - time_since_act, 0.0)
        est_com = np.where(up_range, env["exp_link_time"], 0.0).max(axis=1)
        ctx = RoundContext(
            t=t, round_cost=h_cmp + est_com,
            readiness=env["h_i"] - time_since_act, in_range=up_range,
            class_counts=env["class_counts"], phys_dist=env["net"].dist,
            pull_counts=pull_counts, staleness=st, bandwidth_budget=budget,
            data_sizes=env["data_sizes"], rng=rng,
            base_in_range=env["in_range"])
        dec = mechanism.round(ctx)
        if failure_prob > 0:
            dec.active = dec.active & ~down
            dec.links = dec.links & ~down[None, :] & ~down[:, None]
        raw = env["model_bytes"] / env["net"].link_rates()
        if dec.synchronous:
            link_time = np.minimum(raw, 30.0)
            cmp_part, eligible = env["h_i"], np.ones(n, bool)
        else:
            link_time = np.minimum(raw, 5.0)
            cmp_part, eligible = h_cmp, dec.active
        com = np.where(dec.links, link_time, 0.0).max(axis=1)
        dur = float((cmp_part + com)[eligible].max()) if eligible.any() else 0.0
        W = mixing_matrix(dec.active, dec.links, env["data_sizes"])
        pull_counts += dec.links
        time_since_act += dur
        time_since_act[dec.active] = 0.0
        st.advance(dec.active)
        out.append((dec, W, dur, st.tau.copy(), st.queue.copy()))
    return out


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("mech_cls", [
    lambda: DySTop(V=10.0, t_thre=6, max_neighbors=4, max_workers=8),
    lambda: AsyDFL(n_neighbors=3),          # exercises ctx.rng draws
])
def test_planner_matches_sequential_mechanism_rounds(seed, mech_cls):
    """H planned rounds == H sequential Mechanism.round calls, exactly."""
    n, horizon = 24, 20
    env_p, env_s = _env(n, seed), _env(n, seed)
    planner = HorizonPlanner(mech_cls(), tau_bound=5, bandwidth_budget=8.0,
                             link_timeout_s=5.0, sync_link_timeout_s=30.0,
                             **env_p)
    plans = planner.plan(horizon)
    ref = _sequential_reference(mech_cls(), env_s, n, horizon)
    assert len(plans) == len(ref) == horizon
    for p, (dec, W, dur, tau, queue) in zip(plans, ref):
        np.testing.assert_array_equal(p.active, dec.active)
        np.testing.assert_array_equal(p.links, dec.links)
        np.testing.assert_array_equal(p.W, W)
        assert p.duration == dur
        assert p.n_transfers == int(dec.links.sum())
    # the planner's final staleness counters match the sequential loop's
    np.testing.assert_array_equal(planner.st.tau, ref[-1][3])
    np.testing.assert_array_equal(planner.st.queue, ref[-1][4])


def test_planner_respects_max_round():
    env = _env(16)
    planner = HorizonPlanner(DySTop(V=10.0, t_thre=4), tau_bound=5,
                             bandwidth_budget=8.0, link_timeout_s=5.0,
                             sync_link_timeout_s=30.0, **env)
    assert len(planner.plan(8, max_round=5)) == 5
    assert planner.t == 5
    assert len(planner.plan(8, max_round=5)) == 0


def _failure_planner(seed, *, persist, prob=0.25, n=24, mesh_shards=1):
    return HorizonPlanner(DySTop(V=10.0, t_thre=6, max_neighbors=4),
                          tau_bound=5, bandwidth_budget=8.0,
                          link_timeout_s=5.0, sync_link_timeout_s=30.0,
                          failure_prob=prob, failure_persist=persist,
                          mesh_shards=mesh_shards, **_env(n, seed))


def test_failure_persist_one_is_monotone():
    """persist=1.0: a downed worker never recovers — the down mask is
    monotone non-decreasing round over round."""
    planner = _failure_planner(seed=1, persist=1.0)
    prev = np.zeros(24, bool)
    for _ in range(40):
        planner.plan(1)
        assert (prev <= planner.down).all()
        prev = planner.down.copy()
    assert prev.any()          # with prob=0.25 over 40 rounds, someone fell


def test_failure_persist_zero_never_stays_down():
    """persist=0.0: every failure lasts exactly one round — no worker is
    down in two consecutive rounds."""
    planner = _failure_planner(seed=1, persist=0.0)
    prev = np.zeros(24, bool)
    seen_down = False
    for _ in range(60):
        planner.plan(1)
        assert not (prev & planner.down).any()
        seen_down = seen_down or planner.down.any()
        prev = planner.down.copy()
    assert seen_down


def test_failure_mask_bit_exact_across_chunking_and_shards():
    """The failure-mask trajectory is a property of the rng stream alone:
    one plan(24) call, 24 plan(1) calls, and mesh_shards=2 (dispatch-shape
    only, no control rng) all yield identical plans and down masks."""
    whole = _failure_planner(seed=5, persist=0.5).plan(24)

    stepped_pl = _failure_planner(seed=5, persist=0.5)
    stepped, downs = [], []
    for _ in range(24):
        stepped.extend(stepped_pl.plan(1))
        downs.append(stepped_pl.down.copy())

    sharded_pl = _failure_planner(seed=5, persist=0.5, mesh_shards=2)
    sharded = sharded_pl.plan(24)

    for variant in (stepped, sharded):
        for p, q in zip(whole, variant):
            np.testing.assert_array_equal(p.active, q.active)
            np.testing.assert_array_equal(p.links, q.links)
            np.testing.assert_array_equal(p.W, q.W)
            assert p.duration == q.duration
            assert p.n_transfers == q.n_transfers
    np.testing.assert_array_equal(sharded_pl.down, downs[-1])


@pytest.mark.parametrize("mech_cls,sync,ceiling", [
    (lambda: DySTop(V=10.0, t_thre=6, max_neighbors=4), False, 5.0),
    (lambda: GossipFL(), True, 30.0),
])
def test_comm_accounting_and_timeout_ceilings(mech_cls, sync, ceiling):
    """Per-round durations respect the link-timeout ceilings (async rounds
    bounded by max h_cmp + link_timeout_s, sync rounds by max h_i +
    sync_link_timeout_s) and comm_bytes is exactly Σ n_transfers x
    model_bytes.  Synchrony is a mechanism property: GossipFL pays the
    sync ceiling, DySTop the async one."""
    env = _env(24, seed=2)
    planner = HorizonPlanner(mech_cls(), tau_bound=3, bandwidth_budget=8.0,
                             link_timeout_s=5.0, sync_link_timeout_s=30.0,
                             **env)
    plans = planner.plan(40)
    h_max = env["h_i"].max()
    assert all(p.synchronous == sync for p in plans)
    for p in plans:
        # async: h_cmp <= h_i elementwise, links capped at link_timeout_s;
        # sync: full h_i plus links capped at sync_link_timeout_s
        assert p.duration <= h_max + ceiling + 1e-9
    assert any(p.n_transfers > 0 for p in plans)
    assert planner.comm_bytes == pytest.approx(
        sum(p.n_transfers for p in plans) * env["model_bytes"], rel=0, abs=0)
    assert planner.sim_clock == pytest.approx(
        sum(p.duration for p in plans), rel=0, abs=1e-9)


def test_planner_replays_failure_dynamics():
    n, horizon, seed = 24, 16, 3
    mech = lambda: DySTop(V=10.0, t_thre=6, max_neighbors=4)
    planner = HorizonPlanner(mech(), tau_bound=5, bandwidth_budget=8.0,
                             link_timeout_s=5.0, sync_link_timeout_s=30.0,
                             failure_prob=0.2, failure_persist=0.5,
                             **_env(n, seed))
    plans = planner.plan(horizon)
    ref = _sequential_reference(mech(), _env(n, seed), n, horizon,
                                failure_prob=0.2, failure_persist=0.5)
    for p, (dec, W, dur, _, _) in zip(plans, ref):
        np.testing.assert_array_equal(p.active, dec.active)
        np.testing.assert_array_equal(p.links, dec.links)
        assert p.duration == dur


# --------------------------------------------------------------------------- #
# pack_horizon + mega_round_step == sequential round_step
# --------------------------------------------------------------------------- #


def _fake_plans(rng, n, h, frac=0.4):
    plans = []
    for t in range(1, h + 1):
        active = rng.random(n) < frac
        if not active.any():
            active[rng.integers(n)] = True
        links = (rng.random((n, n)) < 0.15) & active[:, None]
        np.fill_diagonal(links, False)
        W = mixing_matrix(active, links, rng.uniform(1, 10, n))
        plans.append(PlannedRound(t=t, active=active, links=links,
                                  synchronous=False, W=W, duration=1.0,
                                  n_transfers=int(links.sum())))
    return plans


def test_pack_horizon_shapes_and_padding():
    rng = np.random.default_rng(0)
    n, h = 20, 6
    plans = _fake_plans(rng, n, h)
    w_rows, ctrl, ts = WK.pack_horizon(plans)
    k_mix = max(bucket_size(int((p.active | p.links.any(1)).sum()), n)
                for p in plans)
    k_train = max(bucket_size(int(p.active.sum()), n) for p in plans)
    assert w_rows.shape == (h, k_mix, n)
    assert ctrl.shape == (h, k_mix + 2 * k_train)
    np.testing.assert_array_equal(ts, np.arange(1, h + 1))
    # padded mix rows are identity rows of W targeting idle-in-that-round
    # workers: scattering them back must be a value no-op
    for i, p in enumerate(plans):
        ids = ctrl[i, :k_mix]
        np.testing.assert_allclose(w_rows[i], p.W[ids], rtol=0)
        mask = ctrl[i, k_mix + k_train:]
        np.testing.assert_array_equal(
            np.asarray(p.active[ctrl[i, k_mix:k_mix + k_train]], np.int32)
            * mask, mask)


def test_mega_round_step_equals_sequential_round_steps():
    """One scan over H packed rounds == H donated round_step dispatches,
    bit-for-bit on the buffer (identical batch keys via fold_in(key, t))."""
    rng = np.random.default_rng(1)
    n, dim, hidden, ncls = 14, 8, 12, 3
    h, steps, batch = 5, 2, 4
    stacked = WK.init_stacked(jax.random.PRNGKey(2), n, dim, hidden, ncls,
                              same_init=False)
    buf, spec = FS.flatten_stacked(stacked)
    data_x = jnp.asarray(rng.normal(size=(300, dim)), jnp.float32)
    data_y = jnp.asarray(rng.integers(0, ncls, 300), jnp.int32)
    part_idx = jnp.asarray(rng.integers(0, 300, (n, 30)), np.int32)
    part_sizes = jnp.full((n,), 30, jnp.int32)
    key = jax.random.PRNGKey(7)
    plans = _fake_plans(rng, n, h)
    kw = dict(spec=spec, lr=0.05, local_steps=steps, batch_size=batch)

    ref = jnp.array(buf)
    ref_losses = []
    for p in plans:
        w_rows, mix_ids = mixing_rows(p.W, p.active, p.links)
        train_ids, train_mask = padded_rows(p.active)
        ctrl1 = WK.pack_round_ctrl(mix_ids, train_ids, train_mask)
        ref, l = WK.round_step(ref, jnp.asarray(w_rows), jnp.asarray(ctrl1),
                               data_x, data_y, part_idx, part_sizes, key,
                               np.int32(p.t), **kw)
        ref_losses.append(np.asarray(l))

    w, c, ts = WK.pack_horizon(plans)
    out, losses = WK.mega_round_step(jnp.array(buf), jnp.asarray(w),
                                     jnp.asarray(c), jnp.asarray(ts),
                                     data_x, data_y, part_idx, part_sizes,
                                     key, **kw)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert losses.shape == (h, n)
    np.testing.assert_allclose(np.asarray(losses), np.stack(ref_losses),
                               rtol=1e-6, atol=1e-7)


# --------------------------------------------------------------------------- #
# run_simulation: scan-vs-single-round trajectory equality
# --------------------------------------------------------------------------- #


def _cfg(**kw):
    base = dict(n_workers=16, n_rounds=60, phi=0.5, lr=0.1, eval_every=20,
                seed=0, hidden=48, n_samples=6000)
    base.update(kw)
    return SimConfig(**base)


_CONTROL_FIELDS = ("rounds", "sim_time", "comm_gb", "staleness_avg",
                   "staleness_max", "round_durations", "round_active")
_MODEL_FIELDS = ("acc_global", "acc_local", "loss_global")


@pytest.mark.parametrize("horizon", [2, 8, 64])
def test_scan_horizon_history_invariance(horizon):
    """Any scan_horizon reproduces the scan_horizon=1 (PR 1 round_step)
    trajectory EXACTLY — control plane and learning curves bit-for-bit
    (eval points are horizon boundaries; eval_every=20 with horizon=8 also
    exercises ragged 8/8/4 chunking)."""
    mech = lambda: DySTop(V=10.0, t_thre=20, max_neighbors=5)
    h1 = run_simulation(mech(), _cfg(scan_horizon=1))
    hH = run_simulation(mech(), _cfg(scan_horizon=horizon))
    for f in _CONTROL_FIELDS + _MODEL_FIELDS:
        assert getattr(h1, f) == getattr(hH, f), f


def test_scan_horizon_invariance_under_sim_time_grid():
    """Time-grid eval mode: horizon boundaries must land on the same grid
    crossings the per-round loop evaluates at."""
    mech = lambda: DySTop(V=10.0, t_thre=10, max_neighbors=5)
    kw = dict(n_rounds=40, max_sim_time=40.0, eval_every=10)
    h1 = run_simulation(mech(), _cfg(scan_horizon=1, **kw))
    h8 = run_simulation(mech(), _cfg(scan_horizon=8, **kw))
    for f in _CONTROL_FIELDS + _MODEL_FIELDS:
        assert getattr(h1, f) == getattr(h8, f), f


def test_scan_horizon_invariance_under_failures():
    mech = lambda: DySTop(V=10.0, t_thre=10, max_neighbors=5)
    kw = dict(n_rounds=30, eval_every=10, failure_prob=0.15)
    h1 = run_simulation(mech(), _cfg(scan_horizon=1, **kw))
    h8 = run_simulation(mech(), _cfg(scan_horizon=8, **kw))
    for f in _CONTROL_FIELDS + _MODEL_FIELDS:
        assert getattr(h1, f) == getattr(h8, f), f


def test_scan_horizon_one_dispatches_round_step_only(monkeypatch):
    """scan_horizon=1 IS the PR 1 engine: mega_round_step must never run."""
    def boom(*a, **k):  # pragma: no cover
        raise AssertionError("mega_round_step called with scan_horizon=1")

    monkeypatch.setattr(WK, "mega_round_step", boom)
    h = run_simulation(DySTop(V=10.0, t_thre=5),
                       _cfg(n_rounds=12, eval_every=6, scan_horizon=1))
    assert len(h.acc_global) == 2


def test_scan_horizon_mega_actually_used(monkeypatch):
    calls = []
    real = WK.mega_round_step

    def spy(*a, **k):
        calls.append(a[3].shape[0])       # ts length = chunk size
        return real(*a, **k)

    monkeypatch.setattr(WK, "mega_round_step", spy)
    run_simulation(DySTop(V=10.0, t_thre=5),
                   _cfg(n_rounds=12, eval_every=6, scan_horizon=6))
    assert calls and all(c >= 2 for c in calls)


def test_bound_log_identical_across_horizons():
    mech = lambda: DySTop(V=10.0, t_thre=10, max_neighbors=5)
    h1 = run_simulation(mech(), _cfg(n_rounds=20, scan_horizon=1),
                        record_history_for_bound=True)
    h8 = run_simulation(mech(), _cfg(n_rounds=20, scan_horizon=8),
                        record_history_for_bound=True)
    for a, b in zip(h1.bound_log["active"], h8.bound_log["active"]):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(h1.bound_log["W"], h8.bound_log["W"]):
        np.testing.assert_array_equal(a, b)
