"""DFL over real zoo architectures (dfl/lm_worker.py).

Oracle ladder for the resident LM plane:
  * the per-call-flatten reference (``tests/engine_reference.py``: stacked
    pytrees, ``fleet_mix_stacked`` + the masked train-all-N step) replaying
    the engine's plans: control plane bit-for-bit, params + optimizer state
    to f32 tolerance, for EVERY optimizer family;
  * the planner-driven driver's control trajectory == an independently
    hand-rolled ``Mechanism.round`` loop, exactly;
  * ``worker_streams``'s stride-tricks gather == the scalar slicing loop,
    token-for-token (the rng draw order is the trajectory).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aggregation import apply_mixing, mixing_matrix
from repro.core.planner import PlannedRound, bucket_key
from repro.core.protocol import DySTop, RoundContext
from repro.core.staleness import StalenessState
from repro.core.trace import Trace
from repro.data.synthetic import make_token_stream
from repro.dfl import flat_state as FS
from repro.dfl import lm_worker as LW
from repro.dfl.network import (EdgeNetwork, NetworkConfig,
                               heterogeneous_compute_times)
from repro.kernels.config import KernelConfig
from repro.models import registry as R

import engine_reference as ER


def test_fleet_masked_step_moves_only_active():
    cfg = R.get_smoke_config("smollm-135m")
    n = 4
    fleet = LW.init_fleet(cfg, n, lr=1e-3)
    streams = LW.worker_streams(cfg, n, batch=2, seq=32)
    step = ER.make_fleet_step(fleet)
    batch = {k: jnp.asarray(v) for k, v in next(streams).items()}
    active = jnp.asarray([True, False, True, False])
    p0 = fleet.stacked_params
    p1, o1, losses = step(p0, fleet.stacked_opt, batch, active)
    deltas = []
    for w in range(n):
        d = sum(float(jnp.abs(a[w].astype(jnp.float32) -
                              b[w].astype(jnp.float32)).sum())
                for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p1)))
        deltas.append(d)
    assert deltas[0] > 0 and deltas[2] > 0
    assert deltas[1] == 0 and deltas[3] == 0
    assert np.all(np.isfinite(np.asarray(losses)))


def test_fleet_learns_and_aggregates():
    cfg = R.get_smoke_config("smollm-135m")
    n = 3
    fleet = LW.init_fleet(cfg, n, lr=3e-3)
    streams = LW.worker_streams(cfg, n, batch=2, seq=32)
    step = ER.make_fleet_step(fleet)
    alpha = jnp.full((n,), 1.0 / n)
    eval_batch = {k: jnp.asarray(v[0]) for k, v in next(streams).items()}
    first = ER.fleet_eval(fleet, eval_batch, alpha)
    mean_losses = []
    for t in range(8):
        batch = {k: jnp.asarray(v) for k, v in next(streams).items()}
        # round-robin single activation + full pull (simple DFL round)
        active = np.zeros(n, bool)
        active[t % n] = True
        links = np.zeros((n, n), bool)
        links[t % n] = ~active
        W = mixing_matrix(active, links, np.ones(n))
        fleet.stacked_params = apply_mixing(jnp.asarray(W),
                                            fleet.stacked_params)
        fleet.stacked_params, fleet.stacked_opt, losses = step(
            fleet.stacked_params, fleet.stacked_opt, batch, jnp.asarray(active))
        mean_losses.append(float(jnp.mean(losses)))
    # fixed held-out batch: the global weighted model improves
    assert ER.fleet_eval(fleet, eval_batch, alpha) < first
    # and local training losses trend down across the federation
    assert np.mean(mean_losses[-3:]) < np.mean(mean_losses[:3]) - 0.3


def test_worker_streams_noniid_slices():
    cfg = R.get_smoke_config("gemma2-2b")
    b = next(LW.worker_streams(cfg, 4, batch=2, seq=16))
    assert b["tokens"].shape == (4, 2, 16)
    assert b["labels"].shape == (4, 2, 16)
    # labels are next-token shifts of tokens within each sample
    assert (b["tokens"][0, 0, 1:] == b["labels"][0, 0, :-1]).all()


def test_worker_streams_gather_matches_scalar_loop():
    """The stride-tricks gather reproduces the scalar per-batch slicing loop
    token-for-token across yields — same rng calls, same windows."""
    cfg = R.get_smoke_config("smollm-135m")
    n_workers, batch, seq, seed = 3, 4, 24, 5
    stream = make_token_stream(cfg.vocab_size, 400_000, seed=seed)
    n = len(stream) - seq - 1
    rng = np.random.default_rng(seed)
    slice_len = n // n_workers
    gen = LW.worker_streams(cfg, n_workers, batch, seq, seed=seed)
    for _ in range(3):
        tok = np.empty((n_workers, batch, seq), np.int32)
        lab = np.empty((n_workers, batch, seq), np.int32)
        for w in range(n_workers):
            lo = w * slice_len % max(n - slice_len, 1)
            starts = rng.integers(lo, lo + max(slice_len - seq - 1, 1),
                                  size=batch)
            for b, s in enumerate(starts):
                tok[w, b] = stream[s:s + seq]
                lab[w, b] = stream[s + 1:s + seq + 1]
        got = next(gen)
        np.testing.assert_array_equal(got["tokens"], tok)
        np.testing.assert_array_equal(got["labels"], lab)


# --------------------------------------------------------------------------- #
# resident fleet: FleetSpec round-trips + planner-driven engine oracles
# --------------------------------------------------------------------------- #


def test_fleet_spec_roundtrip_exact():
    """pbuf/obuf <-> stacked pytree round-trips are exact: bf16 params and
    int32 step counters survive the f32 buffers bit-for-bit."""
    cfg = R.get_smoke_config("smollm-135m")
    fleet = LW.init_fleet(cfg, 3, optimizer="adam")
    p0, o0 = np.asarray(fleet.pbuf), np.asarray(fleet.obuf)
    sp, so = fleet.stacked_params, fleet.stacked_opt
    # dtypes materialize as the originals
    assert {str(l.dtype) for l in jax.tree.leaves(sp)} >= {"bfloat16"}
    assert any(str(l.dtype) == "int32" for l in jax.tree.leaves(so))
    fleet.stacked_params = sp           # re-flatten through the setter
    fleet.stacked_opt = so
    np.testing.assert_array_equal(np.asarray(fleet.pbuf), p0)
    np.testing.assert_array_equal(np.asarray(fleet.obuf), o0)
    assert fleet.model_bytes == sum(
        l.size * l.dtype.itemsize for l in jax.tree.leaves(
            jax.tree.map(lambda l: l[0], sp)))


def _mech():
    return DySTop(V=3.0, t_thre=3, max_neighbors=3)


def _run_kw(**kw):
    base = dict(n_workers=4, n_rounds=6, batch=2, seq=16, eval_every=3,
                seed=1)
    base.update(kw)
    return base


_CONTROL_FIELDS = ("rounds", "sim_time", "comm_gb", "staleness_avg",
                   "staleness_max", "round_durations", "round_active")


@pytest.mark.parametrize("optimizer", ["adam", "sgd", "adafactor"])
def test_resident_matches_reflatten_oracle(optimizer):
    """The persistent-flat engine == the per-call-flatten reference on the
    same plans: control plane bit-for-bit, params AND optimizer state to f32
    tolerance — for every optimizer family (full moments, momentum-only,
    factored)."""
    cfg = R.get_smoke_config("smollm-135m")
    f_res, h_res, ref = ER.run_lm_with_reference(
        _mech(), cfg, LW.LMRunConfig(**_run_kw(optimizer=optimizer)))
    for f in _CONTROL_FIELDS:
        assert getattr(h_res, f) == getattr(ref.hist, f), f
    np.testing.assert_allclose(np.asarray(f_res.pbuf), ref.pbuf,
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(f_res.obuf), ref.obuf,
                               rtol=1e-4, atol=1e-5)
    # and the learning curves agree to eval tolerance
    np.testing.assert_allclose(h_res.loss_global, ref.hist.loss_global,
                               rtol=1e-3)


def test_lm_row_and_col_chunks_match_reference(monkeypatch):
    """A run whose chunks take BOTH Eq. 4 contractions (``prefer_cols``
    on the bucketed (k, u) shapes) equals the per-call-flatten reference.
    f32 params: the two contractions sum in different orders, which bf16
    storage would round to whole-ulp differences."""
    picked = []
    orig = LW.LMEngine.dispatch_chunk

    def spy(self, *a, **kw):
        picked.append(kw["col_sparse"])
        return orig(self, *a, **kw)

    monkeypatch.setattr(LW.LMEngine, "dispatch_chunk", spy)
    cfg = dataclasses.replace(R.get_smoke_config("smollm-135m"),
                              dtype="float32")
    f, h, ref = ER.run_lm_with_reference(
        DySTop(V=3.0, t_thre=3, max_neighbors=2, max_workers=2), cfg,
        LW.LMRunConfig(**_run_kw(n_workers=16, n_rounds=8, batch=1, seq=8,
                                 eval_every=4)))
    assert set(picked) == {False, True}, picked
    for fld in _CONTROL_FIELDS:
        assert getattr(h, fld) == getattr(ref.hist, fld), fld
    np.testing.assert_allclose(np.asarray(f.pbuf), ref.pbuf,
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(f.obuf), ref.obuf,
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(h.round_loss, ref.hist.round_loss, rtol=1e-5)


def test_lm_idle_rounds_match_reference():
    """Rounds with no activated worker (failures) dispatch chunks with
    k_train 0, whose host-gathered batch is empty: the run still equals
    the per-call-flatten reference."""
    cfg = R.get_smoke_config("smollm-135m")
    f, h, ref = ER.run_lm_with_reference(
        _mech(), cfg, LW.LMRunConfig(**_run_kw(n_rounds=8, eval_every=4,
                                               failure_prob=0.9)))
    assert 0 in h.round_active
    for fld in _CONTROL_FIELDS:
        assert getattr(h, fld) == getattr(ref.hist, fld), fld
    np.testing.assert_allclose(np.asarray(f.pbuf), ref.pbuf,
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(f.obuf), ref.obuf,
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(h.round_loss, ref.hist.round_loss, rtol=1e-6)


def test_lm_scan_horizon_invariance():
    """Any scan_horizon yields the same resident trajectory (chunks only
    change how many rounds ride in one dispatch)."""
    cfg = R.get_smoke_config("smollm-135m")
    f1, h1 = LW.run_lm_federation(
        _mech(), cfg, LW.LMRunConfig(scan_horizon=1, **_run_kw()))
    f8, h8 = LW.run_lm_federation(
        _mech(), cfg, LW.LMRunConfig(scan_horizon=8, **_run_kw()))
    for f in _CONTROL_FIELDS:
        assert getattr(h1, f) == getattr(h8, f), f
    np.testing.assert_allclose(np.asarray(f1.pbuf), np.asarray(f8.pbuf),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(f1.obuf), np.asarray(f8.obuf),
                               rtol=1e-5, atol=1e-6)


def _one_active_chunk(n):
    """Three hand-built DySTop-style rounds on ``n`` workers, one activated
    worker each (2, 3, 2) pulling half its model from one neighbour.  Rows
    0 and 1 are never activated; with ``min_bucket`` 2 the one padding row
    of every round is row 0 (the first idle row)."""
    rounds = []
    for t, (a, nb) in enumerate([(2, 3), (3, 1), (2, 1)], start=1):
        active = np.zeros(n, bool)
        active[a] = True
        links = np.zeros((n, n), bool)
        links[a, nb] = True
        W = np.eye(n, dtype=np.float32)
        W[a, a] = W[a, nb] = 0.5
        rounds.append(PlannedRound(t=t, active=active, links=links,
                                   synchronous=False, W=W, duration=1.0,
                                   n_transfers=1))
    return rounds


@pytest.mark.parametrize("fuse", [True, False])
def test_padding_rows_skip_train_step(fuse):
    """Bucket padding skips the train step: a chunk dispatched at
    ``min_bucket`` 2 (one padding row a round) leaves the padding row's
    params, optimizer state and loss slots exactly as they went in, and its
    activated rows equal the same chunk dispatched without padding."""
    cfg = R.get_smoke_config("smollm-135m")
    n, b, s = 4, 2, 16
    fleet = LW.init_fleet(cfg, n, lr=1e-3)
    rng = np.random.default_rng(0)
    # distinct rows, so the Eq. 4 mix moves the activated ones
    p0 = np.asarray(fleet.pbuf) + rng.normal(
        0.0, 1e-2, (n, fleet.pbuf.shape[1])).astype(np.float32)
    o0 = np.asarray(fleet.obuf).copy()
    chunk = _one_active_chunk(n)
    tokens = rng.integers(0, cfg.vocab_size, (len(chunk), n, b, s),
                          dtype=np.int32)
    labels = rng.integers(0, cfg.vocab_size, (len(chunk), n, b, s),
                          dtype=np.int32)
    engine = LW.get_lm_engine(cfg, fleet.optimizer, fleet.spec,
                              KernelConfig(), None)

    def dispatch(min_bucket):
        key = bucket_key(chunk[0], n, min_bucket=min_bucket)
        out = engine.dispatch_chunk(
            jnp.asarray(p0), jnp.asarray(o0), chunk, tokens, labels,
            key=key, col_sparse=False, fuse=fuse, min_bucket=min_bucket,
            trace=Trace(LW.LMHistory()))
        return [np.asarray(x) for x in out]

    pb, ob, losses = dispatch(2)
    np.testing.assert_array_equal(pb[0], p0[0])       # the padding row
    np.testing.assert_array_equal(ob[0], o0[0])
    np.testing.assert_array_equal(losses[:, 0], 0.0)
    assert (losses[:, 2:] != 0).sum() == len(chunk)   # one loss a round
    assert not np.array_equal(pb[2], p0[2])           # the real rows trained

    pb1, ob1, losses1 = dispatch(1)                   # no padding at all
    np.testing.assert_array_equal(pb, pb1)
    np.testing.assert_array_equal(ob, ob1)
    np.testing.assert_array_equal(losses, losses1)


def test_planner_driven_control_matches_hand_rolled_loop():
    """The driver's control trajectory == an independently hand-rolled
    ``Mechanism.round`` loop (same rng consumption order: env construction,
    then per round mechanism draws + dense channel sampling), EXACTLY."""
    cfg = R.get_smoke_config("smollm-135m")
    n, rounds, seed = 4, 10, 0
    run = LW.LMRunConfig(n_workers=n, n_rounds=rounds, batch=2, seq=16,
                         eval_every=5, seed=seed)
    fleet, hist = LW.run_lm_federation(_mech(), cfg, run)

    # hand-rolled replay on a fresh, identically-seeded environment
    rng = np.random.default_rng(seed)
    net = EdgeNetwork(NetworkConfig(n_workers=n, comm_range_m=80.0), rng)
    h_i = heterogeneous_compute_times(n, 1.0, rng, sigma=0.6)
    model_bytes = float(fleet.model_bytes)
    in_range = net.in_range()
    exp_link = net.expected_link_time(model_bytes)
    mech = _mech()
    st = StalenessState.create(n, 4)
    pulls = np.zeros((n, n), np.float64)
    time_since = np.zeros(n, np.float64)
    clock = 0.0
    comm = 0.0
    durations, actives, sim_times = [], [], []
    for t in range(1, rounds + 1):
        h_cmp = np.maximum(h_i - time_since, 0.0)
        est = np.where(in_range, exp_link, 0.0).max(axis=1)
        ctx = RoundContext(
            t=t, round_cost=h_cmp + est, readiness=h_i - time_since,
            in_range=in_range, class_counts=np.ones((n, 2)),
            phys_dist=net.dist, pull_counts=pulls, staleness=st,
            bandwidth_budget=np.full(n, 6.0), data_sizes=np.ones(n), rng=rng)
        dec = mech.round(ctx)
        raw = model_bytes / net.link_rates()
        com = np.where(dec.links, np.minimum(raw, 5.0), 0.0).max(axis=1)
        dur = (float((h_cmp + com)[dec.active].max())
               if dec.active.any() else 0.0)
        clock += dur
        comm += int(dec.links.sum()) * model_bytes
        pulls += dec.links
        time_since += dur
        time_since[dec.active] = 0.0
        st.advance(dec.active)
        durations.append(dur)
        actives.append(int(dec.active.sum()))
        sim_times.append(clock)
    assert hist.round_durations == durations
    assert hist.round_active == actives
    assert hist.sim_time == [sim_times[4], sim_times[9]]
    assert hist.comm_gb[-1] == comm / 1e9
