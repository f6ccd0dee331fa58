"""Column-sparse mixing + fused local-steps SGD: oracle equivalence.

The engine's two selected lowerings — the column-sparse Eq. 4 contraction
(picked per chunk by ``aggregation.prefer_cols``) and the fused SGD lowering
(``worker.fused_sgd_supported``) — are pinned against three oracles:

  1. kernel — ``aggregate_rows_cols`` (Pallas, interpret on CPU) vs the
     dense ``W @ X`` product and the row-sparse ``aggregate_rows`` path,
     across bucket sizes INCLUDING the u = N degenerate union and k = 0
     empty rounds;
  2. lowering — ``local_sgd_flat_fused`` (unrolled manual backward) vs the
     per-step AD scan ``local_sgd_flat`` on identical batches;
  3. trajectory — ``run_simulation`` vs the per-round pytree reference
     (tests/engine_reference.py) on the same batches: control-plane
     histories EXACTLY equal, learning curves to f32-rounding tolerance,
     including runs whose chunks take both contractions.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aggregation import (bucket_size, col_union_mask,
                                    mixing_matrix, mixing_rows,
                                    mixing_rows_cols, padded_rows,
                                    plan_buckets, plan_buckets_cols)
from repro.core.protocol import DySTop
from repro.dfl import flat_state as FS
from repro.dfl import worker as WK
from repro.dfl.simulator import SimConfig, run_simulation
from repro.kernels import ops as K
from repro.kernels.config import KernelConfig
from repro.kernels.ref import aggregate_rows_cols_ref

from engine_reference import run_sim_with_reference


def _random_round(rng, n, act_frac, link_p=0.15):
    active = rng.random(n) < act_frac
    links = (rng.random((n, n)) < link_p) & active[:, None]
    np.fill_diagonal(links, False)
    W = mixing_matrix(active, links, rng.uniform(1, 10, n))
    return active, links, W


# --------------------------------------------------------------------------- #
# column union planning
# --------------------------------------------------------------------------- #


def test_col_union_mask_covers_exactly_the_nonzero_columns():
    rng = np.random.default_rng(0)
    n = 30
    for _ in range(8):
        active, links, W = _random_round(rng, n, rng.uniform(0.05, 0.9))
        mix_mask = active | links.any(axis=1)
        cols = col_union_mask(active, links)
        # every nonzero column of a non-identity row is in the union
        nz = (W[mix_mask] != 0).any(axis=0) if mix_mask.any() else \
            np.zeros(n, bool)
        assert not (nz & ~cols).any()
        # the union never exceeds nonzeros + the one row-padding identity col
        assert cols.sum() <= nz.sum() + 1


def test_col_union_empty_round_is_empty():
    n = 12
    none = np.zeros(n, bool)
    assert col_union_mask(none, np.zeros((n, n), bool)).sum() == 0
    assert plan_buckets_cols(none, np.zeros((n, n), bool)) == (0, 0, 0)


def test_plan_buckets_cols_extends_plan_buckets():
    rng = np.random.default_rng(1)
    n = 40
    for _ in range(6):
        active, links, _ = _random_round(rng, n, rng.uniform(0.05, 0.8))
        triple = plan_buckets_cols(active, links)
        assert triple[:2] == plan_buckets(active, links)
        assert triple[2] == bucket_size(
            int(col_union_mask(active, links).sum()), n)


# --------------------------------------------------------------------------- #
# aggregate_rows_cols vs dense / row-sparse oracles
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_col_sparse_matches_dense_random_masks(seed, backend):
    """Sweeps activation density so u hits several buckets incl. u = N."""
    rng = np.random.default_rng(seed)
    n, p = 32, 140
    active, links, W = _random_round(rng, n, rng.uniform(0.05, 0.9))
    X = jnp.asarray(rng.normal(size=(n, p)).astype(np.float32))

    w_sub, row_ids, col_ids = mixing_rows_cols(W, active, links)
    out = WK.mix_flat_cols(X, jnp.asarray(w_sub), jnp.asarray(row_ids),
                           jnp.asarray(col_ids),
                           kernels=KernelConfig(backend=backend))
    np.testing.assert_allclose(out, jnp.asarray(W) @ X, rtol=1e-5, atol=1e-5)
    # rows outside the mix set are never touched by the scatter
    idle = ~(active | links.any(axis=1))
    np.testing.assert_array_equal(np.asarray(out)[idle], np.asarray(X)[idle])
    # ... and the row-sparse path agrees with the column-sparse one
    w_rows, row_ids2 = mixing_rows(W, active, links)
    out_rows = WK.mix_flat(X, jnp.asarray(w_rows), jnp.asarray(row_ids2))
    np.testing.assert_allclose(out, out_rows, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("min_bucket", [2, 8, 32])
def test_col_sparse_across_bucket_sizes(min_bucket):
    rng = np.random.default_rng(7)
    n, p = 24, 90
    active, links, W = _random_round(rng, n, 0.3)
    X = jnp.asarray(rng.normal(size=(n, p)).astype(np.float32))
    w_sub, row_ids, col_ids = mixing_rows_cols(W, active, links,
                                               min_bucket=min_bucket)
    assert w_sub.shape == (len(row_ids), len(col_ids))
    out = WK.mix_flat_cols(X, jnp.asarray(w_sub), jnp.asarray(row_ids),
                           jnp.asarray(col_ids))
    np.testing.assert_allclose(out, jnp.asarray(W) @ X, rtol=1e-5, atol=1e-5)


def test_col_sparse_degenerate_u_equals_n():
    """Full links ⇒ the union is all N columns: col_ids must be arange(N)
    and the contraction must equal the dense product."""
    rng = np.random.default_rng(2)
    n, p = 9, 33
    active = np.ones(n, bool)
    links = ~np.eye(n, dtype=bool)
    W = mixing_matrix(active, links, np.ones(n))
    X = jnp.asarray(rng.normal(size=(n, p)).astype(np.float32))
    w_sub, row_ids, col_ids = mixing_rows_cols(W, active, links)
    assert w_sub.shape == (n, n)
    np.testing.assert_array_equal(col_ids, np.arange(n))
    np.testing.assert_allclose(
        WK.mix_flat_cols(X, jnp.asarray(w_sub), jnp.asarray(row_ids),
                         jnp.asarray(col_ids)),
        jnp.asarray(W) @ X, rtol=1e-5, atol=1e-5)


def test_col_sparse_empty_round_k0():
    """No activations and no links ⇒ k = 0, u = 0, mixing is a no-op."""
    n, p = 9, 33
    X = jnp.asarray(np.random.default_rng(0).normal(size=(n, p)), jnp.float32)
    none = np.zeros(n, bool)
    W = mixing_matrix(none, np.zeros((n, n), bool), np.ones(n))
    w_sub, row_ids, col_ids = mixing_rows_cols(W, none, np.zeros((n, n), bool))
    assert w_sub.shape == (0, 0) and len(col_ids) == 0
    np.testing.assert_array_equal(
        WK.mix_flat_cols(X, jnp.asarray(w_sub), jnp.asarray(row_ids),
                         jnp.asarray(col_ids)), X)


def test_col_padding_columns_contribute_zero():
    """Padded col_ids repeat index 0 — the zeroed W_sub columns must keep the
    contraction exact even though X[0] is gathered twice."""
    rng = np.random.default_rng(3)
    n, p = 64, 50
    active, links, W = _random_round(rng, n, 0.08, link_p=0.05)
    if not (active | links.any(axis=1)).any():
        pytest.skip("empty draw")
    w_sub, row_ids, col_ids = mixing_rows_cols(W, active, links)
    u_true = int(col_union_mask(active, links).sum())
    assert len(col_ids) < n, "draw unexpectedly degenerate (u = N)"
    if len(col_ids) > u_true:                       # padding happened
        assert (w_sub[:, u_true:] == 0).all()
    X = jnp.asarray(rng.normal(size=(n, p)).astype(np.float32))
    np.testing.assert_allclose(
        WK.mix_flat_cols(X, jnp.asarray(w_sub), jnp.asarray(row_ids),
                         jnp.asarray(col_ids)),
        jnp.asarray(W) @ X, rtol=1e-5, atol=1e-5)


def test_aggregate_rows_cols_kernel_matches_ref():
    rng = np.random.default_rng(4)
    Ws = jnp.asarray(rng.normal(size=(6, 12)), jnp.float32)
    cid = jnp.asarray(rng.permutation(20)[:12], jnp.int32)
    X = jnp.asarray(rng.normal(size=(20, 513)), jnp.float32)
    np.testing.assert_allclose(K.aggregate_rows_cols(Ws, cid, X),
                               aggregate_rows_cols_ref(Ws, cid, X),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(aggregate_rows_cols_ref(Ws, cid, X),
                               Ws @ X[cid], rtol=1e-6, atol=1e-6)


# --------------------------------------------------------------------------- #
# fused local-steps SGD vs the per-step AD oracle
# --------------------------------------------------------------------------- #


def _sgd_inputs(n=8, dim=12, hidden=16, ncls=4, steps=3, batch=6, seed=0):
    stacked = WK.init_stacked(jax.random.PRNGKey(seed), n, dim, hidden, ncls,
                              same_init=False)
    buf, spec = FS.flatten_stacked(stacked)
    kx, ky = jax.random.split(jax.random.PRNGKey(seed + 1))
    xb = jax.random.normal(kx, (n, steps, batch, dim), jnp.float32)
    yb = jax.random.randint(ky, (n, steps, batch), 0, ncls)
    active = jnp.asarray(np.arange(n) % 3 != 1, jnp.float32)
    return buf, spec, xb, yb, active


def test_fused_sgd_matches_ad_oracle():
    buf, spec, xb, yb, active = _sgd_inputs()
    ref, ref_loss = WK.local_sgd_flat(buf, xb, yb, active, spec, lr=0.05)
    out, out_loss = WK.local_sgd_flat_fused(buf, xb, yb, active, spec,
                                            lr=0.05)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(out_loss, ref_loss, rtol=1e-5, atol=1e-6)
    # masked rows stay bit-identical to their input
    inactive = ~np.asarray(active, bool)
    np.testing.assert_array_equal(np.asarray(out)[inactive],
                                  np.asarray(buf)[inactive])


def test_fused_sgd_single_step():
    buf, spec, xb, yb, active = _sgd_inputs(steps=1)
    ref, _ = WK.local_sgd_flat(buf, xb, yb, active, spec, lr=0.1)
    out, _ = WK.local_sgd_flat_fused(buf, xb, yb, active, spec, lr=0.1)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)


def test_fused_sgd_supported_guard():
    stacked = WK.init_stacked(jax.random.PRNGKey(0), 4, 8, 6, 3)
    _, spec = FS.flatten_stacked(stacked)
    assert WK.fused_sgd_supported(spec)
    # a non-MLP pytree must fall back to the AD path
    other = {"w": jnp.zeros((4, 5, 5)), "b": jnp.zeros((4, 5))}
    _, spec2 = FS.flatten_stacked(other)
    assert not WK.fused_sgd_supported(spec2)


# --------------------------------------------------------------------------- #
# round_step / mega_round_step with the new flags vs the oracle paths
# --------------------------------------------------------------------------- #


def _round_env(rng, n=12, dim=8, hidden=12, ncls=3):
    stacked = WK.init_stacked(jax.random.PRNGKey(2), n, dim, hidden, ncls)
    buf, spec = FS.flatten_stacked(stacked)
    data_x = jnp.asarray(rng.normal(size=(200, dim)), jnp.float32)
    data_y = jnp.asarray(rng.integers(0, ncls, 200), jnp.int32)
    part_idx = jnp.asarray(rng.integers(0, 200, (n, 20)), jnp.int32)
    part_sizes = jnp.full((n,), 20, jnp.int32)
    return buf, spec, data_x, data_y, part_idx, part_sizes


def test_round_step_col_sparse_fused_matches_oracle_flags():
    """Same inputs + same batch key: the flagged paths may only differ from
    the PR 2 oracle dispatch by f32 rounding."""
    rng = np.random.default_rng(0)
    n = 12
    buf, spec, data_x, data_y, part_idx, part_sizes = _round_env(rng, n)
    active, links, W = _random_round(rng, n, 0.5, link_p=0.2)
    key = jax.random.PRNGKey(9)
    kw = dict(spec=spec, lr=0.05, local_steps=2, batch_size=4)
    train_ids, train_mask = padded_rows(active)

    w_rows, mix_ids = mixing_rows(W, active, links)
    ctrl = WK.pack_round_ctrl(mix_ids, train_ids, train_mask)
    ref, ref_l = WK.round_step(jnp.array(buf), jnp.asarray(w_rows),
                               jnp.asarray(ctrl), data_x, data_y, part_idx,
                               part_sizes, key, np.int32(7), **kw)

    w_sub, mix_ids2, col_ids = mixing_rows_cols(W, active, links)
    ctrl2 = WK.pack_round_ctrl(mix_ids2, train_ids, train_mask,
                               col_ids=col_ids)
    out, out_l = WK.round_step(jnp.array(buf), jnp.asarray(w_sub),
                               jnp.asarray(ctrl2), data_x, data_y, part_idx,
                               part_sizes, key, np.int32(7), col_sparse=True,
                               fused_sgd=True, **kw)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(out_l, ref_l, rtol=1e-4, atol=1e-6)


def test_mega_round_step_col_sparse_matches_sequential():
    """pack_horizon(col_sparse=True) scan == per-round col-sparse round_step
    dispatches, bit-for-bit, when the rounds share one bucket triple (the
    only way the simulator ever packs a chunk)."""
    rng = np.random.default_rng(1)
    n, h = 14, 4
    buf, spec, data_x, data_y, part_idx, part_sizes = _round_env(rng, n)
    key = jax.random.PRNGKey(7)
    kw = dict(spec=spec, lr=0.05, local_steps=2, batch_size=4)

    plans = []
    t = 0
    while len(plans) < h:                      # uniform-bucket steady chunk
        t += 1
        active, links, W = _random_round(rng, n, 0.4, link_p=0.2)
        if plans and (plan_buckets_cols(active, links)
                      != plan_buckets_cols(plans[0].active, plans[0].links)):
            continue
        plans.append(type("P", (), dict(t=t, active=active, links=links,
                                        W=W, mix_cols=None))())
    w, c, ts = WK.pack_horizon(plans, col_sparse=True)

    ref = jnp.array(buf)
    for p in plans:
        w_sub, mix_ids, col_ids = mixing_rows_cols(p.W, p.active, p.links)
        train_ids, train_mask = padded_rows(p.active)
        ctrl1 = WK.pack_round_ctrl(mix_ids, train_ids, train_mask,
                                   col_ids=col_ids)
        ref, _ = WK.round_step(ref, jnp.asarray(w_sub), jnp.asarray(ctrl1),
                               data_x, data_y, part_idx, part_sizes, key,
                               np.int32(p.t), col_sparse=True, fused_sgd=True,
                               **kw)
    out, losses = WK.mega_round_step(jnp.array(buf), jnp.asarray(w),
                                     jnp.asarray(c), jnp.asarray(ts),
                                     data_x, data_y, part_idx, part_sizes,
                                     key, col_sparse=True, fused_sgd=True,
                                     **kw)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert losses.shape == (h, n)


# --------------------------------------------------------------------------- #
# end-to-end: the engine vs the plain pytree reference
# --------------------------------------------------------------------------- #


def _cfg(**kw):
    base = dict(n_workers=16, n_rounds=40, phi=0.5, lr=0.1, eval_every=10,
                seed=0, hidden=48, n_samples=6000)
    base.update(kw)
    return SimConfig(**base)


def test_new_engine_history_matches_pr2_oracle():
    mech = lambda: DySTop(V=10.0, t_thre=10, max_neighbors=5)
    h_new, h_old = run_sim_with_reference(mech(), _cfg())
    # bit-for-bit identical control plane (same planned rounds)
    assert h_new.rounds == h_old.rounds
    np.testing.assert_allclose(h_new.sim_time, h_old.sim_time, rtol=0)
    np.testing.assert_allclose(h_new.comm_gb, h_old.comm_gb, rtol=0)
    assert h_new.staleness_avg == h_old.staleness_avg
    assert h_new.staleness_max == h_old.staleness_max
    assert h_new.round_active == h_old.round_active
    assert h_new.round_durations == h_old.round_durations
    # learning curves agree to f32-rounding tolerance (identical batch keys)
    np.testing.assert_allclose(h_new.acc_global, h_old.acc_global, atol=0.03)
    np.testing.assert_allclose(h_new.loss_global, h_old.loss_global,
                               rtol=0.05, atol=0.02)


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_engine_row_and_col_chunks_match_reference(backend, monkeypatch):
    """A run whose chunks take BOTH Eq. 4 contractions (``prefer_cols``
    picks rows for wide unions, columns for narrow ones) equals the dense
    pytree reference: control exact, curves to f32 rounding."""
    picked = []
    for name in ("round_step", "mega_round_step"):
        orig = getattr(WK, name)

        def spy(*a, _orig=orig, **kw):
            picked.append(kw["col_sparse"])
            return _orig(*a, **kw)

        monkeypatch.setattr(WK, name, spy)
    kernels = KernelConfig(backend=backend)
    h, ref = run_sim_with_reference(
        DySTop(V=10.0, t_thre=8, max_neighbors=2),
        _cfg(n_rounds=24, eval_every=6, hidden=16, n_samples=1200, dim=8,
             min_bucket=2, kernels=kernels))
    assert set(picked) == {False, True}, picked
    for f in ("rounds", "sim_time", "comm_gb", "staleness_avg",
              "staleness_max", "round_durations", "round_active"):
        assert getattr(h, f) == getattr(ref, f), f
    for f in ("acc_global", "acc_local", "loss_global"):
        np.testing.assert_allclose(getattr(h, f), getattr(ref, f),
                                   rtol=1e-6, atol=1e-7, err_msg=f)


def test_new_engine_reproducible_and_horizon_invariant():
    mech = lambda: DySTop(V=10.0, t_thre=10, max_neighbors=5)
    h8 = run_simulation(mech(), _cfg(scan_horizon=8))
    h1 = run_simulation(mech(), _cfg(scan_horizon=1))
    h8b = run_simulation(mech(), _cfg(scan_horizon=8))
    assert h8.acc_global == h8b.acc_global            # reproducible
    assert h8.acc_global == h1.acc_global             # horizon-invariant
    assert h8.sim_time == h1.sim_time
