"""Fused round engine: flat-buffer equivalence with the per-leaf pytree
reference.

Three layers of oracle:
  1. numerics — mix/train on the flat (N, P) buffer vs apply_mixing +
     local_train on the stacked pytree with IDENTICAL inputs (tight rtol);
  2. sparse aggregation — active-row gather/matmul/scatter vs the dense
     W @ X product over random masks (includes the Pallas kernel path);
  3. end-to-end — run_simulation vs the per-round pytree reference
     (tests/engine_reference.py) replaying its planned rounds: the
     control-plane trajectory (sim time, comm, staleness, activations) must
     match EXACTLY, accuracy to a loose tolerance (the reference here draws
     numpy batches, not the engine's device stream).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aggregation import (apply_mixing, mixing_matrix, mixing_rows,
                                    padded_rows)
from repro.core.protocol import DySTop
from repro.dfl import flat_state as FS
from repro.dfl import worker as WK
from repro.dfl.simulator import SimConfig, run_simulation
from repro.kernels import ops as K
from repro.kernels.config import KernelConfig

from engine_reference import run_sim_with_reference


def _random_tree(key, n=12):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w1": jax.random.normal(k1, (n, 7, 5), jnp.float32),
        "b1": jax.random.normal(k2, (n, 5), jnp.float32),
        "w2": jax.random.normal(k3, (n, 5, 3), jnp.float32),
    }


# --------------------------------------------------------------------------- #
# flat state
# --------------------------------------------------------------------------- #


def test_flat_roundtrip():
    tree = _random_tree(jax.random.PRNGKey(0))
    buf, spec = FS.flatten_stacked(tree)
    assert buf.shape == (12, 7 * 5 + 5 + 5 * 3)
    back = FS.unflatten(buf, spec)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b), tree, back)


def test_unravel_row_matches_leaf_slices():
    tree = _random_tree(jax.random.PRNGKey(1))
    buf, spec = FS.flatten_stacked(tree)
    row3 = FS.unravel_row(buf[3], spec)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b[3]),
                 row3, tree)
    np.testing.assert_array_equal(FS.ravel_row(row3, spec), buf[3])
    # the same leaves sliced from the buffer at a traced row index
    at3 = jax.jit(lambda b, r: FS.unravel_row_at(b, r, spec))(buf, 3)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b),
                 at3, row3)


@pytest.mark.parametrize("ids", [[3, 0, 11], [5, 5, 2, 5], [7]])
def test_take_rows_equals_gather(ids):
    """The row-slice loop returns exactly ``buf[ids]``, duplicates included."""
    tree = _random_tree(jax.random.PRNGKey(2))
    buf, _ = FS.flatten_stacked(tree)
    ids = jnp.asarray(ids, jnp.int32)
    np.testing.assert_array_equal(jax.jit(FS.take_rows)(buf, ids), buf[ids])


# --------------------------------------------------------------------------- #
# sparse aggregation vs dense
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("seed", range(5))
@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_sparse_matches_dense_random_masks(seed, backend):
    rng = np.random.default_rng(seed)
    n, p = 24, 140
    active = rng.random(n) < rng.uniform(0.1, 0.9)
    links = (rng.random((n, n)) < 0.15) & active[:, None]
    np.fill_diagonal(links, False)
    W = mixing_matrix(active, links, rng.uniform(1, 10, n))
    X = jnp.asarray(rng.normal(size=(n, p)).astype(np.float32))

    w_rows, row_ids = mixing_rows(W, active, links)
    out_sparse = WK.mix_flat(X, jnp.asarray(w_rows), jnp.asarray(row_ids),
                             kernels=KernelConfig(backend=backend))
    out_dense = jnp.asarray(W) @ X
    np.testing.assert_allclose(out_sparse, out_dense, rtol=1e-5, atol=1e-5)
    # identity rows must come back bit-stable (never touched by the scatter)
    idle = ~(active | links.any(axis=1))
    np.testing.assert_array_equal(np.asarray(out_sparse)[idle],
                                  np.asarray(X)[idle])


def test_sparse_edge_cases():
    n, p = 9, 33
    X = jnp.asarray(np.random.default_rng(0).normal(size=(n, p)), jnp.float32)
    d = np.ones(n)
    # no one active, no links -> k = 0, mixing is a no-op
    none = np.zeros(n, bool)
    W = mixing_matrix(none, np.zeros((n, n), bool), d)
    w_rows, row_ids = mixing_rows(W, none, np.zeros((n, n), bool))
    assert w_rows.shape == (0, n)
    np.testing.assert_array_equal(WK.mix_flat(X, jnp.asarray(w_rows),
                                              jnp.asarray(row_ids)), X)
    # everyone active with full links -> k = n, no padding possible
    full = np.ones(n, bool)
    links = ~np.eye(n, dtype=bool)
    W = mixing_matrix(full, links, d)
    w_rows, row_ids = mixing_rows(W, full, links)
    assert w_rows.shape == (n, n)
    np.testing.assert_allclose(
        WK.mix_flat(X, jnp.asarray(w_rows), jnp.asarray(row_ids)),
        jnp.asarray(W) @ X, rtol=1e-5, atol=1e-5)


def test_aggregate_rows_kernel_matches_matmul():
    rng = np.random.default_rng(3)
    Wr = jnp.asarray(rng.normal(size=(6, 20)), jnp.float32)
    X = jnp.asarray(rng.normal(size=(20, 513)), jnp.float32)
    np.testing.assert_allclose(K.aggregate_rows(Wr, X), Wr @ X,
                               rtol=1e-5, atol=1e-5)


def test_mixing_matrix_vectorized_matches_loop_reference():
    rng = np.random.default_rng(7)
    n = 15
    for _ in range(4):
        active = rng.random(n) < 0.4
        links = (rng.random((n, n)) < 0.2)
        np.fill_diagonal(links, False)
        d = rng.uniform(1, 20, n)
        W = mixing_matrix(active, links, d)
        # naive per-row reference (the pre-vectorization implementation)
        W_ref = np.eye(n, dtype=np.float32)
        for i in np.flatnonzero(active | links.any(axis=1)):
            members = np.unique(np.concatenate([np.flatnonzero(links[i]), [i]]))
            w = d[members] / d[members].sum()
            W_ref[i, :] = 0.0
            W_ref[i, members] = w.astype(np.float32)
        np.testing.assert_allclose(W, W_ref, rtol=1e-6, atol=0)
        np.testing.assert_allclose(W.sum(1), 1.0, rtol=1e-5)


# --------------------------------------------------------------------------- #
# flat local SGD vs stacked local_train (identical batches)
# --------------------------------------------------------------------------- #


def test_flat_sgd_matches_stacked_local_train():
    n, dim, hidden, n_classes = 8, 12, 16, 4
    steps, batch = 2, 6
    stacked = WK.init_stacked(jax.random.PRNGKey(0), n, dim, hidden, n_classes,
                              same_init=False)
    buf, spec = FS.flatten_stacked(stacked)
    kx, ky = jax.random.split(jax.random.PRNGKey(1))
    xb = jax.random.normal(kx, (n, steps, batch, dim), jnp.float32)
    yb = jax.random.randint(ky, (n, steps, batch), 0, n_classes)
    active = jnp.asarray(np.array([1, 0, 1, 1, 0, 0, 1, 0], bool))

    ref, ref_loss = WK.local_train(stacked, xb, yb, active, lr=0.05,
                                   local_steps=steps)
    out, out_loss = WK.local_sgd_flat(buf, xb, yb, active, spec, lr=0.05)
    ref_buf, _ = FS.flatten_stacked(ref)
    np.testing.assert_allclose(out, ref_buf, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(out_loss, ref_loss, rtol=1e-5, atol=1e-6)
    # inactive workers stay bit-identical
    np.testing.assert_array_equal(np.asarray(out)[~np.asarray(active)],
                                  np.asarray(buf)[~np.asarray(active)])


def test_round_step_fused_equals_unfused_pieces():
    """round_step == sparse mix -> gather -> sample -> SGD -> scatter, and the
    gathered-active-rows training equals full-buffer masked training."""
    n, dim, hidden, n_classes = 10, 8, 12, 3
    steps, batch = 2, 4
    rng = np.random.default_rng(0)
    stacked = WK.init_stacked(jax.random.PRNGKey(2), n, dim, hidden, n_classes)
    buf, spec = FS.flatten_stacked(stacked)
    data_x = jnp.asarray(rng.normal(size=(200, dim)), jnp.float32)
    data_y = jnp.asarray(rng.integers(0, n_classes, 200), jnp.int32)
    part_idx = jnp.asarray(rng.integers(0, 200, (n, 20)), jnp.int32)
    part_sizes = jnp.full((n,), 20, jnp.int32)
    active = rng.random(n) < 0.5
    links = (rng.random((n, n)) < 0.2) & active[:, None]
    np.fill_diagonal(links, False)
    W = mixing_matrix(active, links, np.ones(n))
    w_rows, mix_ids = mixing_rows(W, active, links)
    train_ids, train_mask = padded_rows(active)
    key = jax.random.PRNGKey(9)

    # reference: dense mix, then masked SGD over the FULL buffer with the
    # same per-worker-id-keyed batches
    mixed = jnp.asarray(W) @ buf
    round_key = jax.random.fold_in(key, 7)
    xb, yb = WK.sample_batches_device(round_key, jnp.arange(n), data_x, data_y,
                                      part_idx, part_sizes, steps, batch)
    ref, _ = WK.local_sgd_flat(mixed, xb, yb, jnp.asarray(active), spec,
                               lr=0.05)
    ctrl = WK.pack_round_ctrl(mix_ids, train_ids, train_mask)
    out, losses = WK.round_step(
        buf, jnp.asarray(w_rows), jnp.asarray(ctrl), data_x, data_y,
        part_idx, part_sizes, key, np.int32(7), spec=spec, lr=0.05,
        local_steps=steps, batch_size=batch)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)
    assert losses.shape == (n,)
    np.testing.assert_array_equal(np.asarray(losses)[~active], 0.0)
    assert np.all(np.asarray(losses)[active] > 0.0)


# --------------------------------------------------------------------------- #
# end-to-end History equivalence
# --------------------------------------------------------------------------- #


def _cfg(**kw):
    base = dict(n_workers=16, n_rounds=60, phi=0.5, lr=0.1, eval_every=20,
                seed=0, hidden=48, n_samples=6000)
    base.update(kw)
    return SimConfig(**base)


def test_fused_history_matches_legacy():
    """The engine against the per-round pytree reference drawing its own
    numpy batches: identical control plane, learning dynamics to tolerance
    (different batch streams)."""
    mech = lambda: DySTop(V=10.0, t_thre=20, max_neighbors=5)
    h_f, h_l = run_sim_with_reference(mech(), _cfg(), batches="host")
    # identical control plane: same rounds, times, comm, staleness, activity
    assert h_f.rounds == h_l.rounds
    np.testing.assert_allclose(h_f.sim_time, h_l.sim_time, rtol=0)
    np.testing.assert_allclose(h_f.comm_gb, h_l.comm_gb, rtol=0)
    assert h_f.staleness_avg == h_l.staleness_avg
    assert h_f.round_active == h_l.round_active
    # learning dynamics agree to tolerance (different batch RNG streams)
    assert abs(h_f.acc_global[-1] - h_l.acc_global[-1]) < 0.1
    assert h_f.acc_global[-1] > h_f.acc_global[0]
    np.testing.assert_allclose(h_f.acc_global, h_l.acc_global, atol=0.1)


def test_fused_kernel_path_matches_fused_jnp_path():
    """Same engine + same batch keys: only the mix arithmetic differs."""
    mech = lambda: DySTop(V=10.0, t_thre=10, max_neighbors=5)
    h_k = run_simulation(mech(), _cfg(
        n_rounds=20, kernels=KernelConfig(backend="pallas")))
    h_j = run_simulation(mech(), _cfg(n_rounds=20))
    np.testing.assert_allclose(h_k.acc_global, h_j.acc_global, atol=0.02)
    np.testing.assert_allclose(h_k.sim_time, h_j.sim_time, rtol=0)


def test_fused_reproducible():
    h1 = run_simulation(DySTop(V=10.0, t_thre=10), _cfg(n_rounds=10, eval_every=10))
    h2 = run_simulation(DySTop(V=10.0, t_thre=10), _cfg(n_rounds=10, eval_every=10))
    assert h1.acc_global == h2.acc_global
    assert h1.sim_time == h2.sim_time
