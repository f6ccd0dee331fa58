"""Sharded fleet engine: the mesh-partitioned resident buffers vs the
``mesh_shards=1`` oracle.

Oracle ladder (ISSUE 5 acceptance):
  * host helpers — shard-aware padding/unions/spans keep the identity-row
    padding contract consistent and stay bit-identical at ``shards=1``
    (these run on ANY backend, including the single-device tier-1 lane);
  * unit — sharded mix/round_step against the dense/unsharded references on
    identical inputs, including a ragged (padded) worker axis;
  * end-to-end — ``run_simulation`` (N=100) and ``run_lm_federation``
    (N=64) at ``mesh_shards ∈ {2, 4, 8}``: control-plane histories
    bit-exact vs the single-device engine, learning curves / model state to
    f32 reduction-order tolerance, for N both divisible and NOT divisible
    by the shard count;

Multi-device cases skip unless the backend exposes enough devices — CI runs
them in the ``tests-multidevice`` lane under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (JAX_PLATFORMS=cpu).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aggregation import (col_union_mask, mixing_matrix,
                                    mixing_rows, mixing_rows_cols,
                                    padded_rows, shard_pad_candidates)
from repro.core.planner import shard_spans
from repro.core.protocol import DySTop
from repro.dfl import flat_state as FS
from repro.dfl import lm_worker as LW
from repro.dfl import worker as WK
from repro.dfl.simulator import SimConfig, run_simulation
from repro.models import registry as R

N_DEV = jax.device_count()


def needs_devices(k: int):
    return pytest.mark.skipif(
        N_DEV < k,
        reason=f"needs >= {k} jax devices; run under "
               f"XLA_FLAGS=--xla_force_host_platform_device_count=8")


def _shd(shards: int):
    from repro.sharding.rules import FleetSharding
    return FleetSharding.create(shards)


# --------------------------------------------------------------------------- #
# host-side shard helpers (any backend)
# --------------------------------------------------------------------------- #


def test_shard_pad_candidates_layout():
    mask = np.zeros(12, bool)
    mask[[0, 1, 5, 9]] = True
    # shards=1: the historical first-idle choice, exactly
    np.testing.assert_array_equal(shard_pad_candidates(mask, 1), [2])
    # 4 shards x block 3: first idle of each block
    np.testing.assert_array_equal(shard_pad_candidates(mask, 4),
                                  [2, 3, 6, 10])
    # a fully-busy block falls back to the global first idle
    mask2 = np.ones(8, bool)
    mask2[[6, 7]] = False
    np.testing.assert_array_equal(shard_pad_candidates(mask2, 4), [6])
    # no idle rows at all -> empty (no padding is ever needed then)
    assert len(shard_pad_candidates(np.ones(4, bool), 2)) == 0


def test_padded_rows_sharded_layout_and_oracle():
    rng = np.random.default_rng(0)
    for n, shards in ((16, 4), (10, 4), (100, 8)):
        for _ in range(5):
            mask = rng.random(n) < 0.3
            if mask.all():
                mask[0] = False
            ids1, valid1 = padded_rows(mask, min_bucket=4)
            ids_s, valid_s = padded_rows(mask, min_bucket=4, shards=shards)
            # same bucket, same REAL rows, masks mark exactly the real rows
            assert len(ids_s) == len(ids1)
            np.testing.assert_array_equal(np.sort(ids_s[valid_s]),
                                          np.sort(ids1[valid1]))
            assert not mask[ids_s[~valid_s]].any()
            # grouped by home shard: sorted ids + contiguous spans cover all
            assert (np.diff(ids_s) >= 0).all()
            spans = shard_spans(ids_s, n, shards)
            assert spans[-1][1] == len(ids_s)
            assert all(lo <= hi for lo, hi in spans)


def test_col_union_mask_contains_all_padding_candidates():
    """The identity-row padding contract: every padding candidate's column
    must be in the union, so padded rows restricted to the union still pick
    out their own value."""
    rng = np.random.default_rng(1)
    n, shards = 24, 8
    for _ in range(8):
        active = rng.random(n) < 0.3
        links = (rng.random((n, n)) < 0.1) & active[:, None]
        np.fill_diagonal(links, False)
        mix_mask = active | links.any(axis=1)
        if mix_mask.all() or not mix_mask.any():
            continue
        cols = col_union_mask(active, links, shards)
        assert cols[shard_pad_candidates(mix_mask, shards)].all()
        # and it is a superset of the unsharded union
        assert (cols | col_union_mask(active, links)).sum() == cols.sum()


def test_sharded_mixing_rows_cols_matches_dense():
    """Shard-aware padding + unions stay exact: gathered rows restricted to
    the union, scattered back, equal the dense W @ X product (including the
    multi-candidate identity padding rows)."""
    rng = np.random.default_rng(2)
    n, p, shards = 24, 33, 8
    for seed in range(5):
        active = rng.random(n) < 0.35
        links = (rng.random((n, n)) < 0.12) & active[:, None]
        np.fill_diagonal(links, False)
        W = mixing_matrix(active, links, rng.uniform(1, 9, n))
        X = jnp.asarray(rng.normal(size=(n, p)), jnp.float32)
        w_sub, row_ids, col_ids = mixing_rows_cols(W, active, links,
                                                   min_bucket=4,
                                                   shards=shards)
        out = WK.mix_flat_cols(X, jnp.asarray(w_sub), jnp.asarray(row_ids),
                               jnp.asarray(col_ids))
        np.testing.assert_allclose(out, jnp.asarray(W) @ X,
                                   rtol=1e-5, atol=1e-5)
        w_rows, row_ids2 = mixing_rows(W, active, links, min_bucket=4,
                                       shards=shards)
        out2 = WK.mix_flat(X, jnp.asarray(w_rows), jnp.asarray(row_ids2))
        np.testing.assert_allclose(out2, jnp.asarray(W) @ X,
                                   rtol=1e-5, atol=1e-5)


def test_pad_w_cols_noop_value():
    w = np.arange(12, dtype=np.float32).reshape(3, 4)
    wp = WK.pad_w_cols(w, 6)
    assert wp.shape == (3, 6)
    np.testing.assert_array_equal(wp[:, 4:], 0.0)
    x = np.random.default_rng(0).normal(size=(6, 5)).astype(np.float32)
    np.testing.assert_allclose(wp @ x, w @ x[:4], rtol=1e-6)


# --------------------------------------------------------------------------- #
# device-level units (mesh required)
# --------------------------------------------------------------------------- #


@needs_devices(2)
@pytest.mark.parametrize("shards", [2, 4])
def test_sharded_mix_matches_dense(shards):
    if N_DEV < shards:
        pytest.skip(f"{shards} shards need {shards} devices")
    shd = _shd(shards)
    rng = np.random.default_rng(3)
    n, p = 16, 40
    active = rng.random(n) < 0.4
    links = (rng.random((n, n)) < 0.15) & active[:, None]
    np.fill_diagonal(links, False)
    W = mixing_matrix(active, links, rng.uniform(1, 5, n))
    X = rng.normal(size=(n, p)).astype(np.float32)
    Xs = shd.put_rows(jnp.asarray(X))
    dense = np.asarray(W @ X)

    w_rows, row_ids = mixing_rows(W, active, links, min_bucket=4,
                                  shards=shards)
    out = jax.jit(WK.mix_flat, static_argnames=("kernels", "shd"))(
        Xs, shd.put(jnp.asarray(w_rows)), shd.put(jnp.asarray(row_ids)),
        shd=shd)
    assert out.sharding == shd.rows()
    np.testing.assert_allclose(np.asarray(out), dense, rtol=1e-5, atol=1e-5)

    w_sub, row_ids2, col_ids = mixing_rows_cols(W, active, links,
                                                min_bucket=4, shards=shards)
    out2 = jax.jit(WK.mix_flat_cols, static_argnames=("kernels", "shd"))(
        Xs, shd.put(jnp.asarray(w_sub)), shd.put(jnp.asarray(row_ids2)),
        shd.put(jnp.asarray(col_ids)), shd=shd)
    assert out2.sharding == shd.rows()
    np.testing.assert_allclose(np.asarray(out2), dense, rtol=1e-5, atol=1e-5)


@needs_devices(4)
def test_sharded_round_step_matches_unsharded_ragged():
    """One fused round on a PADDED (ragged N) sharded buffer == the same
    round unsharded: real rows match to f32 tolerance, padding rows stay
    bit-identical (never touched)."""
    shards = 4
    n, n_pad = 10, 12               # ragged: 10 rows over 4 shards -> pad 2
    dim, hidden, n_classes, steps, batch = 8, 12, 3, 2, 4
    shd = _shd(shards)
    assert shd.pad(n) == 2
    rng = np.random.default_rng(4)
    stacked = WK.init_stacked(jax.random.PRNGKey(2), n, dim, hidden,
                              n_classes, same_init=False)
    buf, spec = FS.flatten_stacked(stacked)
    data_x = jnp.asarray(rng.normal(size=(200, dim)), jnp.float32)
    data_y = jnp.asarray(rng.integers(0, n_classes, 200), jnp.int32)
    part_idx = rng.integers(0, 200, (n, 20)).astype(np.int32)
    part_sizes = np.full((n,), 20, np.int32)
    active = rng.random(n) < 0.5
    links = (rng.random((n, n)) < 0.25) & active[:, None]
    np.fill_diagonal(links, False)
    W = mixing_matrix(active, links, np.ones(n))
    key = jax.random.PRNGKey(9)
    kw = dict(spec=spec, lr=0.05, local_steps=steps, batch_size=batch,
              col_sparse=True, fused_sgd=True, mix_is_train=False)

    w_sub, mix_ids, col_ids = mixing_rows_cols(W, active, links, min_bucket=4)
    train_ids, train_mask = padded_rows(active, min_bucket=4)
    ctrl = WK.pack_round_ctrl(mix_ids, train_ids, train_mask, col_ids=col_ids)
    ref, _ = WK.round_step(jnp.array(buf), jnp.asarray(w_sub),
                           jnp.asarray(ctrl), data_x, data_y,
                           jnp.asarray(part_idx), jnp.asarray(part_sizes),
                           key, np.int32(7), **kw)

    # sharded twin: padded buffer, shard-aware padding layout
    buf_p = shd.put_rows(jnp.concatenate(
        [buf, jnp.zeros((n_pad - n, buf.shape[1]), buf.dtype)]))
    w_sub_s, mix_ids_s, col_ids_s = mixing_rows_cols(
        W, active, links, min_bucket=4, shards=shards)
    train_ids_s, train_mask_s = padded_rows(active, min_bucket=4,
                                            shards=shards)
    ctrl_s = WK.pack_round_ctrl(mix_ids_s, train_ids_s, train_mask_s,
                                col_ids=col_ids_s)
    out, _ = WK.round_step(
        buf_p, shd.put(jnp.asarray(w_sub_s)), shd.put(jnp.asarray(ctrl_s)),
        shd.put(data_x), shd.put(data_y),
        shd.put_rows(jnp.asarray(np.pad(part_idx, ((0, n_pad - n), (0, 0))))),
        shd.put_rows(jnp.asarray(np.pad(part_sizes, (0, n_pad - n),
                                        constant_values=1))),
        shd.put(key), np.int32(7), shd=shd, **kw)
    assert out.sharding == shd.rows()
    np.testing.assert_allclose(np.asarray(out)[:n], np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(out)[n:], 0.0)


# --------------------------------------------------------------------------- #
# end-to-end: the ISSUE 5 acceptance runs
# --------------------------------------------------------------------------- #


_CONTROL_FIELDS = ("rounds", "sim_time", "comm_gb", "staleness_avg",
                   "staleness_max", "round_durations", "round_active")

_ORACLE_CACHE: dict = {}


def _cached(key, fn):
    """One oracle run shared across the parametrized shard counts."""
    if key not in _ORACLE_CACHE:
        _ORACLE_CACHE[key] = fn()
    return _ORACLE_CACHE[key]


def _sim_cfg(**kw):
    base = dict(n_workers=100, n_rounds=24, phi=0.5, lr=0.1, eval_every=8,
                seed=0, hidden=24, n_samples=4000)
    base.update(kw)
    return SimConfig(**base)


def _sim_mech():
    return DySTop(V=10.0, t_thre=10, max_neighbors=5, max_workers=16)


@needs_devices(2)
@pytest.mark.parametrize("shards", [2, 4, 8])
def test_sim_sharded_matches_oracle_n100(shards):
    """N=100 simulation: mesh_shards ∈ {2, 4, 8} (100 % 8 != 0 — the ragged
    case pads to 104) reproduce the single-device control trajectory
    bit-for-bit and the learning curve to f32 tolerance."""
    if N_DEV < shards:
        pytest.skip(f"{shards} shards need {shards} devices")
    h1 = _cached("sim100",
                 lambda: run_simulation(_sim_mech(), _sim_cfg(mesh_shards=1)))
    hs = run_simulation(_sim_mech(), _sim_cfg(mesh_shards=shards))
    for f in _CONTROL_FIELDS:
        assert getattr(hs, f) == getattr(h1, f), f
    np.testing.assert_allclose(hs.acc_global, h1.acc_global, atol=2e-2)
    np.testing.assert_allclose(hs.acc_local, h1.acc_local, atol=2e-2)
    np.testing.assert_allclose(hs.loss_global, h1.loss_global,
                               rtol=1e-3, atol=1e-3)


@needs_devices(2)
def test_sim_sharded_row_sparse_path(monkeypatch, shards=2):
    """The row-sparse mix exercises the psum lowering + the zero-padded W
    columns; control stays exact.  At N=10 with 8-row buckets every chunk's
    column union is as wide as its rows, so ``prefer_cols`` picks rows."""
    picked = []
    for name in ("round_step", "mega_round_step"):
        orig = getattr(WK, name)

        def spy(*a, _orig=orig, **kw):
            picked.append(kw["col_sparse"])
            return _orig(*a, **kw)

        monkeypatch.setattr(WK, name, spy)
    h1 = run_simulation(_sim_mech(),
                        _sim_cfg(n_workers=10, n_rounds=12, eval_every=6,
                                 n_samples=1500, mesh_shards=1))
    hs = run_simulation(_sim_mech(),
                        _sim_cfg(n_workers=10, n_rounds=12, eval_every=6,
                                 n_samples=1500, mesh_shards=shards))
    assert picked and not any(picked), picked
    for f in _CONTROL_FIELDS:
        assert getattr(hs, f) == getattr(h1, f), f
    np.testing.assert_allclose(hs.acc_global, h1.acc_global, atol=2e-2)


@needs_devices(2)
def test_sim_mesh_with_kernel_composes():
    """PR 10: Pallas + mesh_shards is no longer rejected — the shard_map
    panel kernels carry the mix, and the control plane stays bit-identical
    to the single-device pallas run."""
    from repro.kernels.config import KernelConfig
    kw = dict(n_workers=10, n_rounds=12, eval_every=6, n_samples=1500,
              kernels=KernelConfig(backend="pallas"))
    h1 = _cached("mesh_kernel_base", lambda: run_simulation(
        _sim_mech(), _sim_cfg(**kw)))
    hs = run_simulation(_sim_mech(), _sim_cfg(mesh_shards=2, **kw))
    for f in _CONTROL_FIELDS:
        assert getattr(hs, f) == getattr(h1, f), f
    np.testing.assert_allclose(hs.acc_global, h1.acc_global, atol=2e-2)


def _lm_kw(**kw):
    base = dict(n_rounds=6, batch=1, seq=16, eval_every=3, seed=1)
    base.update(kw)
    return base


def _lm_mech():
    return DySTop(V=3.0, t_thre=3, max_neighbors=3, max_workers=8)


@needs_devices(2)
@pytest.mark.parametrize("n_workers,shards,min_bucket",
                         [(64, 8, 2), (6, 4, 2), (8, 4, 4)])
def test_lm_sharded_matches_oracle(n_workers, shards, min_bucket):
    """N=64 LM fleet at mesh_shards=8 (the acceptance geometry), a small
    ragged case, and a coarse bucket whose padding rows (skipped train
    steps) lie in several shards: control bit-exact, resident buffers to
    f32 tolerance."""
    if N_DEV < shards:
        pytest.skip(f"{shards} shards need {shards} devices")
    cfg = R.get_smoke_config("smollm-135m")
    kw = _lm_kw(n_workers=n_workers, min_bucket=min_bucket)
    f1, h1 = _cached(
        f"lm{n_workers}-b{min_bucket}",
        lambda: LW.run_lm_federation(_lm_mech(), cfg,
                                     LW.LMRunConfig(mesh_shards=1, **kw)))
    fs, hs = LW.run_lm_federation(_lm_mech(), cfg,
                                  LW.LMRunConfig(mesh_shards=shards, **kw))
    for f in _CONTROL_FIELDS:
        assert getattr(hs, f) == getattr(h1, f), f
    assert fs.pbuf.shape == f1.pbuf.shape      # padding shed at return
    np.testing.assert_allclose(np.asarray(fs.pbuf), np.asarray(f1.pbuf),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(fs.obuf), np.asarray(f1.obuf),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(hs.loss_global, h1.loss_global, rtol=1e-3)
