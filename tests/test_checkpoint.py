"""checkpoint/io vs the LM fleet's bitwise residency contract.

``LMFleet`` stores bf16 params and int32 opt-state counters losslessly inside
f32 flat buffers; ``stacked_params``/``stacked_opt`` materialize (and, on
assignment, re-flatten) the typed pytrees.  A checkpoint must survive the full
cycle — materialize → save (bf16 as uint16 view) → load → reassign — with
every leaf bit-identical, and restoring host numpy control-plane arrays must
be dtype-exact (int64/float64 MUST NOT round-trip through jax's x64-disabled
default).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.io import (load_checkpoint, save_checkpoint)
from repro.dfl.lm_worker import init_fleet
from repro.models import registry as R


@pytest.fixture(scope="module")
def fleet():
    return init_fleet(R.get_smoke_config("smollm-135m"), n_workers=3, seed=0)


def _leaf_dtypes(tree):
    return {str(l.dtype) for l in jax.tree.leaves(tree)}


def test_fleet_has_the_dtypes_under_test(fleet):
    """Guard: the fixture actually exercises the contract (bf16 params,
    int32 opt counters) — if the smoke config changes, this fails loudly
    rather than letting the round-trip test go vacuous."""
    assert "bfloat16" in _leaf_dtypes(fleet.stacked_params)
    assert "int32" in _leaf_dtypes(fleet.stacked_opt)


def test_bf16_int32_roundtrip_through_residency(fleet, tmp_path):
    # perturb so the buffers aren't all-equal broadcast copies of w_0, then
    # canonicalize through the setter: the residency invariant is that the
    # f32 buffer holds values exactly representable in the leaf dtypes
    key = jax.random.PRNGKey(3)
    fleet.pbuf = fleet.pbuf + jax.random.normal(key, fleet.pbuf.shape) * 0.01
    fleet.stacked_params = fleet.stacked_params
    sp, so = fleet.stacked_params, fleet.stacked_opt
    path = tmp_path / "fleet.npz"
    save_checkpoint(path, sp, opt_state=so, extra={"round": 7})

    tmpl_p = jax.tree.map(jnp.zeros_like, sp)
    tmpl_o = jax.tree.map(jnp.zeros_like, so)
    lp, lo, extra = load_checkpoint(path, tmpl_p, tmpl_o)
    assert extra["round"] == 7

    for a, b in zip(jax.tree.leaves(sp), jax.tree.leaves(lp)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(so), jax.tree.leaves(lo)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # the residency contract: reassignment re-flattens EXACTLY — the flat
    # f32 buffers after the checkpoint cycle equal the originals bitwise
    pbuf0, obuf0 = np.asarray(fleet.pbuf), np.asarray(fleet.obuf)
    fleet.stacked_params = lp
    fleet.stacked_opt = lo
    np.testing.assert_array_equal(np.asarray(fleet.pbuf), pbuf0)
    np.testing.assert_array_equal(np.asarray(fleet.obuf), obuf0)


def test_numpy_control_plane_leaves_restore_dtype_exact(tmp_path):
    """int64/float64 host arrays (planner state) must come back bit-exact
    and dtype-exact even though jax runs x64-disabled."""
    state = {"tau": np.arange(2**40, 2**40 + 4, dtype=np.int64),
             "queue": np.array([1e-300, 1.5, np.pi], np.float64),
             "down": np.array([True, False, True])}
    path = tmp_path / "ctrl.npz"
    save_checkpoint(path, state)
    tmpl = {k: np.zeros_like(v) for k, v in state.items()}
    loaded, _, _ = load_checkpoint(path, tmpl)
    for k in state:
        assert loaded[k].dtype == state[k].dtype, k
        assert isinstance(loaded[k], np.ndarray)
        np.testing.assert_array_equal(loaded[k], state[k])


def test_missing_leaf_is_actionable(tmp_path):
    path = tmp_path / "p.npz"
    save_checkpoint(path, {"a": np.ones(2)})
    with pytest.raises(KeyError, match="params|b"):
        load_checkpoint(path, {"a": np.ones(2), "b": np.ones(2)})


def test_save_is_atomic_no_tmp_left_behind(tmp_path):
    path = tmp_path / "x.npz"
    save_checkpoint(path, {"a": np.ones(3)})
    save_checkpoint(path, {"a": np.zeros(3)})      # overwrite in place
    assert [p.name for p in tmp_path.iterdir()] == ["x.npz"]
    loaded, _, _ = load_checkpoint(path, {"a": np.ones(3)})
    np.testing.assert_array_equal(loaded["a"], np.zeros(3))


def test_trace_counts_survive_snapshot_and_resume(tmp_path):
    """``History.counts`` rides in the snapshot's JSON ``extra``: a run
    resumed from a mid-run snapshot ends with the counters of the
    uninterrupted run (compilations aside, which belong to each process's
    caches), while its host times are its own call's."""
    from repro.checkpoint import io as CIO
    from repro.core.protocol import DySTop
    from repro.dfl.simulator import SimConfig, run_simulation

    cfg = SimConfig(n_workers=16, n_rounds=20, hidden=16, n_samples=1200,
                    dim=8, eval_every=5, checkpoint_every=5,
                    checkpoint_dir=str(tmp_path))
    mech = DySTop(V=10.0, t_thre=8, max_neighbors=4)
    full = run_simulation(mech, cfg)
    mid = CIO.list_checkpoints(tmp_path)[1]
    _, _, extra = load_checkpoint(mid, {"buf": np.zeros((16, 1))})
    assert len(extra["history"]["round_active"]) == extra["round"]
    assert extra["history"]["counts"]["dispatches"] > 0
    resumed = run_simulation(mech, cfg, resume_from=str(mid))

    def trajectory(h):
        return {k: v for k, v in h.counts.items() if "/" not in k}

    assert trajectory(resumed) == trajectory(full)
    assert len(resumed.round_active) == 20
    assert resumed.plan_wall_s < full.plan_wall_s
