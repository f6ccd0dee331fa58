"""Async dispatch pipeline (dfl/pipeline.py + the pipelined drive loops).

The cardinal invariant: ``pipeline_depth`` NEVER changes a trajectory — the
rng stream is the trajectory, and the pipeline only rewires host/device
overlap.  Oracle ladder:

  * unit — ``worker.pack_chunk`` (the drive loops' fast packer) is
    bit-identical to ``pack_horizon`` on every bucket-uniform chunk, across
    row/col-sparse layouts, bucket sizes, planner-resolved and re-derived
    sparsity fields, and the documented fallback cases (all-idle chunks,
    full-width unions);
  * end-to-end sim — the depth-1 engine == the plain per-round pytree
    reference (``tests/engine_reference.py``) across ``scan_horizon`` x
    scenario presets: control plane exact, learning curves to f32
    tolerance; depth 2 == depth 1;
  * end-to-end LM — the depth-1 engine == the per-call-flatten reference on
    the smoke zoo arch;
  * sharded — ``mesh_shards=2`` == ``mesh_shards=1`` at depth 1
    (multidevice lane, skipped unless the backend exposes the devices);
  * resume — a depth-1 run resumed from a mid-run snapshot (a drained
    pipeline boundary by construction) finishes on the uninterrupted run's
    exact trajectory.  The real SIGKILL cycle rides scripts/chaos_check.py.
"""
import jax
import numpy as np
import pytest

from repro.checkpoint import io as CIO
from repro.core.aggregation import (col_union_mask, mixing_matrix,
                                    mixing_matrix_rows)
from repro.core.planner import PlannedRound, chunk_spans
from repro.core.protocol import DySTop
from repro.core.trace import Trace
from repro.dfl import lm_worker as LW
from repro.dfl import worker as WK
from repro.dfl.pipeline import DispatchPipeline
from repro.dfl.simulator import History as SimHistory
from repro.dfl.simulator import SimConfig, run_simulation
from repro.models import registry as R

from engine_reference import run_lm_with_reference, run_sim_with_reference

N_DEV = jax.device_count()


def needs_devices(k: int):
    return pytest.mark.skipif(
        N_DEV < k,
        reason=f"needs >= {k} jax devices; run under "
               f"XLA_FLAGS=--xla_force_host_platform_device_count=8")


# --------------------------------------------------------------------------- #
# DispatchPipeline unit behavior
# --------------------------------------------------------------------------- #


class _Token:
    def __init__(self):
        self.waited = False


def test_pipeline_bounds_in_flight_and_drains_fifo(monkeypatch):
    waited = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda tok: waited.append(tok))
    hist = SimHistory()
    pipe = DispatchPipeline(2, Trace(hist))
    toks = [_Token() for _ in range(4)]
    pipe.submit(toks[0])
    pipe.submit(toks[1])
    assert waited == []           # both fit in flight
    pipe.submit(toks[2])
    assert waited == [toks[0]]    # oldest popped to respect depth 2
    pipe.submit(toks[3])
    assert waited == [toks[0], toks[1]]
    pipe.drain()
    assert waited == toks         # FIFO, all retired
    pipe.drain()
    assert waited == toks         # idempotent
    assert hist.drain_wall_s >= 0.0
    assert hist.counts["backpressure_waits"] == 2


# --------------------------------------------------------------------------- #
# pack_chunk == pack_horizon, bit for bit
# --------------------------------------------------------------------------- #


def _random_plans(n, h, rng, idle_round=False, dense_links=False,
                  resolved=True):
    """Planner-shaped rounds: random activations/links, Eq. 4 W, and the
    plan-time sparsity fields either resolved (the pipelined planner) or
    left None (the packers' re-derive fallback)."""
    plans = []
    for t in range(h):
        if idle_round:
            active = np.zeros(n, bool)
            links = np.zeros((n, n), bool)
        else:
            # sparse enough that col-sparse unions bucket BELOW n (the
            # fast-packed case) while some rounds still pad mix/train rows
            active = rng.random(n) < 0.15
            if not active.any():
                active[int(rng.integers(n))] = True
            if dense_links:
                links = np.ones((n, n), bool) & active[:, None]
            else:
                links = (rng.random((n, n)) < 0.06) & active[:, None]
            np.fill_diagonal(links, False)
        W, mix_rows = mixing_matrix_rows(active, links, np.ones(n))
        kw = {}
        if resolved:
            mix_mask = np.zeros(n, bool)
            mix_mask[mix_rows] = True
            kw = dict(mix_cols=col_union_mask(active, links, 1),
                      mix_rows=mix_rows,
                      train_rows=np.flatnonzero(active),
                      mix_pad=np.flatnonzero(~mix_mask)[:1],
                      train_pad=np.flatnonzero(~active)[:1])
        plans.append(PlannedRound(t=t, active=active, links=links,
                                  synchronous=False, W=W, duration=1.0,
                                  n_transfers=int(links.sum()), **kw))
    return plans


@pytest.mark.parametrize("col_sparse", [False, True])
@pytest.mark.parametrize("min_bucket", [2, 8])
@pytest.mark.parametrize("resolved", [True, False])
def test_pack_chunk_matches_pack_horizon(col_sparse, min_bucket, resolved):
    rng = np.random.default_rng(0)
    n = 32
    plans = _random_plans(n, 32, rng, resolved=resolved)
    seen_fast = 0
    for lo, hi, key in chunk_spans(plans, n, min_bucket=min_bucket):
        chunk = plans[lo:hi]
        ref = WK.pack_horizon(chunk, min_bucket=min_bucket,
                              col_sparse=col_sparse)
        out = WK.pack_chunk(chunk, key, min_bucket=min_bucket,
                            col_sparse=col_sparse)
        for a, b in zip(ref, out):
            np.testing.assert_array_equal(a, b)
            assert a.dtype == b.dtype
        if not (col_sparse and int(key[2]) >= n):
            seen_fast += 1
    assert seen_fast            # the sweep exercised the fast loop


def test_pack_chunk_fallback_cases():
    rng = np.random.default_rng(1)
    n = 16

    # all-idle chunk: k_mix == 0 routes through pack_horizon verbatim
    idle = _random_plans(n, 3, rng, idle_round=True)
    (lo, hi, key), = list(chunk_spans(idle, n))
    assert key[0] == 0
    ref = WK.pack_horizon(idle)
    out = WK.pack_chunk(idle, key)
    for a, b in zip(ref, out):
        np.testing.assert_array_equal(a, b)

    # dense links: the column union goes full-width (u >= n), the
    # documented col-sparse fallback
    dense = _random_plans(n, 4, rng, dense_links=True)
    for lo, hi, key in chunk_spans(dense, n, min_bucket=2):
        assert int(key[2]) >= n
        ref = WK.pack_horizon(dense[lo:hi], min_bucket=2, col_sparse=True)
        out = WK.pack_chunk(dense[lo:hi], key, min_bucket=2, col_sparse=True)
        for a, b in zip(ref, out):
            np.testing.assert_array_equal(a, b)

    # shards > 1 routes through pack_horizon's shard-aware padding layout
    mixed = _random_plans(n, 4, rng)
    for lo, hi, key in chunk_spans(mixed, n, mesh_shards=2):
        ref = WK.pack_horizon(mixed[lo:hi], shards=2)
        out = WK.pack_chunk(mixed[lo:hi], key, shards=2)
        for a, b in zip(ref, out):
            np.testing.assert_array_equal(a, b)


def test_planner_resolved_pad_fields_match_rederived():
    """The plan-time padding candidates equal what pack_chunk re-derives,
    so resolved and fallback packs agree on every chunk."""
    rng = np.random.default_rng(2)
    n = 24
    resolved = _random_plans(n, 16, rng, resolved=True)
    bare = [PlannedRound(t=p.t, active=p.active, links=p.links,
                         synchronous=p.synchronous, W=p.W,
                         duration=p.duration, n_transfers=p.n_transfers)
            for p in resolved]
    for (lo, hi, key), (lo2, hi2, key2) in zip(chunk_spans(resolved, n),
                                               chunk_spans(bare, n)):
        assert (lo, hi, key) == (lo2, hi2, key2)
        for cs in (False, True):
            a = WK.pack_chunk(resolved[lo:hi], key, col_sparse=cs)
            b = WK.pack_chunk(bare[lo:hi], key, col_sparse=cs)
            for x, y in zip(a, b):
                np.testing.assert_array_equal(x, y)


# --------------------------------------------------------------------------- #
# end-to-end: the depth-1 engine == the plain reference (sim plane)
# --------------------------------------------------------------------------- #

_CONTROL_FIELDS = ("rounds", "sim_time", "comm_gb", "staleness_avg",
                   "staleness_max", "round_durations", "round_active")
_MODEL_FIELDS = ("acc_global", "acc_local", "loss_global")


def _mech():
    return DySTop(V=10.0, t_thre=8, max_neighbors=4)


def _sim_cfg(**kw):
    base = dict(n_workers=16, n_rounds=24, phi=0.5, lr=0.1, eval_every=6,
                seed=0, hidden=16, n_samples=1200, dim=8)
    base.update(kw)
    return SimConfig(**base)


@pytest.mark.parametrize("horizon", [1, 8])
@pytest.mark.parametrize("scenario", ["churn20", "blackout"])
def test_sim_depth1_matches_lockstep_oracle(horizon, scenario):
    """The pipelined, chunked engine against the per-round pytree
    reference fed the same batches: control plane exact, learning curves to
    f32 rounding (dense vs sparse Eq. 4, AD vs fused Eq. 5)."""
    h1, ref = run_sim_with_reference(
        _mech(), _sim_cfg(scan_horizon=horizon, scenario=scenario,
                          pipeline_depth=1))
    for f in _CONTROL_FIELDS:
        assert getattr(ref, f) == getattr(h1, f), f
    for f in _MODEL_FIELDS:
        np.testing.assert_allclose(getattr(ref, f), getattr(h1, f),
                                   rtol=1e-6, atol=1e-7, err_msg=f)


def test_sim_deeper_pipeline_is_still_identical():
    """Depth 2 keeps two chunks in flight — same trajectory regardless."""
    h1 = run_simulation(_mech(), _sim_cfg(pipeline_depth=1))
    h2 = run_simulation(_mech(), _sim_cfg(pipeline_depth=2))
    for f in _CONTROL_FIELDS:
        assert getattr(h1, f) == getattr(h2, f), f
    for f in _MODEL_FIELDS:
        np.testing.assert_allclose(getattr(h1, f), getattr(h2, f),
                                   rtol=1e-6, atol=1e-7, err_msg=f)


def test_sim_depth1_resume_is_bit_identical(tmp_path):
    """Resume from a snapshot written mid-run at depth 1: checkpoint
    boundaries drain the pipeline, so the snapshot is round-consistent and
    the resumed run finishes on the uninterrupted trajectory."""
    ref = run_simulation(_mech(), _sim_cfg(n_rounds=20, scenario="churn20",
                                           eval_every=5, pipeline_depth=1))
    ck = _sim_cfg(n_rounds=20, scenario="churn20", eval_every=5,
                  pipeline_depth=1, checkpoint_every=5,
                  checkpoint_dir=str(tmp_path))
    run_simulation(_mech(), ck)
    mid = CIO.list_checkpoints(tmp_path)[1]      # a mid-run snapshot
    res = run_simulation(_mech(), ck, resume_from=str(mid))
    for f in _CONTROL_FIELDS:
        assert getattr(ref, f) == getattr(res, f), f
    for f in _MODEL_FIELDS:
        np.testing.assert_allclose(getattr(ref, f), getattr(res, f),
                                   rtol=1e-6, atol=1e-7, err_msg=f)


@pytest.mark.parametrize("plane", ["sim", "lm"])
@pytest.mark.parametrize("depth", [-1, 0])
def test_pipeline_depth_validation(plane, depth):
    """One engine path: every depth runs the pipeline, so a depth below 1
    is refused at construction, not silently run lockstep."""
    make = SimConfig if plane == "sim" else LW.LMRunConfig
    with pytest.raises(ValueError, match="pipeline_depth must be >= 1"):
        make(pipeline_depth=depth)


def test_dispatch_pipeline_rejects_depth_below_one():
    with pytest.raises(ValueError, match=">= 1"):
        DispatchPipeline(0, Trace(SimHistory()))


# --------------------------------------------------------------------------- #
# end-to-end: LM plane
# --------------------------------------------------------------------------- #


def _lm_mech():
    return DySTop(V=3.0, t_thre=3, max_neighbors=3)


def _lm_kw(**kw):
    base = dict(n_workers=4, n_rounds=12, batch=2, seq=8, eval_every=4,
                seed=1, scenario="blackout")
    base.update(kw)
    return base


@pytest.mark.parametrize("horizon", [1, 8])
def test_lm_depth1_matches_lockstep_oracle(horizon):
    """The pipelined LM engine against the per-call-flatten reference on
    the same plans and token streams."""
    cfg = R.get_smoke_config("smollm-135m")
    f1, h1, ref = run_lm_with_reference(
        _lm_mech(), cfg,
        LW.LMRunConfig(scan_horizon=horizon, pipeline_depth=1, **_lm_kw()))
    h0 = ref.hist
    for f in _CONTROL_FIELDS:
        assert getattr(h0, f) == getattr(h1, f), f
    # per-round losses drain at eval/history boundaries only — values
    # still match the per-round reference's
    np.testing.assert_allclose(h0.round_loss, h1.round_loss,
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(h0.loss_global, h1.loss_global, rtol=1e-6)
    np.testing.assert_allclose(ref.pbuf, np.asarray(f1.pbuf),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(ref.obuf, np.asarray(f1.obuf),
                               rtol=1e-6, atol=1e-7)


# --------------------------------------------------------------------------- #
# sharded: the depth-1 engine at mesh_shards=2 (multidevice lane)
# --------------------------------------------------------------------------- #


@needs_devices(2)
def test_sim_depth1_matches_oracle_sharded():
    h0 = run_simulation(_mech(), _sim_cfg(mesh_shards=1, pipeline_depth=1))
    h1 = run_simulation(_mech(), _sim_cfg(mesh_shards=2, pipeline_depth=1))
    for f in _CONTROL_FIELDS:
        assert getattr(h0, f) == getattr(h1, f), f
    for f in _MODEL_FIELDS:
        np.testing.assert_allclose(getattr(h0, f), getattr(h1, f),
                                   rtol=1e-6, atol=1e-7, err_msg=f)
