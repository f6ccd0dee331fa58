"""Per-plane ``min_bucket`` knob: trajectory equivalence + compile count.

Bucket padding only adds zero-weight rows / zero columns, so ANY min_bucket
replays the same control plane bit for bit on both planes.  The model state
is bit-identical on the sim plane; on the LM plane the padded bucket shapes
are different XLA programs whose f32 rounding may differ, so its state
agrees to f32 tolerance.  What the knob trades is compiled-shape count
(coarse buckets collapse many activation counts onto one shape) against
wasted padded row slots per dispatch."""
import numpy as np
import pytest

from repro.core.planner import chunk_spans
from repro.core.protocol import DySTop
from repro.dfl import lm_worker as LW
from repro.dfl.simulator import SimConfig, run_simulation
from repro.kernels.config import KernelConfig
from repro.models import registry as R


def _mech():
    return DySTop(V=3.0, t_thre=3, max_neighbors=3)


def test_sim_min_bucket_bit_identical():
    """Sim plane: min_bucket 2 / 8 (default) / N all replay the same run."""
    kw = dict(n_workers=12, n_rounds=20, eval_every=5, seed=0)
    h8 = run_simulation(_mech(), SimConfig(min_bucket=8, **kw))
    for mb in (2, 12):
        h = run_simulation(_mech(), SimConfig(min_bucket=mb, **kw))
        assert h.sim_time == h8.sim_time, mb
        assert h.round_active == h8.round_active, mb
        assert h.comm_gb == h8.comm_gb, mb
        assert h.acc_global == h8.acc_global, mb      # bit-exact, not close
        assert h.loss_global == h8.loss_global, mb


def test_lm_min_bucket_bit_identical_and_compile_count():
    """LM plane: min_bucket=8 vs 1 — identical control plane bit for bit,
    fleet state and losses to f32 tolerance (the padded shapes lower to
    different programs), and the coarse bucket compiles strictly fewer
    mega-dispatch shape variants (the whole point of the per-plane knob)."""
    cfg = R.get_smoke_config("smollm-135m")
    # unique lr -> a fresh LMEngine for this test (the engine cache keys on
    # the optimizer), so compiled-variant counts aren't polluted by other
    # tests that share the default-lr engine
    kw = dict(n_workers=8, n_rounds=10, batch=2, seq=16, eval_every=5,
              seed=1, lr=1.000001e-3)
    f8, h8 = LW.run_lm_federation(_mech(), cfg,
                                  LW.LMRunConfig(min_bucket=8, **kw))
    engine = LW.get_lm_engine(cfg, f8.optimizer, f8.spec,
                              KernelConfig(), None)
    megas = list(engine._mega_cache.values())
    if not all(hasattr(m, "_cache_size") for m in megas):
        pytest.skip("jitted _cache_size introspection unavailable")
    coarse = sum(m._cache_size() for m in megas)

    f1, h1 = LW.run_lm_federation(_mech(), cfg,
                                  LW.LMRunConfig(min_bucket=1, **kw))
    assert h1.sim_time == h8.sim_time
    assert h1.round_active == h8.round_active
    np.testing.assert_allclose(h1.loss_global, h8.loss_global, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(f1.pbuf), np.asarray(f8.pbuf),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(f1.obuf), np.asarray(f8.obuf),
                               rtol=1e-5, atol=1e-6)

    fine = sum(m._cache_size() for m in engine._mega_cache.values())
    # the same engine served both runs: min_bucket=8 collapsed every round
    # onto few shapes; dropping to 1 forced additional compiles
    assert coarse < fine, (coarse, fine)


def test_chunk_spans_min_bucket_controls_key_count():
    """The compile-count driver, unit-level: coarse buckets collapse varying
    activation counts onto one chunk key, fine buckets split them."""
    rng = np.random.default_rng(0)
    n = 16

    class P:                                          # minimal PlannedRound
        def __init__(self, k):
            self.active = np.zeros(n, bool)
            self.active[rng.choice(n, size=k, replace=False)] = True
            self.links = np.zeros((n, n), bool)
            self.mix_cols = None

    # k <= 7: with the padding column the column union stays within 8
    plans = [P(k) for k in (1, 2, 3, 5, 7, 4, 6)]
    coarse = list(chunk_spans(plans, n, min_bucket=8))
    fine = list(chunk_spans(plans, n, min_bucket=1))
    assert len({key for _, _, key in coarse}) == 1    # all k <= 7 -> one key
    assert len(coarse) == 1
    assert len({key for _, _, key in fine}) > 1
    assert len(fine) > len(coarse)
