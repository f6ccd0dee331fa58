"""Kernel-plane pins (PR 10): the ``KernelConfig`` API and the three Pallas
moves behind it.

  1. the fused multi-step local-SGD kernel == the PR 3 manual-backward
     oracle (``local_sgd_flat_fused``) across bucket sizes and step counts;
  2. per-arch forward/backward parity of the zoo-kernel model integration
     (flash_attention / ssd_chunk / moe_router) vs the reference einsums,
     in interpret mode — the CI oracle for the TPU claim;
  3. ``backend="pallas"`` composes with ``mesh_shards`` ∈ {1, 2, 8}:
     control plane bit-exact, curves to f32 tolerance (multidevice lane);
  4. both engine configs default to the reference ``KernelConfig`` and
     refuse anything else in its place.

Everything here runs interpret-mode Pallas on CPU; see docs/BENCHMARKS.md
for the CPU-parity-vs-TPU claim policy.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.protocol import DySTop
from repro.dfl import flat_state as FS
from repro.dfl import worker as WK
from repro.dfl.simulator import SimConfig, run_simulation
from repro.kernels import fused_sgd as FSGD
from repro.kernels import ops as K
from repro.kernels.config import KernelConfig
from repro.models import registry as R


def needs_devices(k: int):
    return pytest.mark.skipif(
        jax.device_count() < k,
        reason=f"needs {k} devices (XLA_FLAGS=--xla_force_host_platform_"
               f"device_count=8)")


# --------------------------------------------------------------------------- #
# KernelConfig surface
# --------------------------------------------------------------------------- #


def test_kernel_config_is_frozen_and_hashable():
    a = KernelConfig(backend="pallas", agg_p_blk=256)
    b = KernelConfig(backend="pallas", agg_p_blk=256)
    assert a == b and hash(a) == hash(b)
    assert a != KernelConfig()
    with pytest.raises(dataclasses.FrozenInstanceError):
        a.backend = "reference"
    # rides through jit statics without retracing surprises
    jax.jit(lambda x, k: x * (2.0 if k.use_pallas else 1.0),
            static_argnames=("k",))(jnp.ones(3), a)


def test_kernel_config_validation():
    with pytest.raises(ValueError, match="backend"):
        KernelConfig(backend="cuda")
    with pytest.raises(ValueError, match="interpret"):
        KernelConfig(interpret="yes")
    with pytest.raises(ValueError, match="agg_p_blk"):
        KernelConfig(agg_p_blk=100)          # not lane-aligned
    with pytest.raises(ValueError, match="attn_blk_q"):
        KernelConfig(attn_blk_q=-8)
    with pytest.raises(ValueError, match="moe_blk_t"):
        KernelConfig(moe_blk_t=True)         # bools are not sizes
    with pytest.raises(ValueError, match="TPU"):
        KernelConfig(backend="pallas",
                     interpret=False).check_executable("here")


# --------------------------------------------------------------------------- #
# 1. fused-SGD kernel vs the manual-backward oracle
# --------------------------------------------------------------------------- #


def _stacked_mlp(rng, k, dim, hidden, n_classes):
    keys = jax.random.split(jax.random.PRNGKey(rng), k)
    trees = [WK.init_mlp(key, dim, hidden, n_classes) for key in keys]
    stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *trees)
    return FS.flatten_stacked(stacked)


@pytest.mark.parametrize("k,steps,batch", [(1, 1, 4), (4, 3, 8), (8, 2, 16),
                                           (5, 4, 4)])
@pytest.mark.parametrize("with_losses", [True, False])
def test_fused_sgd_kernel_matches_oracle(k, steps, batch, with_losses):
    dim, hidden, n_classes = 6, 9, 5
    buf, spec = _stacked_mlp(0, k, dim, hidden, n_classes)
    assert WK.fused_sgd_supported(spec)
    rng = np.random.default_rng(k * 100 + steps)
    xb = jnp.asarray(rng.normal(size=(k, steps, batch, dim)), jnp.float32)
    yb = jnp.asarray(rng.integers(0, n_classes, (k, steps, batch)), jnp.int32)
    active = jnp.asarray(rng.random(k) < 0.7, jnp.bool_)

    out_o, loss_o = WK.local_sgd_flat_fused(buf, xb, yb, active, spec, 0.05,
                                            with_losses=with_losses)
    out_k, loss_k = FSGD.fused_sgd(buf, xb, yb, active, spec, 0.05,
                                   with_losses=with_losses)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_o),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(loss_k), np.asarray(loss_o),
                               rtol=1e-5, atol=1e-5)
    # inactive rows take a zero-scaled update: bit-identical to the input
    idle = ~np.asarray(active)
    np.testing.assert_array_equal(np.asarray(out_k)[idle],
                                  np.asarray(buf)[idle])
    if not with_losses:
        np.testing.assert_array_equal(np.asarray(loss_k), np.zeros((k,)))


def test_fused_engine_pallas_matches_reference_trajectory():
    """Engine-level dispatch: the fused sim engine under
    ``KernelConfig(backend='pallas')`` (panel mix + fused-SGD kernel) tracks
    the jnp reference run — control plane bit-exact, f32 curves close."""
    mech = lambda: DySTop(V=10.0, t_thre=10, max_neighbors=5)
    h_ref = run_simulation(mech(), SimConfig(**_sim_kw()))
    h_pal = run_simulation(mech(), SimConfig(**_sim_kw(
        kernels=KernelConfig(backend="pallas"))))
    assert h_ref.sim_time == h_pal.sim_time
    assert h_ref.rounds == h_pal.rounds
    np.testing.assert_allclose(h_pal.loss_global, h_ref.loss_global,
                               atol=1e-4)
    np.testing.assert_allclose(h_pal.acc_global, h_ref.acc_global,
                               atol=1e-2)


# --------------------------------------------------------------------------- #
# 2. zoo kernels through the model zoo (forward AND backward)
# --------------------------------------------------------------------------- #

# one arch per kernel: flash_attention -> transformer family,
# ssd_chunk -> mamba2, moe_router -> MoE; recurrentgemma covers the
# hybrid (local-attention + rglru) composition of the same attention kernel
_KERNEL_ARCHS = ["smollm-135m", "gemma2-2b", "mamba2-2.7b",
                 "kimi-k2-1t-a32b", "recurrentgemma-2b"]


@pytest.mark.parametrize("arch", _KERNEL_ARCHS)
def test_model_forward_backward_parity(arch):
    cfg = R.get_smoke_config(arch)
    pal = dataclasses.replace(cfg, kernels=KernelConfig(backend="pallas"))
    params, _ = R.init_params(cfg, jax.random.PRNGKey(0))
    B, S = 2, 64
    tok = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                             cfg.vocab_size)
    batch = {"tokens": tok, "labels": tok,
             "loss_mask": jnp.ones((B, S), jnp.float32)}

    def loss(c):
        return lambda p: R.compute_loss(c, p, batch)[0]

    l_ref, g_ref = jax.value_and_grad(loss(cfg))(params)
    l_pal, g_pal = jax.value_and_grad(loss(pal))(params)
    # bf16 activations reordered through the kernel: loss to ~1e-3, grads to
    # a bf16 ulp at the observed magnitudes
    np.testing.assert_allclose(float(l_pal), float(l_ref), atol=2e-3)
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_pal)):
        np.testing.assert_allclose(np.asarray(b, np.float32),
                                   np.asarray(a, np.float32),
                                   atol=5e-3, rtol=5e-2)


def test_moe_router_ids_bitexact_and_gates_match():
    logits = jnp.asarray(np.random.default_rng(3).normal(size=(37, 8)),
                         jnp.float32)
    from repro.kernels.ref import moe_router_ref
    g_ref, i_ref = moe_router_ref(logits, 2)
    kc = KernelConfig(backend="pallas")
    g_k, i_k = K.moe_router_diff(logits, 2, kc)
    np.testing.assert_array_equal(np.asarray(i_k), np.asarray(i_ref))
    np.testing.assert_allclose(np.asarray(g_k), np.asarray(g_ref),
                               rtol=1e-6, atol=1e-6)
    # gates differentiate through the reference pullback; ids carry no grad
    g = jax.grad(lambda l: jnp.sum(K.moe_router_diff(l, 2, kc)[0] ** 2))(
        logits)
    assert np.isfinite(np.asarray(g)).all()


# --------------------------------------------------------------------------- #
# 3. pallas x mesh_shards (multidevice lane)
# --------------------------------------------------------------------------- #

_CONTROL_FIELDS = ("rounds", "sim_time", "comm_gb", "staleness_avg",
                   "staleness_max", "round_durations", "round_active")
_MESH_CACHE = {}


def _mesh_kw():
    return dict(n_workers=24, n_rounds=12, phi=0.5, lr=0.1, eval_every=6,
                seed=0, hidden=24, n_samples=2000,
                kernels=KernelConfig(backend="pallas"))


def _mesh_mech():
    return DySTop(V=10.0, t_thre=10, max_neighbors=5, max_workers=8)


@needs_devices(8)
@pytest.mark.parametrize("shards", [2, 8])
def test_sim_pallas_composes_with_mesh(shards):
    """shard_map panel kernels + fused-SGD rows under ``mesh_shards``:
    control plane bit-exact vs the single-shard pallas run, learning curves
    to f32 tolerance."""
    if "base" not in _MESH_CACHE:
        _MESH_CACHE["base"] = run_simulation(_mesh_mech(),
                                             SimConfig(**_mesh_kw()))
    h1 = _MESH_CACHE["base"]
    hs = run_simulation(_mesh_mech(),
                        SimConfig(mesh_shards=shards, **_mesh_kw()))
    for f in _CONTROL_FIELDS:
        assert getattr(hs, f) == getattr(h1, f), f
    np.testing.assert_allclose(hs.acc_global, h1.acc_global, atol=2e-2)
    np.testing.assert_allclose(hs.loss_global, h1.loss_global, atol=5e-2)


@needs_devices(2)
def test_lm_pallas_composes_with_mesh():
    from repro.dfl import lm_worker as LW
    cfg = R.get_smoke_config("smollm-135m")
    kw = dict(n_workers=4, n_rounds=4, batch=2, seq=16, seed=1, eval_every=2,
              kernels=KernelConfig(backend="pallas"))
    mech = lambda: DySTop(V=3.0, t_thre=3, max_neighbors=3)
    _, h1 = LW.run_lm_federation(mech(), cfg, LW.LMRunConfig(**kw))
    _, hs = LW.run_lm_federation(mech(), cfg,
                                 LW.LMRunConfig(mesh_shards=2, **kw))
    for f in _CONTROL_FIELDS:
        assert getattr(hs, f) == getattr(h1, f), f
    np.testing.assert_allclose(hs.loss_global, h1.loss_global, atol=5e-2)


@needs_devices(8)
@pytest.mark.parametrize("shards", [2, 8])
def test_sharded_panel_kernels_match_dense(shards):
    from repro.sharding.rules import FleetSharding
    shd = FleetSharding.create(shards)
    rng = np.random.default_rng(shards)
    n, p, k = 16, 200, 8
    X = jnp.asarray(rng.normal(size=(n, p)), jnp.float32)
    W_rows = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    dense = np.asarray(W_rows @ X)
    out = K.aggregate_rows_sharded(W_rows, shd.put_rows(X), shd, p_blk=128)
    np.testing.assert_allclose(np.asarray(out), dense, rtol=1e-5, atol=1e-5)

    u = 8
    col_ids = jnp.asarray(rng.choice(n, u, replace=False), jnp.int32)
    W_sub = jnp.asarray(rng.normal(size=(k, u)), jnp.float32)
    dense2 = np.asarray(W_sub @ np.asarray(X)[np.asarray(col_ids)])
    out2 = K.aggregate_rows_cols_sharded(W_sub, col_ids, shd.put_rows(X),
                                         shd, p_blk=128)
    np.testing.assert_allclose(np.asarray(out2), dense2, rtol=1e-5,
                               atol=1e-5)


# --------------------------------------------------------------------------- #
# 4. the engine configs' kernel field
# --------------------------------------------------------------------------- #


def _sim_kw(**kw):
    base = dict(n_workers=12, n_rounds=8, phi=0.5, lr=0.1, eval_every=4,
                seed=0, hidden=24, n_samples=1500)
    base.update(kw)
    return base


@pytest.mark.parametrize("plane", ["sim", "lm"])
def test_engine_config_kernels_default_and_type_check(plane):
    from repro.dfl.lm_worker import LMRunConfig
    make = ((lambda **kw: SimConfig(**_sim_kw(**kw))) if plane == "sim"
            else (lambda **kw: LMRunConfig(n_workers=4, n_rounds=2, **kw)))
    assert make().kernels == KernelConfig()
    assert make(kernels=KernelConfig(backend="pallas")).kernels.use_pallas
    with pytest.raises(ValueError, match="KernelConfig"):
        make(kernels="pallas")
