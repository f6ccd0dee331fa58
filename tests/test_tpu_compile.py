"""v5e compile rehearsals: every Pallas kernel, at the shapes the chip runs,
and the LM mega-round's skip of padding rows.

Each kernel test compiles one kernel for a described (not attached) TPU v5e
with the TPU compiler installed next to JAX, and checks that the compiled
program holds the Mosaic kernel (``tpu_custom_call``).  Interpret mode, which the
rest of the suite runs on the CPU, accepts tilings and slices that Mosaic
refuses; these compiles catch that without a chip.  Nothing runs, so they
say nothing about values or times.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.  The fixture skips where the topology cannot be described.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.dfl import flat_state as FS
from repro.dfl import lm_worker as LW
from repro.dfl.worker import init_mlp
from repro.kernels import aggregate as AGG
from repro.kernels import flash_attention as FA
from repro.kernels import fused_sgd as FSGD
from repro.kernels import moe_router as MR
from repro.kernels import ssd_chunk as SC
from repro.kernels.config import KernelConfig
from repro.launch.mesh import FLEET_AXIS
from repro.models import registry as R
from repro.sharding.rules import FleetSharding

SIM_P = 6922            # default sim MLP: dim 32, hidden 64, 10 classes
SMOLLM_P = 134_515_008  # smollm-135m, one worker's flat row
MAMBA_P = 177_252_800   # mamba2-2.7b at 4 layers, an eighth of the vocab


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def fleet4(topo):
    mesh = Mesh(topo.devices[:4], (FLEET_AXIS,),
                axis_types=(jax.sharding.AxisType.Auto,))
    return FleetSharding(mesh=mesh, axis=FLEET_AXIS)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("n,k,u,p", [
    (100, 8, 64, SIM_P),      # sim plane: N=100 fleet, 64-column union
    (4, 4, 4, SMOLLM_P),      # LM plane: 4 smollm-135m workers, all mixed
    (4, 2, 4, SMOLLM_P),      # the smollm cell's buckets: the VPU body
    (2, 2, 2, MAMBA_P),       # the Mamba-2 cell (4 layers, 2 workers)
])
def test_aggregate_rows_cols_compiles(one_chip, n, k, u, p):
    txt = _compiled_text(
        lambda w, c, x: AGG.aggregate_rows_cols(w, c, x, interpret=False),
        _sds((k, u), one_chip), _sds((u,), one_chip, jnp.int32),
        _sds((n, p), one_chip))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("cols", [True, False])
def test_sharded_panels_compile_on_four_chips(fleet4, cols):
    rows = fleet4.rows()
    rep = fleet4.replicated()
    x = _sds((128, SIM_P), rows)
    if cols:
        txt = _compiled_text(
            lambda w, c, x: AGG.aggregate_rows_cols_sharded_kernel(
                w, c, x, fleet4, interpret=False),
            _sds((8, 64), rows), _sds((64,), rep, jnp.int32), x)
    else:
        txt = _compiled_text(
            lambda w, x: AGG.aggregate_rows_sharded_kernel(
                w, x, fleet4, interpret=False),
            _sds((8, 128), rep), x)
    assert "tpu_custom_call" in txt
    assert "all-reduce" in txt            # the psum over the fleet axis


def test_fused_sgd_compiles(one_chip):
    params = init_mlp(jax.random.PRNGKey(0), 32, 64, 10)
    spec = FS.spec_of(jax.tree.map(lambda a: a[None], params))
    assert spec.n_params == SIM_P
    k, steps, batch, dim = 16, 2, 32, 32
    txt = _compiled_text(
        lambda b, x, y, a: FSGD.fused_sgd(b, x, y, a, spec, 0.05,
                                          interpret=False),
        _sds((k, SIM_P), one_chip), _sds((k, steps, batch, dim), one_chip),
        _sds((k, steps, batch), one_chip, jnp.int32), _sds((k,), one_chip))
    assert "tpu_custom_call" in txt


def test_flash_attention_compiles(one_chip):
    q = _sds((2, 9, 256, 64), one_chip, jnp.bfloat16)   # smollm-135m heads
    txt = _compiled_text(
        lambda q, k, v: FA.flash_attention(q, k, v, interpret=False), q, q, q)
    assert "tpu_custom_call" in txt


def test_ssd_chunk_compiles(one_chip):
    g, h, q, n, p = 2, 80, 256, 128, 64                 # mamba2-2.7b chunk
    txt = _compiled_text(
        lambda b, c, la, x: SC.ssd_chunk(b, c, la, x, interpret=False),
        _sds((g, q, n), one_chip), _sds((g, q, n), one_chip),
        _sds((g, h, q), one_chip), _sds((g, h, q, p), one_chip))
    assert "tpu_custom_call" in txt


def test_moe_router_compiles(one_chip):
    txt = _compiled_text(
        lambda l: MR.moe_router(l, 8, interpret=False),
        _sds((512, 384), one_chip))                     # kimi-k2 router
    assert "tpu_custom_call" in txt


def test_lm_mega_round_skips_padding_in_place(one_chip):
    """The LM mega-round (smoke widths, 4 workers, fused mix and train,
    a padded bucket of 2) skips a padding row by a conditional, and the
    fleet buffers stay in place through it: no instruction copies a whole
    (N, P) or (N, S) buffer."""
    kern = KernelConfig(backend="pallas", interpret=False)
    cfg = dataclasses.replace(R.get_smoke_config("smollm-135m"),
                              kernels=kern)
    n, h, k, b, s = 4, 4, 2, 2, 256
    opt = LW._cached_optimizer("adam", 1e-3)
    params = jax.eval_shape(
        lambda: R.init_params(cfg, jax.random.PRNGKey(0))[0])

    def stacked(tree):
        return FS.spec_of(jax.tree.map(
            lambda l: jax.ShapeDtypeStruct((n,) + l.shape, l.dtype), tree))

    spec = FS.FleetSpec(params=stacked(params),
                        opt=stacked(jax.eval_shape(opt.init, params)))
    p, o = spec.params.n_params, spec.opt.n_params
    mega = LW.LMEngine(cfg, opt, spec, kernels=kern)._mega(
        col_sparse=False, fuse=True)
    txt = mega.lower(
        _sds((n, p), one_chip), _sds((n, o), one_chip),
        _sds((h, k, n), one_chip), _sds((h, 3 * k), one_chip, jnp.int32),
        _sds((h, k, b, s), one_chip, jnp.int32),
        _sds((h, k, b, s), one_chip, jnp.int32)).compile().as_text()
    assert " conditional(" in txt
    whole = re.compile(rf"= f32\[{n},({p}|{o})\]\S* (\S+?)\(")
    ops = {m.group(2) for m in map(whole.search, txt.splitlines()) if m}
    assert ops and not any(op.startswith("copy") for op in ops), ops


def test_mamba2_mega_round_fits_one_chip(one_chip, tmp_path):
    """The LM mega-round of the ``lm-mamba2l4-n2`` cell, at Mamba-2 2.7B's
    published widths (4 layers, an eighth of the vocabulary): 2 workers
    with Adam, a fused mix-and-train bucket of 2, batch 1 x seq 4096 (16
    SSD chunks), 4 rounds a dispatch.  It compiles for a v5e with the SSD
    forward in the ``dystop_ssd_chunk`` kernel, and the compiler's memory
    report (its own account of the program's HBM, the one it refuses a
    program by) fits the v5e's 15.75 GiB: 14.74 GiB, of it 3.96 GiB the
    resident fleet.  ``memory_analysis()`` reads more (4.25e9 B of
    arguments and 12.95e9 B of temporaries), and the chip's measured peak
    less (PERF.md)."""
    from repro.configs.base import ModelConfig, SSMConfig
    kern = KernelConfig(backend="pallas", interpret=False)
    cfg = ModelConfig(
        arch_id="mamba2-2.7b-l4", family="ssm", n_layers=4, d_model=2560,
        n_heads=1, n_kv_heads=1, d_ff=0, vocab_size=6286,
        ssm=SSMConfig(d_state=128, head_dim=64, expand=2, chunk_size=256,
                      conv_width=4),
        norm_eps=1e-5, tie_embeddings=True, scale_embeddings=False,
        kernels=kern)
    n, h, k, b, s = 2, 4, 2, 1, 4096
    opt = LW._cached_optimizer("adam", 1e-3)
    params = jax.eval_shape(
        lambda: R.init_params(cfg, jax.random.PRNGKey(0))[0])

    def stacked(tree):
        return FS.spec_of(jax.tree.map(
            lambda l: jax.ShapeDtypeStruct((n,) + l.shape, l.dtype), tree))

    spec = FS.FleetSpec(params=stacked(params),
                        opt=stacked(jax.eval_shape(opt.init, params)))
    p, o = spec.params.n_params, spec.opt.n_params
    mega = LW.LMEngine(cfg, opt, spec, kernels=kern)._mega(
        col_sparse=False, fuse=True)
    compiled = mega.lower(
        _sds((n, p), one_chip), _sds((n, o), one_chip),
        _sds((h, k, n), one_chip), _sds((h, 3 * k), one_chip, jnp.int32),
        _sds((h, k, b, s), one_chip, jnp.int32),
        _sds((h, k, b, s), one_chip, jnp.int32)).compile(
            compiler_options={"xla_dump_to": str(tmp_path)})
    assert "dystop_ssd_chunk" in compiled.as_text()
    report, = tmp_path.glob("*jit_mega*memory-usage-report.txt")
    used = int(re.search(r"Total bytes used: (\d+)",
                         report.read_text()).group(1))
    assert 4 * (p + o) * n <= used <= 15.75 * 2**30, used
