"""Per-kernel correctness: Pallas (interpret=True) vs the pure-jnp oracles,
swept over shapes and dtypes (+ hypothesis for the aggregation kernel)."""
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import aggregate as AGG
from repro.kernels import flash_attention as FA
from repro.kernels import fused_sgd as FSGD
from repro.kernels import moe_router as MR
from repro.kernels import ops as K
from repro.kernels import ref as REF
from repro.kernels import ssd_chunk as SC
from repro.kernels.config import resolve_interpret


# --------------------------------------------------------------------------- #
# one interpret policy
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("fn", [
    AGG.aggregate, AGG.aggregate_rows, AGG.aggregate_rows_cols,
    AGG.aggregate_rows_sharded_kernel, AGG.aggregate_rows_cols_sharded_kernel,
    FSGD.fused_sgd, FSGD.fused_sgd_sharded, FA.flash_attention, SC.ssd_chunk,
    MR.moe_router, K.flash_attention, K.moe_router, K.ssd_chunk])
def test_kernel_interpret_defaults_to_auto(fn):
    """No kernel entry point defaults to the interpreter: ``"auto"`` compiles
    on a TPU backend and interprets elsewhere (``resolve_interpret``)."""
    assert inspect.signature(fn).parameters["interpret"].default == "auto"


def test_resolve_interpret_policy():
    assert resolve_interpret("auto") == (jax.default_backend() != "tpu")
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False


# --------------------------------------------------------------------------- #
# aggregate
# --------------------------------------------------------------------------- #


@settings(max_examples=25, deadline=None)
@given(n=st.integers(1, 40), p=st.integers(1, 700),
       p_blk=st.sampled_from([128, 256, 512]))
def test_aggregate_matches_ref(n, p, p_blk):
    key = jax.random.PRNGKey(n * 1000 + p)
    k1, k2 = jax.random.split(key)
    W = jax.nn.softmax(jax.random.normal(k1, (n, n)), axis=-1)
    X = jax.random.normal(k2, (n, p))
    out = K.aggregate(W, X, p_blk=p_blk)
    np.testing.assert_allclose(out, REF.aggregate_ref(W, X), rtol=1e-5, atol=1e-5)


def test_aggregate_identity_rows():
    """Inactive workers (identity rows) must come back bit-stable."""
    n, p = 8, 300
    W = np.eye(n, dtype=np.float32)
    W[0] = np.full(n, 1.0 / n)
    X = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (n, p)))
    out = np.asarray(K.aggregate(jnp.asarray(W), jnp.asarray(X)))
    np.testing.assert_allclose(out[1:], X[1:], rtol=1e-6)
    np.testing.assert_allclose(out[0], X.mean(0), rtol=1e-5, atol=1e-6)


def _panel_grid(fn, *args):
    """The grid of the one ``pallas_call`` in ``fn``'s jaxpr."""
    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                return eqn.params["grid_mapping"].grid
            for v in eqn.params.values():
                sub = v if hasattr(v, "eqns") else getattr(v, "jaxpr", None)
                if sub is not None and (g := find(sub)) is not None:
                    return g
        return None
    return find(jax.make_jaxpr(fn)(*args).jaxpr)


@pytest.mark.parametrize("p", [1, 127, 129, 70_001])
@pytest.mark.parametrize("k,u", [(1, 1), (2, 2), (2, 4), (4, 4), (8, 8),
                                 (16, 64)])
def test_panel_schedule_matches_ref(k, u, p):
    """The derived-width schedule, both bodies (VPU for u < 8, MXU
    otherwise) and a ragged last panel: column-sparse Eq. 4 against the
    oracle, and an identity row comes back bit for bit."""
    n = u + 3
    kw, kx, kc = jax.random.split(jax.random.PRNGKey(k * 100 + u), 3)
    W = np.array(jax.nn.softmax(jax.random.normal(kw, (k, u)), axis=-1))
    W[0] = np.eye(u, dtype=np.float32)[u - 1]          # an identity row
    X = jax.random.normal(kx, (n, p))
    cids = jax.random.permutation(kc, n)[:u].astype(jnp.int32)
    assert AGG.vpu_body(u) == (u < 8)
    out = np.asarray(K.aggregate_rows_cols(jnp.asarray(W), cids, X))
    ref = np.asarray(REF.aggregate_rows_cols_ref(jnp.asarray(W), cids, X))
    np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(out[0], np.asarray(X)[int(cids[u - 1])])


@pytest.mark.parametrize("k,u,p,steps", [
    (2, 4, 134_515_008, 2_053),     # smollm-135m fleet of 4: u 4, k 2
    (2, 2, 177_252_800, 2_705),     # mamba2-2.7b (4 layers) fleet of 2
    (8, 64, 6_922, 1),              # the sim MLP at its bucket floor
    (100, 100, 6_922, 2),           # the sim's dense N = 100 mix
    (1, 1, 1, 1),
    (16, 64, 70_001, 6),
])
def test_panel_width_rule(k, u, p, steps):
    """The derived width: a multiple of 128 lanes, its double-buffered X and
    Y blocks (rows rounded up to 8 sublanes) within the VMEM budget, the
    widest such, no wider than P rounded up to 128; an explicit width still
    pins the grid."""
    w = AGG.panel_width(k, u, p)
    per_lane = (-(-u // 8) * 8 + -(-k // 8) * 8) * 4 * 2
    lanes = -(-p // 128) * 128
    assert w % 128 == 0 and 128 <= w <= lanes
    assert per_lane * w <= AGG.PANEL_VMEM_BYTES
    assert w == lanes or per_lane * (w + 128) > AGG.PANEL_VMEM_BYTES
    assert -(-p // w) == steps
    W, X = jnp.ones((k, u)), jnp.ones((u, min(p, 70_001)))
    cids = jnp.arange(u, dtype=jnp.int32)
    for p_blk, grid in ((None, -(-X.shape[1] // AGG.panel_width(
            k, u, X.shape[1]))), (256, -(-X.shape[1] // 256))):
        assert _panel_grid(lambda w_, c_, x_: AGG.aggregate_rows_cols(
            w_, c_, x_, p_blk=p_blk), W, cids, X) == (grid,)


# --------------------------------------------------------------------------- #
# flash attention
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("s,d", [(64, 32), (128, 64), (192, 64), (256, 128)])
@pytest.mark.parametrize("causal,window,softcap", [
    (True, None, None), (True, 64, None), (True, 48, 50.0), (False, None, None)])
def test_flash_attention_shapes(s, d, causal, window, softcap):
    key = jax.random.PRNGKey(s + d)
    q, k, v = (jax.random.normal(kk, (2, 3, s, d), jnp.float32)
               for kk in jax.random.split(key, 3))
    out = K.flash_attention(q, k, v, causal=causal, window=window, softcap=softcap)
    ref = REF.flash_attention_ref(q, k, v, causal=causal, window=window,
                                  softcap=softcap)
    np.testing.assert_allclose(out, ref, rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 3e-4), (jnp.bfloat16, 3e-2)])
def test_flash_attention_dtypes(dtype, tol):
    key = jax.random.PRNGKey(7)
    q, k, v = (jax.random.normal(kk, (1, 2, 128, 64)).astype(dtype)
               for kk in jax.random.split(key, 3))
    out = K.flash_attention(q, k, v, causal=True)
    ref = REF.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


def test_flash_attention_nonaligned_seq():
    """Sequence not a multiple of the block size exercises padding+masking."""
    key = jax.random.PRNGKey(3)
    q, k, v = (jax.random.normal(kk, (1, 2, 200, 32), jnp.float32)
               for kk in jax.random.split(key, 3))
    out = K.flash_attention(q, k, v, causal=True, blk_q=128, blk_k=128)
    ref = REF.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, rtol=3e-4, atol=3e-4)


def test_flash_attention_sliding_window_locality():
    """Tokens beyond the window must not influence the output."""
    key = jax.random.PRNGKey(11)
    q, k, v = (jax.random.normal(kk, (1, 1, 256, 32), jnp.float32)
               for kk in jax.random.split(key, 3))
    w = 32
    out1 = K.flash_attention(q, k, v, causal=True, window=w)
    # perturb keys/values far outside the window of the last query
    k2 = k.at[:, :, :128, :].set(jax.random.normal(key, (1, 1, 128, 32)))
    v2 = v.at[:, :, :128, :].set(jax.random.normal(key, (1, 1, 128, 32)))
    out2 = K.flash_attention(q, k2, v2, causal=True, window=w)
    np.testing.assert_allclose(out1[:, :, -64:], out2[:, :, -64:], rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------- #
# moe router
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("t,e,k", [(16, 4, 1), (250, 16, 2), (512, 64, 8),
                                   (100, 8, 4)])
def test_moe_router_matches_ref(t, e, k):
    logits = jax.random.normal(jax.random.PRNGKey(t + e + k), (t, e))
    g, i = K.moe_router(logits, k)
    gr, ir = REF.moe_router_ref(logits, k)
    np.testing.assert_allclose(g, gr, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))


def test_moe_router_gates_normalized():
    logits = jax.random.normal(jax.random.PRNGKey(0), (333, 12)) * 3
    g, _ = K.moe_router(logits, 3)
    np.testing.assert_allclose(np.asarray(g).sum(-1), 1.0, rtol=1e-5)


# --------------------------------------------------------------------------- #
# ssd chunk (Mamba-2 intra-chunk dual form)
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("g,h,q,n,p", [(2, 2, 32, 16, 16), (4, 8, 64, 32, 64),
                                       (1, 4, 128, 128, 32)])
def test_ssd_chunk_matches_ref(g, h, q, n, p):
    key = jax.random.PRNGKey(g * 100 + q)
    ks = jax.random.split(key, 4)
    Bc = jax.random.normal(ks[0], (g, q, n))
    Cc = jax.random.normal(ks[1], (g, q, n))
    la = -jnp.cumsum(jax.nn.softplus(jax.random.normal(ks[2], (g, h, q))),
                     axis=-1) * 0.1
    xb = jax.random.normal(ks[3], (g, h, q, p))
    out = K.ssd_chunk(Bc, Cc, la, xb)
    ref = REF.ssd_chunk_ref(Bc, Cc, la, xb)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_ssd_chunk_causality():
    """Future positions inside the chunk must not affect earlier outputs."""
    key = jax.random.PRNGKey(9)
    ks = jax.random.split(key, 4)
    g, h, q, n, p = 1, 2, 32, 16, 16
    Bc = jax.random.normal(ks[0], (g, q, n))
    Cc = jax.random.normal(ks[1], (g, q, n))
    la = -jnp.cumsum(jax.nn.softplus(jax.random.normal(ks[2], (g, h, q))), -1) * 0.1
    xb = jax.random.normal(ks[3], (g, h, q, p))
    out1 = K.ssd_chunk(Bc, Cc, la, xb)
    xb2 = xb.at[:, :, q // 2:, :].set(0.0)
    out2 = K.ssd_chunk(Bc, Cc, la, xb2)
    np.testing.assert_allclose(out1[:, :, : q // 2], out2[:, :, : q // 2],
                               rtol=1e-6, atol=1e-6)
