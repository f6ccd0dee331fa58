"""jit'd public wrappers for the Pallas kernels.

On TPU the kernels compile natively; on a CPU backend they execute in
``interpret=True`` mode (the kernel body runs step-by-step with the same
block schedule), which is how the correctness tests validate them.  The
interpret policy lives in ``kernels.config.resolve_interpret``: every kernel
and wrapper takes ``interpret="auto"`` (interpret everywhere except a real
TPU backend) or an explicit boolean.

The ``*_diff`` factories at the bottom are the model-plane entry points:
``jax.custom_vjp`` wrappers whose forward runs the Pallas kernel and whose
backward is the ``jax.vjp`` of the matching ``kernels.ref`` oracle — the
kernels ship forward-only, and in interpret mode forward and oracle agree to
f32 tolerance, so the pullback of the oracle is the pullback of the kernel.
Factories are ``lru_cache``d on their static params so each (config, shape)
combination builds its ``custom_vjp`` object once and jit caches stay warm.
"""
from __future__ import annotations

import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp

from repro.kernels import aggregate as _agg
from repro.kernels import flash_attention as _fa
from repro.kernels import moe_router as _mr
from repro.kernels import ref as _ref
from repro.kernels import ssd_chunk as _sc
from repro.kernels.config import KernelConfig

def aggregate(W: jnp.ndarray, X: jnp.ndarray,
              p_blk: Optional[int] = None) -> jnp.ndarray:
    """Y = W @ X (mixing-matrix model aggregation, paper Eq. 4)."""
    return _agg.aggregate(W, X, p_blk=p_blk)


def aggregate_rows(W_rows: jnp.ndarray, X: jnp.ndarray,
                   p_blk: Optional[int] = None) -> jnp.ndarray:
    """Sparse Eq. 4: the k gathered non-identity rows of W times the buffer."""
    return _agg.aggregate_rows(W_rows, X, p_blk=p_blk)


def aggregate_rows_cols(W_sub: jnp.ndarray, col_ids: jnp.ndarray,
                        X: jnp.ndarray, p_blk: Optional[int] = None) -> jnp.ndarray:
    """Column-sparse Eq. 4: gather the u-column union slab once, then
    contract ``(k, u) @ (u, P)`` (see ``kernels.aggregate``)."""
    return _agg.aggregate_rows_cols(W_sub, col_ids, X, p_blk=p_blk)


def aggregate_rows_sharded(W_rows: jnp.ndarray, X: jnp.ndarray, shd,
                           p_blk: Optional[int] = None) -> jnp.ndarray:
    """Per-shard ``shard_map`` panel schedule over a row-sharded buffer."""
    return _agg.aggregate_rows_sharded_kernel(W_rows, X, shd, p_blk=p_blk)


def aggregate_rows_cols_sharded(W_sub: jnp.ndarray, col_ids: jnp.ndarray,
                                X: jnp.ndarray, shd,
                                p_blk: Optional[int] = None) -> jnp.ndarray:
    """Column-sparse shard_map twin (masked union gather + psum slab)."""
    return _agg.aggregate_rows_cols_sharded_kernel(W_sub, col_ids, X, shd,
                                                   p_blk=p_blk)


def flash_attention(q, k, v, causal: bool = True, window: Optional[int] = None,
                    softcap: Optional[float] = None, blk_q: int = 128,
                    blk_k: int = 128,
                    interpret: Union[str, bool] = "auto") -> jnp.ndarray:
    """Blockwise attention (B, H, S, D); kv heads pre-broadcast for GQA."""
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, blk_q=blk_q, blk_k=blk_k,
                               interpret=interpret)


def moe_router(logits, top_k: int, blk_t: int = 256,
               interpret: Union[str, bool] = "auto"):
    """Fused softmax -> top-k -> renormalize."""
    return _mr.moe_router(logits, top_k, blk_t=blk_t,
                          interpret=interpret)


def ssd_chunk(Bc, Cc, cum_la, xbar, interpret: Union[str, bool] = "auto"):
    """Fused Mamba-2 intra-chunk dual form (scores stay in VMEM)."""
    return _sc.ssd_chunk(Bc, Cc, cum_la, xbar,
                         interpret=interpret)


# --------------------------------------------------------------------------- #
# differentiable model-plane wrappers (Pallas forward, reference backward)
# --------------------------------------------------------------------------- #


@functools.lru_cache(maxsize=None)
def _flash_attention_diff(causal: bool, window: Optional[int],
                          softcap: Optional[float], blk_q: int, blk_k: int,
                          interpret: bool):
    @jax.custom_vjp
    def fa(q, k, v):
        return _fa.flash_attention(q, k, v, causal=causal, window=window,
                                   softcap=softcap, blk_q=blk_q, blk_k=blk_k,
                                   interpret=interpret)

    def fwd(q, k, v):
        return fa(q, k, v), (q, k, v)

    def bwd(res, g):
        q, k, v = res
        _, pullback = jax.vjp(
            lambda q_, k_, v_: _ref.flash_attention_ref(
                q_, k_, v_, causal=causal, window=window, softcap=softcap),
            q, k, v)
        return pullback(g)

    fa.defvjp(fwd, bwd)
    return fa


def flash_attention_diff(q, k, v, kernels: KernelConfig,
                         causal: bool = True, window: Optional[int] = None,
                         softcap: Optional[float] = None) -> jnp.ndarray:
    """Differentiable flash attention per a ``KernelConfig``."""
    fa = _flash_attention_diff(causal, window, softcap, kernels.attn_blk_q,
                               kernels.attn_blk_k,
                               kernels.resolve_interpret())
    return fa(q, k, v)


@functools.lru_cache(maxsize=None)
def _ssd_chunk_diff(interpret: bool):
    @jax.custom_vjp
    def ssd(Bc, Cc, cum_la, xbar):
        return _sc.ssd_chunk(Bc, Cc, cum_la, xbar, interpret=interpret)

    def fwd(Bc, Cc, cum_la, xbar):
        return ssd(Bc, Cc, cum_la, xbar), (Bc, Cc, cum_la, xbar)

    def bwd(res, g):
        _, pullback = jax.vjp(_ref.ssd_chunk_ref, *res)
        return pullback(g)

    ssd.defvjp(fwd, bwd)
    return ssd


def ssd_chunk_diff(Bc, Cc, cum_la, xbar, kernels: KernelConfig):
    """Differentiable intra-chunk SSD per a ``KernelConfig``."""
    return _ssd_chunk_diff(kernels.resolve_interpret())(Bc, Cc, cum_la, xbar)


@functools.lru_cache(maxsize=None)
def _moe_router_diff(top_k: int, blk_t: int, interpret: bool):
    # gates only: an int output of a custom_vjp would carry a concrete float0
    # tangent into the integer slot arithmetic downstream (stop_gradient is a
    # no-op on int tracers), so the expert ids never pass through AD at all
    @jax.custom_vjp
    def route(logits):
        gates, _ = _mr.moe_router(logits, top_k, blk_t=blk_t,
                                  interpret=interpret)
        return gates

    def fwd(logits):
        return route(logits), (logits,)

    def bwd(res, g_gates):
        (logits,) = res
        _, pullback = jax.vjp(
            lambda l: _ref.moe_router_ref(l, top_k)[0], logits)
        return pullback(g_gates)

    route.defvjp(fwd, bwd)
    return route


def moe_router_diff(logits, top_k: int, kernels: KernelConfig):
    """Differentiable router per a ``KernelConfig`` (ids are int, no grad)."""
    blk_t = kernels.moe_blk_t
    interp = kernels.resolve_interpret()
    gates = _moe_router_diff(top_k, blk_t, interp)(logits)
    _, ids = _mr.moe_router(jax.lax.stop_gradient(logits), top_k,
                            blk_t=blk_t, interpret=interp)
    return gates, ids
