"""Pallas TPU kernel: blockwise (flash) attention with causal masking,
sliding-window masking, and gemma-style logit softcapping.

This is the TPU adaptation of the framework's attention hot-spot: the online-
softmax accumulator lives in VMEM scratch and the kv-block axis is the
minor-most grid dimension, so each (batch, head, q-block) revisits its
accumulators across kv steps — the canonical TPU flash schedule.  MXU tiles
are (blk_q x head_dim) @ (head_dim x blk_k) with 128-aligned blocks.

The lowering path on the CPU dry-runs is XLA einsum attention (Pallas does not
lower on the host backend); both share the ``ref.py`` oracle.
"""
from __future__ import annotations

import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.config import resolve_interpret

_NEG_INF = -1e30
_LANES = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: Optional[int],
                  softcap: Optional[float], blk_q: int, blk_k: int,
                  n_kv_blocks: int, seq_len: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (blk_q, d)
    k = k_ref[0, 0].astype(jnp.float32)                  # (blk_k, d)
    v = v_ref[0, 0].astype(jnp.float32)                  # (blk_k, d)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)   # (blk_q, blk_k)
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap

    rows = qi * blk_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = ki * blk_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = cols < seq_len
    if causal:
        mask = mask & (cols <= rows)
    if window is not None:
        mask = mask & ((rows - cols) < window)
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_scr[:, 0]                                  # (blk_q,)
    l_prev = l_scr[:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    # fully-masked rows (early causal blocks): keep accumulators at zero
    p = jnp.where((s <= _NEG_INF)[:, :], 0.0, p)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        l = l_scr[:, 0]
        o_ref[0, 0, :, :] = (acc_scr[...] /
                             jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "blk_q", "blk_k", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    blk_q: int = 128, blk_k: int = 128,
                    interpret: Union[str, bool] = "auto") -> jnp.ndarray:
    """q, k, v: (B, H, S, D) -> (B, H, S, D).  GQA callers broadcast kv heads."""
    b, h, s, d = q.shape
    assert k.shape == v.shape == (b, h, s, d)
    blk_q = min(blk_q, s)
    blk_k = min(blk_k, s)
    pad_q = (-s) % blk_q
    pad_k = (-s) % blk_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0))) if pad_k else v
    nq = qp.shape[2] // blk_q
    nk = kp.shape[2] // blk_k
    grid = (b, h, nq, nk)

    kernel = functools.partial(
        _flash_kernel, scale=d ** -0.5, causal=causal, window=window,
        softcap=softcap, blk_q=blk_q, blk_k=blk_k, n_kv_blocks=nk, seq_len=s)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        name="flash_attention",
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, d), lambda b_, h_, q_, k_: (b_, h_, q_, 0)),
            pl.BlockSpec((1, 1, blk_k, d), lambda b_, h_, q_, k_: (b_, h_, k_, 0)),
            pl.BlockSpec((1, 1, blk_k, d), lambda b_, h_, q_, k_: (b_, h_, k_, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, d), lambda b_, h_, q_, k_: (b_, h_, q_, 0)),
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, _LANES), jnp.float32),   # running max m
            pltpu.VMEM((blk_q, _LANES), jnp.float32),   # running denom l
            pltpu.VMEM((blk_q, d), jnp.float32),        # output accumulator
        ],
        interpret=resolve_interpret(interpret),
    )(qp, kp, vp)
    return out[:, :, :s, :]
