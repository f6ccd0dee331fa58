"""Pallas TPU kernel: fused MoE router (softmax -> top-k -> renormalize).

The routing control path touches every token once per MoE layer; fusing the
three steps keeps the (blk_t x n_experts) logit panel resident in VMEM instead
of bouncing softmax/top-k/renorm through HBM.  Token blocks are 8-sublane
aligned; the expert axis is small and stays whole in the panel.
"""
from __future__ import annotations

import functools
from typing import Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.config import resolve_interpret


def _router_kernel(logits_ref, gates_ref, ids_ref, *, top_k: int):
    x = logits_ref[...].astype(jnp.float32)                 # (blk_t, E)
    x = x - jnp.max(x, axis=-1, keepdims=True)
    probs = jnp.exp(x)
    probs = probs / jnp.sum(probs, axis=-1, keepdims=True)

    cols = jax.lax.broadcasted_iota(jnp.int32, probs.shape, 1)
    remaining = probs
    gates = []
    ids = []
    for _ in range(top_k):
        g = jnp.max(remaining, axis=-1)                     # (blk_t,)
        a = jnp.argmax(remaining, axis=-1).astype(jnp.int32)
        gates.append(g)
        ids.append(a)
        remaining = jnp.where(cols == a[:, None], -1.0, remaining)

    g = jnp.stack(gates, axis=-1)                           # (blk_t, k)
    g = g / jnp.maximum(jnp.sum(g, axis=-1, keepdims=True), 1e-9)
    gates_ref[...] = g
    ids_ref[...] = jnp.stack(ids, axis=-1)


@functools.partial(jax.jit, static_argnames=("top_k", "blk_t", "interpret"))
def moe_router(logits: jnp.ndarray, top_k: int, blk_t: int = 256,
               interpret: Union[str, bool] = "auto"):
    """logits: (T, E) -> (gates (T, k) f32 renormalized, ids (T, k) i32)."""
    t, e = logits.shape
    blk_t = min(blk_t, t)
    pad = (-t) % blk_t
    lp = jnp.pad(logits, ((0, pad), (0, 0))) if pad else logits
    grid = (lp.shape[0] // blk_t,)
    gates, ids = pl.pallas_call(
        functools.partial(_router_kernel, top_k=top_k),
        grid=grid,
        name="moe_router",
        in_specs=[pl.BlockSpec((blk_t, e), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((blk_t, top_k), lambda i: (i, 0)),
                   pl.BlockSpec((blk_t, top_k), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((lp.shape[0], top_k), jnp.float32),
                   jax.ShapeDtypeStruct((lp.shape[0], top_k), jnp.int32)],
        interpret=resolve_interpret(interpret),
    )(lp)
    return gates[:t], ids[:t]
