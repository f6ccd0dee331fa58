"""Pure-jnp oracles for every Pallas kernel (the source of truth in tests)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def aggregate_ref(W: jnp.ndarray, X: jnp.ndarray) -> jnp.ndarray:
    return (W.astype(jnp.float32) @ X.astype(jnp.float32))


def aggregate_rows_cols_ref(W_sub: jnp.ndarray, col_ids: jnp.ndarray,
                            X: jnp.ndarray) -> jnp.ndarray:
    """Column-sparse Eq. 4 oracle: gather the union slab, plain matmul."""
    return W_sub.astype(jnp.float32) @ X.astype(jnp.float32)[col_ids]


def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        causal: bool = True, window: Optional[int] = None,
                        softcap: Optional[float] = None) -> jnp.ndarray:
    b, h, s, d = q.shape
    scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * d ** -0.5
    if softcap is not None:
        scores = jnp.tanh(scores / softcap) * softcap
    rows = jnp.arange(s)[:, None]
    cols = jnp.arange(s)[None, :]
    mask = jnp.ones((s, s), bool)
    if causal:
        mask = mask & (cols <= rows)
    if window is not None:
        mask = mask & ((rows - cols) < window)
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs,
                      v.astype(jnp.float32)).astype(q.dtype)


def moe_router_ref(logits: jnp.ndarray, top_k: int):
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, ids = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    return gates, ids.astype(jnp.int32)


def ssd_chunk_ref(Bc, Cc, cum_la, xbar):
    """Oracle for the intra-chunk SSD dual form (see models/ssm.py)."""
    scores = jnp.einsum("gqn,gkn->gqk", Cc.astype(jnp.float32),
                        Bc.astype(jnp.float32))
    decay = cum_la[:, :, :, None] - cum_la[:, :, None, :]      # (G,H,Q,Q)
    q = scores.shape[-1]
    causal = jnp.tril(jnp.ones((q, q), bool))
    # mask before the exp: above the diagonal the decay is positive and can
    # overflow f32, and inf times the masked cotangent's 0 is a NaN
    l_mat = jnp.exp(jnp.where(causal[None, None], decay, -jnp.inf))
    return jnp.einsum("gqk,ghqk,ghkp->ghqp", scores, l_mat,
                      xbar.astype(jnp.float32))
