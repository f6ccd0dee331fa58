"""Pallas TPU kernel: Mamba-2 SSD intra-chunk dual form.

The quadratic-in-chunk half of the SSD algorithm (models/ssm.py) is the
compute hot spot of the attention-free architecture:

    y[q] = sum_{t<=q} (C_q . B_t) * exp(cum_a[q] - cum_a[t]) * xbar[t]

Per (batch*head-group, chunk) grid cell the kernel fuses:
  scores = C @ B^T                       (Q x Q on the MXU)
  scores *= causal decay exp(la_q-la_t)  (VPU, in VMEM)
  y      = scores @ xbar                 (Q x P on the MXU)
so the (Q, Q) score panel never leaves VMEM — the same accumulator-residency
argument as flash attention, applied to the SSD dual form.  Q = chunk size
(<= 256) and P = head_dim keep every tile 128-lane aligned.

Heads share B/C (single group); the per-head decay enters via the cumulative
log-a vector, so the grid is (batch*n_chunks, heads) with B/C indexed by the
chunk only.  The log-a vector ships twice, as a (Q, 1) column and a (1, Q)
row, so the (Q, Q) decay is one broadcast subtraction with no lane-to-sublane
relayout in the kernel, and each block's last two dims are whole.
"""
from __future__ import annotations

import functools
from typing import Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.config import resolve_interpret


def _ssd_chunk_kernel(cb_ref, cc_ref, la_col_ref, la_row_ref, x_ref, o_ref):
    """Blocks: cb/cc (1, Q, N) chunk B/C; la_col (1, 1, Q, 1) and la_row
    (1, 1, 1, Q) cumulative log-a for this head; x (1, 1, Q, P) xbar;
    o (1, 1, Q, P)."""
    C = cc_ref[0].astype(jnp.float32)                       # (Q, N)
    B = cb_ref[0].astype(jnp.float32)                       # (Q, N)
    x = x_ref[0, 0].astype(jnp.float32)                     # (Q, P)

    scores = jax.lax.dot_general(                           # C @ B.T, (Q, Q)
        C, B, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    decay = (la_col_ref[0, 0].astype(jnp.float32)
             - la_row_ref[0, 0].astype(jnp.float32))
    q = scores.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    l_mat = jnp.exp(jnp.where(cols <= rows, decay, -jnp.inf))
    o_ref[0, 0, :, :] = jnp.dot(scores * l_mat, x,
                                preferred_element_type=jnp.float32
                                ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk(Bc: jnp.ndarray, Cc: jnp.ndarray, cum_la: jnp.ndarray,
              xbar: jnp.ndarray,
              interpret: Union[str, bool] = "auto") -> jnp.ndarray:
    """Intra-chunk SSD.

    Bc, Cc:  (batch*n_chunks, Q, N)   chunk B / C projections (shared by heads)
    cum_la:  (batch*n_chunks, H, Q)   per-head cumulative log decay
    xbar:    (batch*n_chunks, H, Q, P) dt-scaled inputs
    returns  (batch*n_chunks, H, Q, P)
    """
    G, Q, N = Bc.shape
    _, H, _, P = xbar.shape
    assert cum_la.shape == (G, H, Q) and xbar.shape[:3] == (G, H, Q)
    grid = (G, H)
    return pl.pallas_call(
        _ssd_chunk_kernel,
        grid=grid,
        name="dystop_ssd_chunk",
        in_specs=[
            pl.BlockSpec((1, Q, N), lambda g, h: (g, 0, 0)),
            pl.BlockSpec((1, Q, N), lambda g, h: (g, 0, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda g, h: (g, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda g, h: (g, h, 0, 0)),
            pl.BlockSpec((1, 1, Q, P), lambda g, h: (g, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, Q, P), lambda g, h: (g, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((G, H, Q, P), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(Bc, Cc, cum_la[..., None], cum_la[..., None, :], xbar)
