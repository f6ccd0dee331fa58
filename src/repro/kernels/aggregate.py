"""Pallas TPU kernel: staleness-weighted model aggregation (paper Eq. 4).

The DFL simulation's per-round hot spot is ``Y = W @ X`` where ``W`` is the
(N_workers x N_workers) row-stochastic mixing matrix and ``X`` stacks all
worker models as (N_workers, P) flat parameters — P is tens of millions while
N is ~100, so this is a skinny matmul that XLA handles poorly when fused into
the surrounding pytree traffic.

TPU-native tiling: W is tiny and stays resident whole (VMEM for the MXU
body, SMEM for the VPU body); X/Y stream through VMEM in (·, p_blk) column
panels.  The schedule takes its width and its body from the operand shapes
(``_panel_matmul``): the panel is as wide as a fixed VMEM budget for the
double-buffered X and Y blocks allows (``panel_width``), so a skinny
contraction over P ~ 1e8 parameters is a few thousand grid steps, not
hundreds of thousands of fixed per-step costs; and a contraction narrower
than one f32 sublane tile (u < 8, the LM fleets' buckets) runs on the VPU
as k·u scalar-times-row multiply-adds, W's scalars in SMEM (``vpu_body``),
instead of an MXU dot that would use at most u/128 of the systolic array's
depth.  The VPU body is f32 throughout; the MXU body's f32 ``jnp.dot`` runs
at the default precision.

Sparse variant: rows of W are identity for workers that neither activated nor
received a push this round (MATCHA's sparse-mixing insight), so the dense
O(N^2 P) product collapses to the k gathered non-identity rows — the
``(k, N) @ (N, P)`` skinny matmul of ``aggregate_rows`` — and a scatter back
into the model buffer.

Column-sparse variant: each mixing row also has at most max_neighbors+1
nonzero COLUMNS (an activated worker pulls from a bounded neighborhood plus
itself), so the k rows jointly touch only the union of their nonzero columns
— u ≤ k·(max_neighbors+1) worker models.  ``aggregate_rows_cols`` gathers
that (u, P) slab once and contracts ``(k, u) @ (u, P)``, cutting the mix
flops (and the buffer read traffic) from k·N·P to k·u·P.  The host side
(``core.aggregation.mixing_rows_cols``) computes the union, buckets u to
power-of-two shapes, and zeroes the padding columns of W_sub so padded
column ids contribute exactly 0.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec

from repro.dfl.flat_state import take_rows
from repro.kernels.config import resolve_interpret
from repro.sharding.rules import shard_map

LANES = 128                   # f32 vreg lanes: the panel width's unit
SUBLANES = 8                  # f32 vreg sublanes: a block's rows pad to this
PANEL_VMEM_BYTES = 8 << 20    # double-buffered X + Y blocks; v5e scopes 16 MiB
VPU_SLICE = 2048              # lanes per step of the VPU body's loop


def vpu_body(u: int) -> bool:
    """Whether a contraction of width ``u`` runs the VPU body: narrower than
    one f32 sublane tile, where the MXU dot would idle nearly all of its
    depth."""
    return u < SUBLANES


def panel_width(k: int, u: int, p: int) -> int:
    """The derived panel width of a (k, u) @ (u, p) contraction: the widest
    multiple of 128 lanes whose double-buffered (u, width) X and (k, width)
    Y blocks, rows rounded up to 8 sublanes, fit ``PANEL_VMEM_BYTES``, and
    no wider than p rounded up to 128."""
    rows = -(-u // SUBLANES) * SUBLANES + -(-k // SUBLANES) * SUBLANES
    fit = PANEL_VMEM_BYTES // (rows * 4 * 2) // LANES * LANES
    return max(LANES, min(fit, -(-p // LANES) * LANES))


def _mxu_kernel(w_ref, x_ref, o_ref):
    o_ref[...] = jnp.dot(w_ref[...], x_ref[...],
                         preferred_element_type=jnp.float32)


def _vpu_kernel(w_ref, x_ref, o_ref):
    """Y[i] = sum_j W[i, j] X[j] as scalar-times-row multiply-adds in f32,
    W's scalars read from SMEM, one lane slice at a time so the body stays
    small however wide the panel."""
    k, u = w_ref.shape
    width = o_ref.shape[1]
    sl = math.gcd(width, VPU_SLICE)

    def step(s, carry):
        cols = pl.ds(pl.multiple_of(s * sl, sl), sl)
        for i in range(k):
            acc = w_ref[i, 0] * x_ref[0:1, cols]
            for j in range(1, u):
                acc = acc + w_ref[i, j] * x_ref[j:j + 1, cols]
            o_ref[i:i + 1, cols] = acc
        return carry

    jax.lax.fori_loop(0, width // sl, step, 0)


@functools.partial(jax.jit, static_argnames=("p_blk", "interpret"))
def aggregate(W: jnp.ndarray, X: jnp.ndarray, p_blk: Optional[int] = None,
              interpret: Union[str, bool] = "auto") -> jnp.ndarray:
    """Y = W @ X.  W: (N, N) f32; X: (N, P) f32 -> (N, P) f32."""
    n, p = X.shape
    assert W.shape == (n, n), (W.shape, X.shape)
    return _panel_matmul(W, X, p_blk, resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("p_blk", "interpret"))
def aggregate_rows(W_rows: jnp.ndarray, X: jnp.ndarray,
                   p_blk: Optional[int] = None,
                   interpret: Union[str, bool] = "auto") -> jnp.ndarray:
    """Active-row sparse path: Y_rows = W_rows @ X.

    W_rows: (k, N) — the k gathered non-identity rows of the mixing matrix;
    X: (N, P) flat model buffer.  Returns the (k, P) mixed rows; the caller
    scatters them back (``X.at[row_ids].set(...)``).  Same VMEM panel schedule
    as ``aggregate`` with the resident operand now (k, N).
    """
    k, n = W_rows.shape
    assert X.shape[0] == n, (W_rows.shape, X.shape)
    return _panel_matmul(W_rows, X, p_blk, resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("p_blk", "interpret"))
def aggregate_rows_cols(W_sub: jnp.ndarray, col_ids: jnp.ndarray,
                        X: jnp.ndarray, p_blk: Optional[int] = None,
                        interpret: Union[str, bool] = "auto") -> jnp.ndarray:
    """Column-sparse Eq. 4: Y_rows = W_sub @ X[col_ids].

    W_sub: (k, u) — the k gathered non-identity rows of the mixing matrix
    restricted to the u-column union of their nonzero columns; col_ids: (u,)
    i32 union column indices (padding entries may repeat an index, but the
    host zeroes the matching W_sub columns so they contribute exactly 0);
    X: (N, P) flat model buffer.  The (u, P) slab is gathered ONCE, then the
    same VMEM panel schedule as ``aggregate_rows`` contracts (k, u) @ (u, P)
    — k·u·P flops instead of k·N·P, with u ≤ k·(max_neighbors+1).  Returns
    the (k, P) mixed rows; the caller scatters them back.
    """
    k, u = W_sub.shape
    assert col_ids.shape == (u,), (W_sub.shape, col_ids.shape)
    slab = take_rows(X, col_ids)                # (u, P) gather, once
    return _panel_matmul(W_sub, slab, p_blk, resolve_interpret(interpret))


# --------------------------------------------------------------------------- #
# mesh-aware twins: the sharded fleet engine's Eq. 4 contractions
# --------------------------------------------------------------------------- #
#
# When the flat buffer is row-partitioned over the 1-D fleet mesh
# (``sharding.rules.FleetSharding``) the contraction comes in two lowerings:
# the jnp + sharding-constraint twins below (GSPMD emits the collectives) and
# the ``*_sharded_kernel`` shard_map twins further down, which run the SAME
# Pallas panel schedule per shard and spell the collectives explicitly
# (``pallas_call`` cannot be auto-partitioned, so the mesh composition is a
# manual SPMD program).  All twins are value-exact against their dense
# oracles — only reduction order differs.


def aggregate_rows_sharded(W_rows: jnp.ndarray, X: jnp.ndarray,
                           shd) -> jnp.ndarray:
    """Row-sparse Eq. 4 over a row-sharded buffer: Y_rows = W_rows @ X.

    The contraction axis IS the sharded axis, so each shard contracts its
    resident ``(k, N_s) @ (N_s, P)`` slab and GSPMD finishes with one psum
    (all-reduce) over the fleet axis; the replicated constraint on the output
    pins that lowering.  ``shd`` is a ``sharding.rules.FleetSharding``.
    """
    y = W_rows.astype(jnp.float32) @ X
    return jax.lax.with_sharding_constraint(y, shd.replicated())


def aggregate_rows_cols_sharded(W_sub: jnp.ndarray, col_ids: jnp.ndarray,
                                X: jnp.ndarray, shd) -> jnp.ndarray:
    """Column-sparse Eq. 4 over a row-sharded buffer.

    The union gather ``X[col_ids]`` is constrained replicated — an all_gather
    of ONLY the u <= k*(max_neighbors+1) union rows, not the whole (N, P)
    buffer — and the ``(k, u) @ (u, P)`` contraction is constrained to split
    its k OUTPUT rows over the fleet axis (when k divides evenly), so each
    shard computes the mixed rows it will scatter back locally.  This is the
    cross-shard traffic floor of one DySTop round: u rows in, k/S rows of
    compute per shard, zero collective on the scatter for home rows.
    """
    slab = jax.lax.with_sharding_constraint(take_rows(X, col_ids, shd),
                                            shd.replicated())
    y = W_sub.astype(jnp.float32) @ slab
    return jax.lax.with_sharding_constraint(y, shd.for_rows(W_sub.shape[0]))


def aggregate_rows_sharded_kernel(W_rows: jnp.ndarray, X: jnp.ndarray,
                                  shd, p_blk: Optional[int] = None,
                                  interpret: Union[str, bool] = "auto"
                                  ) -> jnp.ndarray:
    """shard_map Pallas twin of ``aggregate_rows_sharded``.

    The contraction axis is the sharded axis, so the SPMD program is the
    textbook inner-product split: each shard runs the VMEM panel schedule on
    its resident ``(k, N_s) @ (N_s, P)`` slab of the row-partitioned buffer,
    then one ``psum`` over the fleet axis completes Eq. 4 and replicates the
    (k, P) mixed rows.  ``check_vma=False``: under JAX 0.9 the check needs
    every ``pallas_call`` output shape to declare its varying axes, which a
    kernel written for one device does not; the psum makes the replication
    claim true by construction.
    """
    interp = resolve_interpret(interpret)
    ax = shd.axis

    def fn(w_loc, x_loc):
        y = _panel_matmul(w_loc, x_loc, p_blk, interp)
        return jax.lax.psum(y, ax)

    y = shard_map(fn, mesh=shd.mesh,
                  in_specs=(PartitionSpec(None, ax), PartitionSpec(ax, None)),
                  out_specs=PartitionSpec(), check_vma=False)(
        W_rows.astype(jnp.float32), X.astype(jnp.float32))
    return jax.lax.with_sharding_constraint(y, shd.replicated())


def aggregate_rows_cols_sharded_kernel(W_sub: jnp.ndarray,
                                       col_ids: jnp.ndarray, X: jnp.ndarray,
                                       shd, p_blk: Optional[int] = None,
                                       interpret: Union[str, bool] = "auto"
                                       ) -> jnp.ndarray:
    """shard_map Pallas twin of ``aggregate_rows_cols_sharded``.

    Collective schedule (mirrors the GSPMD twin's traffic floor): the union
    gather is ``take_rows`` over the row-sharded buffer — each shard slices
    the union rows it holds, zeroes the rest, and one ``psum`` assembles the
    replicated (u, P) slab from exactly u rows of cross-shard traffic.  The
    ``(k, u) @ (u, P)`` panel contraction then runs per shard: over the k/S
    home output rows when k divides the mesh (the scatter back is
    collective-free), else replicated whole, matching
    ``FleetSharding.for_rows``.  ``check_vma=False`` on the panel's
    shard_map, for the reason given in ``aggregate_rows_sharded_kernel``.
    """
    interp = resolve_interpret(interpret)
    k = W_sub.shape[0]
    slab = take_rows(X, col_ids, shd).astype(jnp.float32)
    row_spec = (PartitionSpec(shd.axis, None) if k and k % shd.n_shards == 0
                else PartitionSpec())
    y = shard_map(lambda w_loc, s: _panel_matmul(w_loc, s, p_blk, interp),
                  mesh=shd.mesh, in_specs=(row_spec, PartitionSpec()),
                  out_specs=row_spec, check_vma=False)(
        W_sub.astype(jnp.float32), slab)
    return jax.lax.with_sharding_constraint(y, shd.for_rows(k))


def _panel_matmul(W: jnp.ndarray, X: jnp.ndarray, p_blk: Optional[int],
                  interpret: bool) -> jnp.ndarray:
    """(k, N) @ (N, P) with W resident and X/Y in (·, p_blk) VMEM panels.

    The schedule follows the static shapes: ``p_blk=None`` takes the width
    from ``panel_width`` (an int pins it), and ``vpu_body(N)`` picks the VPU
    body over the MXU dot.  The grid covers P with ``cdiv`` panels and no
    padding copy: output columns depend only on their own X column, so the
    out-of-bounds lanes of a ragged last panel never reach a stored column."""
    k, n = W.shape
    p = X.shape[1]
    if p_blk is None:
        p_blk = panel_width(k, n, p)
    if vpu_body(n):
        kernel, w_spec = _vpu_kernel, pl.BlockSpec(memory_space=pltpu.SMEM)
    else:
        kernel, w_spec = _mxu_kernel, pl.BlockSpec((k, n), lambda i: (0, 0))
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(p, p_blk),),
        name="dystop_aggregate_panel",
        in_specs=[
            w_spec,                                          # W resident
            pl.BlockSpec((n, p_blk), lambda i: (0, i)),      # X panel
        ],
        out_specs=pl.BlockSpec((k, p_blk), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((k, p), jnp.float32),
        interpret=interpret,
    )(W.astype(jnp.float32), X.astype(jnp.float32))
