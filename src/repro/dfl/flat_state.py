"""Flat (N, P) model-buffer representation for the fused round engine.

The simulation plane keeps all N worker replicas in ONE device-resident
``(N, P)`` f32 buffer instead of a stacked pytree: Eq. 4 mixing becomes a
single skinny matmul over one buffer (the shape the Pallas ``aggregate``
kernel tiles) rather than one dispatch per leaf, and local SGD vmaps over the
buffer rows.  ``FlatSpec`` carries the ravel/unravel metadata
(ravel_pytree-style: static offsets, trailing shapes, dtypes) and is hashable
so it can ride through ``jax.jit`` as a static argument.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.sharding.rules import shard_map


@dataclasses.dataclass(frozen=True)
class FlatSpec:
    """Static ravel/unravel metadata for a stacked pytree.

    Leaves of the source pytree have a leading worker axis (N, *shape); the
    flat buffer concatenates each leaf's trailing dims along axis 1 in
    ``jax.tree.leaves`` order.  Hashable (all-tuple fields + treedef) so it is
    a valid ``jax.jit`` static argument.
    """
    treedef: Any                               # jax PyTreeDef (hashable)
    shapes: Tuple[Tuple[int, ...], ...]        # per-leaf trailing shapes
    dtypes: Tuple[str, ...]                    # per-leaf dtype names
    offsets: Tuple[int, ...]                   # per-leaf start column
    sizes: Tuple[int, ...]                     # per-leaf column count
    n_params: int                              # P = sum(sizes)


def spec_of(stacked: Any) -> FlatSpec:
    """Build the FlatSpec for a stacked pytree (leaves (N, ...))."""
    leaves, treedef = jax.tree.flatten(stacked)
    shapes = tuple(tuple(l.shape[1:]) for l in leaves)
    dtypes = tuple(str(l.dtype) for l in leaves)
    sizes = tuple(int(np.prod(s, dtype=np.int64)) if s else 1 for s in shapes)
    offsets = tuple(int(o) for o in np.cumsum((0,) + sizes)[:-1])
    return FlatSpec(treedef=treedef, shapes=shapes, dtypes=dtypes,
                    offsets=offsets, sizes=sizes, n_params=int(sum(sizes)))


def flatten_stacked(stacked: Any) -> Tuple[jnp.ndarray, FlatSpec]:
    """Stacked pytree (leaves (N, ...)) -> ((N, P) f32 buffer, FlatSpec)."""
    spec = spec_of(stacked)
    leaves = jax.tree.leaves(stacked)
    buf = jnp.concatenate(
        [l.reshape(l.shape[0], -1).astype(jnp.float32) for l in leaves], axis=1)
    return buf, spec


def unflatten(buf: jnp.ndarray, spec: FlatSpec) -> Any:
    """(N, P) buffer -> stacked pytree with the original shapes/dtypes."""
    n = buf.shape[0]
    leaves = [
        buf[:, o:o + s].reshape((n,) + shape).astype(dtype)
        for o, s, shape, dtype in zip(spec.offsets, spec.sizes, spec.shapes,
                                      spec.dtypes)
    ]
    return jax.tree.unflatten(spec.treedef, leaves)


def unravel_row(vec: jnp.ndarray, spec: FlatSpec) -> Any:
    """One worker's (P,) parameter vector -> its single-model pytree.

    Offsets are static, so under jit this is pure slicing/reshaping that XLA
    fuses away — the flat buffer stays the only materialized storage.
    """
    leaves = [
        vec[o:o + s].reshape(shape).astype(dtype)
        for o, s, shape, dtype in zip(spec.offsets, spec.sizes, spec.shapes,
                                      spec.dtypes)
    ]
    return jax.tree.unflatten(spec.treedef, leaves)


def unravel_row_at(buf: jnp.ndarray, r, spec: FlatSpec) -> Any:
    """Row ``r`` (traced) of an (N, P) buffer -> its single-model pytree,
    each leaf sliced from the buffer itself: no (P,) row is gathered first.
    """
    leaves = [
        jax.lax.dynamic_slice(buf, (r, o), (1, s)).reshape(shape).astype(dtype)
        for o, s, shape, dtype in zip(spec.offsets, spec.sizes, spec.shapes,
                                      spec.dtypes)
    ]
    return jax.tree.unflatten(spec.treedef, leaves)


def take_rows(buf: jnp.ndarray, ids: jnp.ndarray, shd=None) -> jnp.ndarray:
    """``buf[ids]`` for a wide (N, P) buffer, as a loop of row slices.

    XLA's TPU gather emits code linear in the row width: gathering rows of
    smollm-135m's (N, 134.5M) buffer takes minutes to compile, while a
    ``fori_loop`` of ``dynamic_slice``s compiles in constant time and copies
    the same values.  ``ids`` must be in range.  With ``shd`` (a row-sharded
    ``sharding.rules.FleetSharding`` buffer) each shard slices the rows it
    holds, zeroes the others, and one ``psum`` assembles the replicated
    (k, P) result — every row has exactly one nonzero term, so the sum is
    the row itself.
    """
    k = ids.shape[0]

    def local(x, rows, acc, valid=None):
        def body(i, acc):
            row = jax.lax.dynamic_slice_in_dim(x, rows[i], 1, 0)
            if valid is not None:
                row = jnp.where(valid[i], row, jnp.zeros_like(row))
            return jax.lax.dynamic_update_slice_in_dim(acc, row, i, 0)
        return jax.lax.fori_loop(0, k, body, acc)

    out = jnp.zeros((k,) + buf.shape[1:], buf.dtype)
    if shd is None:
        return local(buf, ids.astype(jnp.int32), out)

    ax = shd.axis

    def fn(x_loc, ids_rep):
        blk = x_loc.shape[0]
        rows = ids_rep.astype(jnp.int32) - jax.lax.axis_index(ax) * blk
        valid = (rows >= 0) & (rows < blk)
        acc = jax.lax.pcast(out, ax, to="varying")
        return jax.lax.psum(
            local(x_loc, jnp.clip(rows, 0, blk - 1), acc, valid), ax)

    return shard_map(fn, mesh=shd.mesh,
                     in_specs=(PartitionSpec(ax), PartitionSpec()),
                     out_specs=PartitionSpec())(buf, ids)


def weighted_row(buf: jnp.ndarray, alpha: jnp.ndarray) -> jnp.ndarray:
    """Weight-averaged (P,) parameter vector straight from the flat buffer.

    The data-size-weighted global model of paper Eq. 11 is a single
    ``(N,) @ (N, P)`` contraction here — no per-leaf tensordot, no pytree
    materialization; unravel with ``unravel_row`` when a model is needed.
    """
    return alpha.astype(jnp.float32) @ buf


def ravel_row(tree: Any, spec: FlatSpec) -> jnp.ndarray:
    """Single-model pytree -> (P,) f32 vector (inverse of ``unravel_row``)."""
    leaves = jax.tree.leaves(tree)
    return jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])


def nbytes_of(spec: FlatSpec) -> int:
    """Bytes of ONE row's pytree at its original dtypes (Eq. 10 pricing).

    The flat buffer stores f32, but transfer accounting must price the model
    as shipped (bf16 leaves ship at 2 bytes), so size from the spec's dtypes.
    """
    return sum(s * np.dtype(d).itemsize for s, d in zip(spec.sizes, spec.dtypes))


# --------------------------------------------------------------------------- #
# multi-buffer fleets: params + optimizer state resident together
# --------------------------------------------------------------------------- #


@dataclasses.dataclass(frozen=True)
class FleetSpec:
    """Ravel/unravel metadata for a fleet that is resident as TWO flat
    buffers: params ``(N, P)`` and optimizer state ``(N, S)``.

    The LM plane flattens once at fleet init and keeps both buffers on device
    for the fleet's lifetime — mixing is a matmul over ``params`` rows, local
    training gathers the activated rows of BOTH buffers, and pytrees are
    materialized only at checkpoint/eval-by-pytree boundaries.  Hashable
    (two hashable ``FlatSpec``s), so it rides through ``jax.jit`` closures
    and static arguments exactly like a single-buffer spec.
    """
    params: FlatSpec
    opt: FlatSpec

