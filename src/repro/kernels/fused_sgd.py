"""Pallas TPU kernel: fused multi-step local SGD (paper Eq. 5), VMEM-resident.

The sim plane's training hot spot is ``local_sgd_flat_fused`` in
``dfl/worker.py``: k gathered worker rows of the flat (N, P) buffer each take
``local_steps`` SGD steps on a 3-layer relu MLP.  The jnp lowering is a chain
of batched tiny gemms — every step re-reads and re-writes the (k, P) weight
slab through HBM.  This kernel makes the weights RESIDENT: grid (k,), one
worker per program, its six MLP leaves loaded into VMEM once, carried through
the statically-unrolled step loop as values, and written back exactly once.
Per-worker minibatches for all steps ride in as one (1, steps, batch, dim)
block.

TPU tiling: Mosaic wants the last two dims of every block to be (8, 128)
multiples or the whole array dims, and cannot reshape a lane vector into a
matrix in VMEM.  So the (k, P) rows are split into their leaves by static
slices outside the kernel (``flat_state.unflatten``), every operand carries
a unit axis that makes its block's last two dims whole (biases (k, 1, h),
labels (k, steps, batch, 1), the update scale (k, 1, 1)), all values in the
kernel stay 2-D, and transposed products are ``dot_general`` contractions.

Numerics mirror the manual-backward oracle op for op — same forward, same
closed-form ``softmax(logits) - onehot`` cross-entropy backward, same
``with_losses`` split (``False`` drops the log-sum-exp chain and reports
zeros), same zero-scaled update for inactive rows (their buffer row is
bit-identical out).  The oracle stays the source of truth in tests.
"""
from __future__ import annotations

import functools
from typing import Tuple, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec

from repro.dfl import flat_state as FS
from repro.kernels.config import resolve_interpret
from repro.sharding.rules import shard_map

_LEAVES = ("b1", "b2", "b3", "w1", "w2", "w3")   # FlatSpec leaf (sort) order


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _dot_tn(a, b):
    """a.T @ b, contracting the leading (batch) axis of both."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    """a @ b.T, contracting the trailing axis of both."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _make_kernel(steps: int, with_losses: bool):
    def kernel(b1_ref, b2_ref, b3_ref, w1_ref, w2_ref, w3_ref, x_ref, y_ref,
               scale_ref, ob1, ob2, ob3, ow1, ow2, ow3, loss_ref):
        b1, b2, b3 = b1_ref[0], b2_ref[0], b3_ref[0]          # (1, ·)
        w1, w2, w3 = w1_ref[0], w2_ref[0], w3_ref[0]          # (·, ·)
        s = scale_ref[0]                                      # (1, 1) active*lr
        c = w3.shape[1]
        loss = jnp.zeros((1, 1), jnp.float32)
        for t in range(steps):                    # static, unrolled: weights
            x = x_ref[0, t].astype(jnp.float32)   # stay resident across steps
            y = y_ref[0, t]                       # (batch, 1) int labels
            batch = x.shape[0]
            z1 = _dot(x, w1) + b1
            h1 = jax.nn.relu(z1)
            z2 = _dot(h1, w2) + b2
            h2 = jax.nn.relu(z2)
            logits = _dot(h2, w3) + b3
            onehot = (jax.lax.broadcasted_iota(jnp.int32, (batch, c), 1)
                      == y).astype(jnp.float32)
            if with_losses:
                logp = jax.nn.log_softmax(logits, axis=-1)
                loss = loss - jnp.sum(logp * onehot, keepdims=True) / batch
                probs = jnp.exp(logp)
            else:
                probs = jax.nn.softmax(logits, axis=-1)
            dz = (probs - onehot) / batch         # d(mean CE)/d logits
            g_w3 = _dot_tn(h2, dz)
            g_b3 = jnp.sum(dz, axis=0, keepdims=True)
            dh2 = _dot_nt(dz, w3) * (z2 > 0)
            g_w2 = _dot_tn(h1, dh2)
            g_b2 = jnp.sum(dh2, axis=0, keepdims=True)
            dh1 = _dot_nt(dh2, w2) * (z1 > 0)
            g_w1 = _dot_tn(x, dh1)
            g_b1 = jnp.sum(dh1, axis=0, keepdims=True)
            w1, b1 = w1 - s * g_w1, b1 - s * g_b1
            w2, b2 = w2 - s * g_w2, b2 - s * g_b2
            w3, b3 = w3 - s * g_w3, b3 - s * g_b3
        ob1[0], ob2[0], ob3[0] = b1, b2, b3
        ow1[0], ow2[0], ow3[0] = w1, w2, w3
        loss_ref[0] = loss / steps

    return kernel


def _whole(shape):
    """Block over one leading-axis slice holding the trailing dims whole."""
    zeros = (0,) * (len(shape) - 1)
    return pl.BlockSpec((1,) + tuple(shape[1:]), lambda i: (i,) + zeros)


@functools.partial(jax.jit,
                   static_argnames=("spec", "lr", "with_losses", "interpret"))
def fused_sgd(buf: jnp.ndarray, xb: jnp.ndarray, yb: jnp.ndarray,
              active: jnp.ndarray, spec, lr: float,
              with_losses: bool = True,
              interpret: Union[str, bool] = "auto"
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``local_sgd_flat_fused``'s contract on the Pallas kernel plane.

    buf (k, P) f32 gathered worker rows; xb (k, steps, batch, dim);
    yb (k, steps, batch) int labels; active (k,).  Returns the updated
    (k, P) rows and the (k,) per-worker mean loss over steps (zeros when
    ``with_losses=False``).  Requires ``fused_sgd_supported(spec)``.
    """
    k = buf.shape[0]
    steps = xb.shape[1]
    leaves = FS.unflatten(buf.astype(jnp.float32), spec)
    ins = [leaves[name] for name in _LEAVES]
    ins = [l[:, None, :] if l.ndim == 2 else l for l in ins]   # biases 3-D
    scale = (active.astype(jnp.float32) * lr).reshape(k, 1, 1)
    operands = ins + [xb, yb[..., None], scale]
    out_shapes = [jax.ShapeDtypeStruct(l.shape, jnp.float32) for l in ins]
    out_shapes.append(jax.ShapeDtypeStruct((k, 1, 1), jnp.float32))
    *outs, loss = pl.pallas_call(
        _make_kernel(steps, with_losses),
        grid=(k,),
        name="dystop_fused_sgd",
        in_specs=[_whole(a.shape) for a in operands],
        out_specs=[_whole(o.shape) for o in out_shapes],
        out_shape=out_shapes,
        interpret=resolve_interpret(interpret),
    )(*operands)
    out = jnp.concatenate([o.reshape(k, -1) for o in outs], axis=1)
    return out, loss.reshape(k)


def fused_sgd_sharded(buf: jnp.ndarray, xb: jnp.ndarray, yb: jnp.ndarray,
                      active: jnp.ndarray, spec, lr: float, shd,
                      with_losses: bool = True,
                      interpret: Union[str, bool] = "auto"
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """shard_map wrapper: Eq. 5 is row-local, so the SPMD program is
    embarrassingly parallel — the gathered rows (and their batches) split
    over the fleet axis when k divides the mesh (``FleetSharding.for_rows``
    row layout), with zero collectives; odd k falls back to replicated
    compute, matching the engine's replication of small buckets.
    ``check_vma=False``: under JAX 0.9 the check needs the kernel's output
    shapes to declare their varying axes (see
    ``aggregate.aggregate_rows_sharded_kernel``)."""
    k = buf.shape[0]
    if not k or k % shd.n_shards:
        return fused_sgd(buf, xb, yb, active, spec, lr,
                         with_losses=with_losses, interpret=interpret)
    ax = shd.axis
    fn = functools.partial(fused_sgd, spec=spec, lr=lr,
                           with_losses=with_losses, interpret=interpret)
    rows = PartitionSpec(ax)
    new, loss = shard_map(fn, mesh=shd.mesh,
                          in_specs=(rows, rows, rows, rows),
                          out_specs=(rows, rows), check_vma=False)(
        buf, xb, yb, active)
    sharding = shd.for_rows(k)
    return (jax.lax.with_sharding_constraint(new, sharding),
            jax.lax.with_sharding_constraint(loss, sharding))
