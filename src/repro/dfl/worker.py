"""Worker-side model + stacked-worker training ops for the DFL simulation.

The simulation plane trains an MLP classifier (the offline stand-in for the
paper's CNN/ResNet) but any ``repro.models`` architecture can be plugged in —
the protocol only needs a param pytree and a local-step function.  All N
worker replicas live in one stacked pytree (leading worker axis) and local
SGD for the activated subset is a masked vmap.

Fused round engine: ``round_step`` keeps the N replicas as ONE flat (N, P)
device buffer (see ``flat_state``) and runs Eq. 4 mixing (sparse matmul),
on-device minibatch sampling, and masked local SGD (Eq. 5) in a single
donated jit — one dispatch per simulated round instead of per-leaf mixing +
a host sampling loop + a separate train dispatch.  ``mega_round_step``
executes a whole planned horizon as one ``lax.scan``.

Lowerings the drive loops select from what they observe:
  * Eq. 4 contracts either the (k, N) row-sparse rows (``mix_flat``) or the
    (k, u) @ (u, P) gathered union of nonzero columns (``mix_flat_cols``),
    per chunk by the ``aggregation.prefer_cols`` traffic model;
  * Eq. 5 runs as one unrolled manual-backward jit region over the gathered
    active rows (``local_sgd_flat_fused``) for the sim-plane MLP
    (``fused_sgd_supported``), and as the per-step AD scan
    (``local_sgd_flat``) for any other spec.

Mesh-sharded fleet: every dispatch takes an optional static ``shd``
(``sharding.rules.FleetSharding``).  When set, the (N_pad, P) buffer is
row-partitioned over the 1-D fleet mesh and the same code paths carry
sharding constraints instead of forking: the row-sparse mix psums shard-local
slabs, the column-sparse mix all_gathers only the union rows and splits the
output rows, gathered active-row SGD shards over k when it divides, and the
scatter-backs land shard-local for home rows (see
``kernels.aggregate.aggregate_rows_sharded`` /
``aggregate_rows_cols_sharded``).  ``shd=None`` (the default) is bit-for-bit
the unsharded engine.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.dfl import flat_state as FS

Params = Dict[str, Any]


def init_mlp(key, dim: int, hidden: int, n_classes: int) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w1": jax.random.normal(k1, (dim, hidden), jnp.float32) * dim ** -0.5,
        "b1": jnp.zeros((hidden,), jnp.float32),
        "w2": jax.random.normal(k2, (hidden, hidden), jnp.float32) * hidden ** -0.5,
        "b2": jnp.zeros((hidden,), jnp.float32),
        "w3": jax.random.normal(k3, (hidden, n_classes), jnp.float32) * hidden ** -0.5,
        "b3": jnp.zeros((n_classes,), jnp.float32),
    }


def mlp_logits(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    h = jax.nn.relu(x @ p["w1"] + p["b1"])
    h = jax.nn.relu(h @ p["w2"] + p["b2"])
    return h @ p["w3"] + p["b3"]


def mlp_loss(p: Params, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    logits = mlp_logits(p, x)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


def init_stacked(key, n_workers: int, dim: int, hidden: int, n_classes: int,
                 same_init: bool = True) -> Params:
    """All workers start from w_0 (paper Thm. 1 assumes shared init)."""
    if same_init:
        p = init_mlp(key, dim, hidden, n_classes)
        return jax.tree.map(lambda t: jnp.broadcast_to(t[None], (n_workers,) + t.shape).copy(), p)
    keys = jax.random.split(key, n_workers)
    return jax.vmap(lambda k: init_mlp(k, dim, hidden, n_classes))(keys)


@functools.partial(jax.jit, static_argnames=("lr", "local_steps"))
def local_train(stacked: Params, xb: jnp.ndarray, yb: jnp.ndarray,
                active: jnp.ndarray, lr: float = 0.05,
                local_steps: int = 1) -> Tuple[Params, jnp.ndarray]:
    """Masked per-worker SGD (paper Eq. 5).

    xb: (N, steps, batch, dim); yb: (N, steps, batch); active: (N,) bool.
    Only activated workers move; returns (new stacked params, per-worker loss).
    """
    def per_worker(p, x_steps, y_steps, a):
        def one_step(pp, xy):
            x, y = xy
            loss, g = jax.value_and_grad(mlp_loss)(pp, x, y)
            pp = jax.tree.map(lambda w, gw: w - lr * a * gw, pp, g)
            return pp, loss

        p, losses = jax.lax.scan(one_step, p, (x_steps, y_steps))
        return p, losses.mean()

    return jax.vmap(per_worker)(stacked, xb, yb,
                                active.astype(jnp.float32))


@jax.jit
def evaluate_stacked(stacked: Params, x: jnp.ndarray, y: jnp.ndarray
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mean test accuracy + loss across workers' local models."""
    def one(p):
        logits = mlp_logits(p, x)
        acc = jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))
        logp = jax.nn.log_softmax(logits, -1)
        loss = -jnp.mean(jnp.take_along_axis(logp, y[:, None], -1))
        return acc, loss

    accs, losses = jax.vmap(one)(stacked)
    return accs.mean(), losses.mean()


@jax.jit
def evaluate_global(stacked: Params, alpha: jnp.ndarray, x: jnp.ndarray,
                    y: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Eval the data-size-weighted global model w_t (paper Eq. 11)."""
    gm = jax.tree.map(lambda t: jnp.tensordot(alpha, t, axes=1), stacked)
    logits = mlp_logits(gm, x)
    acc = jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))
    logp = jax.nn.log_softmax(logits, -1)
    loss = -jnp.mean(jnp.take_along_axis(logp, y[:, None], -1))
    return acc, loss


def param_bytes(params: Params) -> int:
    return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(params))


@functools.partial(jax.jit, static_argnames=("spec",))
def evaluate_global_flat(buf: jnp.ndarray, alpha: jnp.ndarray,
                         x: jnp.ndarray, y: jnp.ndarray, *, spec
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Eq. 11 global-model eval straight off the flat (N, P) buffer.

    The global model is one ``alpha @ buf`` matvec + a static unravel — no
    stacked pytree is materialized, so horizon-boundary evals stay cheap."""
    with jax.named_scope("eval"):
        gm = FS.unravel_row(FS.weighted_row(buf, alpha), spec)
        logits = mlp_logits(gm, x)
        acc = jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))
        logp = jax.nn.log_softmax(logits, -1)
        loss = -jnp.mean(jnp.take_along_axis(logp, y[:, None], -1))
    return acc, loss


@functools.partial(jax.jit, static_argnames=("spec",))
def evaluate_stacked_flat(buf: jnp.ndarray, x: jnp.ndarray, y: jnp.ndarray,
                          *, spec) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mean local-model test accuracy + loss, vmapped over the buffer rows."""
    def one(vec):
        p = FS.unravel_row(vec, spec)
        logits = mlp_logits(p, x)
        acc = jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))
        logp = jax.nn.log_softmax(logits, -1)
        loss = -jnp.mean(jnp.take_along_axis(logp, y[:, None], -1))
        return acc, loss

    with jax.named_scope("eval"):
        accs, losses = jax.vmap(one)(buf)
        return accs.mean(), losses.mean()


# --------------------------------------------------------------------------- #
# fused, device-resident round engine over the flat (N, P) buffer
# --------------------------------------------------------------------------- #


def mlp_loss_flat(vec: jnp.ndarray, spec: FS.FlatSpec, x: jnp.ndarray,
                  y: jnp.ndarray) -> jnp.ndarray:
    """MLP loss on one worker's (P,) slice of the flat buffer.

    The unravel is static slicing/reshapes that XLA fuses away, so gradients
    flow straight back to the flat vector — the buffer stays the only
    materialized model storage.
    """
    return mlp_loss(FS.unravel_row(vec, spec), x, y)


def _pin(x, sharding):
    """``with_sharding_constraint``; identity when ``sharding`` is None (the
    unsharded engine) — one guard for every hot path."""
    if sharding is None:
        return x
    return jax.lax.with_sharding_constraint(x, sharding)


def _pin_rows(x, shd):
    """Pin to the fleet row partition (no-op without a mesh)."""
    return _pin(x, shd.rows() if shd is not None else None)


def _pin_repl(x, shd):
    """Pin to fully replicated (no-op without a mesh)."""
    return _pin(x, shd.replicated() if shd is not None else None)


def _mix_rows(buf: jnp.ndarray, w_rows: jnp.ndarray, col_ids,
              kernels, shd=None) -> jnp.ndarray:
    """The scatter-free Eq. 4 contraction: (k, N) @ (N, P), or column-sparse
    (k, u) @ (u, P) over the gathered union slab when ``col_ids`` is given.
    Single source for the kernel/jnp/mesh variants, shared by ``mix_flat``,
    ``mix_flat_cols`` and the ``mix_is_train`` fused path.  ``kernels`` is a
    ``kernels.config.KernelConfig`` (or None = reference): the Pallas backend
    runs the VMEM panel schedule, and with ``shd`` its per-shard ``shard_map``
    twins (shard-local panels + psum); the reference backend runs plain jnp,
    with ``shd`` the GSPMD-constrained twins."""
    use_pallas = kernels is not None and kernels.use_pallas
    if shd is not None:
        from repro.kernels import aggregate as AGG
        if use_pallas:
            interp = kernels.resolve_interpret()
            if col_ids is not None:
                return AGG.aggregate_rows_cols_sharded_kernel(
                    w_rows, col_ids, buf, shd, p_blk=kernels.agg_p_blk,
                    interpret=interp)
            return AGG.aggregate_rows_sharded_kernel(
                w_rows, buf, shd, p_blk=kernels.agg_p_blk, interpret=interp)
        return (AGG.aggregate_rows_cols_sharded(w_rows, col_ids, buf, shd)
                if col_ids is not None
                else AGG.aggregate_rows_sharded(w_rows, buf, shd))
    if use_pallas:
        from repro.kernels import aggregate as AGG
        interp = kernels.resolve_interpret()
        if col_ids is not None:
            return AGG.aggregate_rows_cols(w_rows, col_ids, buf,
                                           p_blk=kernels.agg_p_blk,
                                           interpret=interp)
        return AGG.aggregate_rows(w_rows, buf, p_blk=kernels.agg_p_blk,
                                  interpret=interp)
    if col_ids is not None:
        return w_rows.astype(jnp.float32) @ FS.take_rows(buf, col_ids)
    return w_rows.astype(jnp.float32) @ buf


def narrow_mix(kernels, w_shape, col_sparse: bool, shards: int = 1) -> bool:
    """Whether ``_mix_rows`` over mixing rows of this (..., k, width) shape
    runs the Pallas panel's VPU body (``kernels.aggregate.vpu_body``): the
    Pallas backend, k > 0 rows, and a contraction narrower than a sublane
    tile, per shard for a row-sparse mix of a sharded buffer (each shard
    contracts its N/S block).  Behind the trace's ``mix_narrow_rounds``."""
    k, width = w_shape[-2:]
    if not (kernels is not None and kernels.use_pallas and k):
        return False
    from repro.kernels.aggregate import vpu_body
    return vpu_body(width if col_sparse else width // shards)


def mix_flat(buf: jnp.ndarray, w_rows: jnp.ndarray, row_ids: jnp.ndarray,
             kernels=None, shd=None) -> jnp.ndarray:
    """Sparse Eq. 4 over the flat buffer: mix the k non-identity rows only.

    ``w_rows`` (k, N) are the gathered rows of W (see
    ``core.aggregation.mixing_rows``); all other rows of W are identity, so
    gather -> (k, N) @ (N, P) -> scatter is exact.  Sharded (``shd``): the
    scatter is shard-local for home rows and the buffer is re-pinned to its
    row partition.
    """
    if w_rows.shape[0] == 0:
        return buf
    buf = buf.at[row_ids].set(_mix_rows(buf, w_rows, None, kernels, shd))
    return _pin_rows(buf, shd)


def mix_flat_cols(buf: jnp.ndarray, w_sub: jnp.ndarray, row_ids: jnp.ndarray,
                  col_ids: jnp.ndarray, kernels=None, shd=None
                  ) -> jnp.ndarray:
    """Column-sparse Eq. 4 over the flat buffer: the default mix hot path.

    ``w_sub`` (k, u) are the gathered non-identity rows of W restricted to
    the union of their nonzero columns, ``col_ids`` (u,) that union (see
    ``core.aggregation.mixing_rows_cols``); the (u, P) slab is gathered once
    and the contraction is (k, u) @ (u, P) — k·u·P flops instead of the
    row-sparse path's k·N·P, exact because every column of W outside the
    union is zero on the gathered rows (padding columns are zeroed host-side).
    """
    if w_sub.shape[0] == 0:
        return buf
    buf = buf.at[row_ids].set(_mix_rows(buf, w_sub, col_ids, kernels, shd))
    return _pin_rows(buf, shd)


def sample_batches_device(key, worker_ids: jnp.ndarray, data_x: jnp.ndarray,
                          data_y: jnp.ndarray, part_idx: jnp.ndarray,
                          part_sizes: jnp.ndarray, local_steps: int,
                          batch_size: int):
    """Minibatches for the given workers from the device-resident dataset.

    part_idx: (k, max_part) padded sample-index rows for those workers;
    part_sizes: (k,) true partition lengths.  Draws are uniform over each
    worker's true partition (padding is never indexed), replacing the
    per-worker host ``rng.choice`` loop and its H2D batch transfer.  Each
    worker's stream is keyed by its id (not its position in the gathered row
    set), so sampling is reproducible across shape buckets.
    """
    keys = jax.vmap(jax.random.fold_in, (None, 0))(key, worker_ids)

    def one(k, idx_row, size):
        r = jax.random.randint(k, (local_steps, batch_size), 0, size)
        ids = idx_row[r]
        return data_x[ids], data_y[ids]

    return jax.vmap(one)(keys, part_idx, part_sizes)


def local_sgd_flat(buf: jnp.ndarray, xb: jnp.ndarray, yb: jnp.ndarray,
                   active: jnp.ndarray, spec: FS.FlatSpec, lr: float
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Masked per-worker SGD (Eq. 5) directly on the flat buffer rows."""
    def per_worker(vec, x_steps, y_steps, a):
        def one_step(v, xy):
            x, y = xy
            loss, g = jax.value_and_grad(mlp_loss_flat)(v, spec, x, y)
            return v - (lr * a) * g, loss

        vec, losses = jax.lax.scan(one_step, vec, (x_steps, y_steps))
        return vec, losses.mean()

    return jax.vmap(per_worker)(buf, xb, yb, active.astype(jnp.float32))


_MLP_TREEDEF = jax.tree.structure(
    {k: 0 for k in ("w1", "b1", "w2", "b2", "w3", "b3")})


def fused_sgd_supported(spec: FS.FlatSpec) -> bool:
    """True iff ``spec`` is the sim-plane 3-layer MLP the fused SGD lowering
    hand-differentiates (``init_mlp`` layout).  Any other architecture falls
    back to the generic AD scan (``local_sgd_flat``)."""
    if spec.treedef != _MLP_TREEDEF or len(spec.shapes) != 6:
        return False
    shapes = dict(zip(("b1", "b2", "b3", "w1", "w2", "w3"), spec.shapes))
    return (len(shapes["w1"]) == len(shapes["w2"]) == len(shapes["w3"]) == 2
            and shapes["w1"][1] == shapes["b1"][0] == shapes["w2"][0]
            and shapes["w2"][1] == shapes["b2"][0] == shapes["w3"][0]
            and shapes["w3"][1] == shapes["b3"][0])


def local_sgd_flat_fused(buf: jnp.ndarray, xb: jnp.ndarray, yb: jnp.ndarray,
                         active: jnp.ndarray, spec: FS.FlatSpec, lr: float,
                         with_losses: bool = True
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused multi-step SGD (Eq. 5) — the default local-training lowering.

    Replaces the per-local-step ``lax.scan`` of AD gradients with one
    straight-line jit region over the gathered active rows: the steps are
    unrolled (``local_steps`` is static), the MLP forward/backward is written
    out as batched einsums over the (k, ·, ·) weight slabs, and the
    cross-entropy backward is the closed form ``softmax(logits) - onehot``
    — no ``take_along_axis`` scatter-gradients, no scan carry, so XLA fuses
    the whole multi-step chain into one computation (the per-step AD path
    lowers to batched tiny gemms separated by while-loop barriers, ~12
    GFLOP/s on CPU).  Minibatches for ALL steps arrive pre-gathered as one
    batched draw (``sample_batches_device``).

    Exactly ``local_sgd_flat``'s contract: xb (k, steps, batch, dim), yb
    (k, steps, batch), active (k,) — inactive rows get a zero-scaled update
    (bit-identical buffer row) and their loss is still reported; requires
    ``fused_sgd_supported(spec)``.  Numerics match the AD oracle to f32
    rounding (einsum reduction order differs), pinned by tests.

    ``with_losses=False`` skips the loss VALUES (returns zeros): the
    gradient only needs ``softmax(logits) - onehot``, so the log/log-sum-exp
    chain drops out of the round entirely — the AD oracle gets the value for
    free from ``value_and_grad``, but here it is real work the simulator
    (which discards per-round losses) never pays.
    """
    p = FS.unflatten(buf.astype(jnp.float32), spec)
    w1, b1, w2, b2 = p["w1"], p["b1"], p["w2"], p["b2"]
    w3, b3 = p["w3"], p["b3"]
    n_classes = w3.shape[-1]
    batch = xb.shape[2]
    a = active.astype(jnp.float32) * lr
    sw = a[:, None, None]                      # (k, 1, 1) weight-update scale
    sb = a[:, None]                            # (k, 1)    bias-update scale
    losses = []
    for s in range(xb.shape[1]):               # local_steps: static, unrolled
        x, y = xb[:, s], yb[:, s]              # (k, batch, dim), (k, batch)
        z1 = jnp.einsum("kbd,kdh->kbh", x, w1) + b1[:, None]
        h1 = jax.nn.relu(z1)
        z2 = jnp.einsum("kbh,khg->kbg", h1, w2) + b2[:, None]
        h2 = jax.nn.relu(z2)
        logits = jnp.einsum("kbg,kgc->kbc", h2, w3) + b3[:, None]
        onehot = jax.nn.one_hot(y, n_classes, dtype=jnp.float32)
        if with_losses:
            logp = jax.nn.log_softmax(logits, axis=-1)
            losses.append(-jnp.sum(logp * onehot, -1).mean(-1))    # (k,)
            probs = jnp.exp(logp)
        else:
            probs = jax.nn.softmax(logits, axis=-1)
        dz = (probs - onehot) / batch          # d(mean CE)/d logits
        # backward as explicit transpose + batched matmul: XLA CPU lowers
        # these to clean row-major batched gemms, measurably faster than the
        # einsum contractions over the middle (batch) axis
        h2t = jnp.transpose(h2, (0, 2, 1))
        h1t = jnp.transpose(h1, (0, 2, 1))
        g_w3 = jnp.matmul(h2t, dz)
        g_b3 = dz.sum(1)
        dh2 = jnp.einsum("kbc,kgc->kbg", dz, w3) * (z2 > 0)
        g_w2 = jnp.matmul(h1t, dh2)
        g_b2 = dh2.sum(1)
        dh1 = jnp.einsum("kbg,khg->kbh", dh2, w2) * (z1 > 0)
        g_w1 = jnp.matmul(jnp.transpose(x, (0, 2, 1)), dh1)
        g_b1 = dh1.sum(1)
        w1, b1 = w1 - sw * g_w1, b1 - sb * g_b1
        w2, b2 = w2 - sw * g_w2, b2 - sb * g_b2
        w3, b3 = w3 - sw * g_w3, b3 - sb * g_b3
    new = {"w1": w1, "b1": b1, "w2": w2, "b2": b2, "w3": w3, "b3": b3}
    out, _ = FS.flatten_stacked(new)
    loss = (jnp.stack(losses).mean(0) if with_losses
            else jnp.zeros((buf.shape[0],), jnp.float32))
    return out, loss


def pack_round_ctrl(mix_row_ids: np.ndarray, train_row_ids: np.ndarray,
                    train_mask: np.ndarray,
                    col_ids: Optional[np.ndarray] = None) -> np.ndarray:
    """Concatenate the per-round integer control vectors into ONE host array
    so the fused dispatch pays a single small H2D transfer instead of three
    (device_put dominates tiny-array transfer cost on CPU).  Layout:
    ``[mix_row_ids (k,) | col_ids (u,) if column-sparse | train_row_ids
    (k_train,) | train_mask (k_train,)]`` — the dispatcher recovers the
    segment boundaries from the static W shapes."""
    segs = [np.asarray(mix_row_ids, np.int32)]
    if col_ids is not None:
        segs.append(np.asarray(col_ids, np.int32))
    segs += [np.asarray(train_row_ids, np.int32),
             np.asarray(train_mask, np.int32)]
    return np.concatenate(segs)


def split_ctrl(ctrl: jnp.ndarray, k_mix: int, u: int):
    """Recover the ``pack_round_ctrl`` segments from a packed control vector
    (or a stacked ``(H, ·)`` horizon of them — slicing is along the last
    axis).  Returns ``(mix_ids, col_ids | None, train_ids, train_mask)``
    with ``train_mask`` cast to f32; the segment boundaries are static
    (derived from the jit-static ``k_mix``/``u`` shapes), so consumers —
    ``round_step``, ``mega_round_step``, and the LM fleet engine — share one
    layout definition.
    """
    k_train = (ctrl.shape[-1] - k_mix - u) // 2
    mix_ids = ctrl[..., :k_mix]
    col_ids = ctrl[..., k_mix:k_mix + u] if u else None
    train_ids = ctrl[..., k_mix + u:k_mix + u + k_train]
    train_mask = ctrl[..., k_mix + u + k_train:].astype(jnp.float32)
    return mix_ids, col_ids, train_ids, train_mask


def _mix_train_body(buf: jnp.ndarray, w_rows: jnp.ndarray,
                    mix_row_ids: jnp.ndarray, col_ids,
                    train_row_ids: jnp.ndarray,
                    train_mask: jnp.ndarray, xb, yb, spec: FS.FlatSpec,
                    lr: float, kernels, fused_sgd: bool,
                    with_losses: bool = True, mix_is_train: bool = False,
                    shd=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mix + masked SGD on pre-sampled batches — the buffer-dependent half of
    a round, shared by ``round_step`` and ``mega_round_step``'s scan body
    (batch sampling is buffer-INdependent, so the mega path hoists it out of
    the scan and draws the whole horizon in one batched op).  ``col_ids``
    non-None selects the column-sparse contraction (else ``mix_flat``);
    ``fused_sgd`` the unrolled manual-backward SGD lowering (else the AD
    scan ``local_sgd_flat``).

    ``mix_is_train`` (host-verified: the mix row ids EQUAL the train row
    ids, as in every DySTop round — activated workers are exactly the
    pullers) lets the fused lowering consume the mixed rows directly: the
    Eq. 4 output feeds Eq. 5 without the intermediate scatter into the
    buffer and re-gather of the same rows — bit-identical values, one
    full-width buffer write less per round.

    ``shd`` (mesh-sharded buffer): the gathered (k, ·) training operands are
    constrained to split over the fleet axis whenever k divides the shard
    count — local SGD then runs on k/S rows per shard — and the buffer is
    re-pinned to its row partition after every scatter."""
    n = buf.shape[0]
    k_train = train_row_ids.shape[0]
    sub_shd = shd.for_rows(k_train) if shd is not None else None

    def train_rows(sub):
        sub = _pin(sub, sub_shd)
        x_s = _pin(xb, sub_shd)
        y_s = _pin(yb, sub_shd)
        if fused_sgd and kernels is not None and kernels.use_pallas:
            from repro.kernels import fused_sgd as FSGD
            interp = kernels.resolve_interpret()
            if shd is not None:
                new_sub, sub_loss = FSGD.fused_sgd_sharded(
                    sub, x_s, y_s, train_mask, spec, lr, shd,
                    with_losses=with_losses, interpret=interp)
            else:
                new_sub, sub_loss = FSGD.fused_sgd(
                    sub, x_s, y_s, train_mask, spec, lr,
                    with_losses=with_losses, interpret=interp)
        elif fused_sgd:
            new_sub, sub_loss = local_sgd_flat_fused(sub, x_s, y_s,
                                                     train_mask, spec, lr,
                                                     with_losses=with_losses)
        else:
            new_sub, sub_loss = local_sgd_flat(sub, x_s, y_s, train_mask,
                                               spec, lr)
        return _pin(new_sub, sub_shd), sub_loss

    if fused_sgd and mix_is_train and k_train > 0 and w_rows.shape[0] > 0:
        with jax.named_scope("mix"):
            sub = _mix_rows(buf, w_rows, col_ids, kernels, shd)
        with jax.named_scope("sgd"):
            new_sub, sub_loss = train_rows(sub)
        with jax.named_scope("write_back"):
            buf = _pin_rows(buf.at[train_row_ids].set(new_sub), shd)
        losses = jnp.zeros((n,), jnp.float32)
        if with_losses:
            losses = losses.at[train_row_ids].set(sub_loss * train_mask)
        return buf, _pin_repl(losses, shd)
    with jax.named_scope("mix"):
        if col_ids is not None:
            buf = mix_flat_cols(buf, w_rows, mix_row_ids, col_ids,
                                kernels=kernels, shd=shd)
        else:
            buf = mix_flat(buf, w_rows, mix_row_ids, kernels=kernels,
                           shd=shd)
    losses = jnp.zeros((n,), jnp.float32)
    if k_train == 0:
        return buf, losses
    with jax.named_scope("sgd"):
        new_sub, sub_loss = train_rows(FS.take_rows(buf, train_row_ids, shd))
    with jax.named_scope("write_back"):
        buf = _pin_rows(buf.at[train_row_ids].set(new_sub), shd)
    if with_losses:
        losses = losses.at[train_row_ids].set(sub_loss * train_mask)
    return buf, _pin_repl(losses, shd)


@functools.partial(jax.jit,
                   static_argnames=("spec", "lr", "local_steps", "batch_size",
                                    "kernels", "col_sparse", "fused_sgd",
                                    "with_losses", "mix_is_train", "shd"),
                   donate_argnums=(0,))
def round_step(buf: jnp.ndarray, w_rows: jnp.ndarray, ctrl: jnp.ndarray,
               data_x: jnp.ndarray, data_y: jnp.ndarray,
               part_idx: jnp.ndarray, part_sizes: jnp.ndarray, key, t,
               *, spec: FS.FlatSpec, lr: float, local_steps: int,
               batch_size: int, kernels=None,
               col_sparse: bool = False, fused_sgd: bool = False,
               with_losses: bool = True, mix_is_train: bool = False,
               shd=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One fused simulated round: sparse mix + on-device sampling + local SGD.

    Both halves of the round exploit the same active-row sparsity: Eq. 4 only
    rewrites the k non-identity rows of W (``w_rows`` + the mix ids in
    ``ctrl``), and Eq. 5 only moves the activated workers, so gradients are
    computed for the gathered activated sub-buffer alone — O(k·N·P +
    k·steps·batch·P) per round instead of O(N²·P + N·steps·batch·P).  The
    (N, P) buffer is donated, so XLA updates the model storage in place.

    ``col_sparse=True`` (the default engine path) interprets ``w_rows`` as
    the (k, u) column-restricted rows from ``mixing_rows_cols`` and cuts the
    mix to k·u·P flops; ``fused_sgd=True`` selects the unrolled
    manual-backward SGD lowering (``local_sgd_flat_fused``).  ``ctrl`` is
    the ``pack_round_ctrl`` concatenation of [mix_row_ids (k_mix,) |
    col_ids (u,) when col_sparse | train_row_ids (k_train,) | train_mask
    (k_train,)].  ``shd`` (static) runs the same round mesh-sharded: the
    buffer stays row-partitioned across the dispatch and the mix/SGD
    constraints lower to fleet-axis collectives.  Returns (new buffer,
    per-worker mean loss scattered to (N,), zero for idle workers).
    """
    k_mix = w_rows.shape[0]
    u = w_rows.shape[1] if col_sparse and k_mix else 0
    mix_row_ids, col_ids, train_row_ids, train_mask = split_ctrl(ctrl, k_mix, u)
    k_train = train_row_ids.shape[0]
    xb = yb = None
    if k_train:
        with jax.named_scope("sample"):
            key = jax.random.fold_in(key, t)       # per-round stream, in-jit
            xb, yb = sample_batches_device(key, train_row_ids, data_x,
                                           data_y, part_idx[train_row_ids],
                                           part_sizes[train_row_ids],
                                           local_steps, batch_size)
    return _mix_train_body(buf, w_rows, mix_row_ids, col_ids, train_row_ids,
                           train_mask, xb, yb, spec, lr, kernels,
                           fused_sgd, with_losses, mix_is_train, shd)


def pad_w_cols(w: np.ndarray, n_pad: int) -> np.ndarray:
    """Zero-pad the trailing (N) axis of a row-sparse W stack to the sharded
    buffer's padded row count: the extra columns multiply the permanently-
    idle padding rows by 0, so the contraction value is unchanged (summing
    exact +0.0 terms) while shapes line up with the (N_pad, P) buffer."""
    if w.shape[-1] >= n_pad:
        return w
    pad = [(0, 0)] * (w.ndim - 1) + [(0, n_pad - w.shape[-1])]
    return np.pad(w, pad)


def pack_horizon(plans, min_bucket: int = 8, col_sparse: bool = False,
                 shards: int = 1
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Stack H planned rounds' control tensors for ``mega_round_step``.

    ``plans``: objects with ``.W (N, N)``, ``.active (N,)``, ``.links
    (N, N)``, ``.t`` (``core.planner.PlannedRound``, duck-typed).  All rounds
    of a scan chunk must share one shape, so each round is padded to the
    horizon-wide max of the per-round power-of-two buckets (itself a bucket,
    keeping the compile count at O(log N) per horizon length).  Padding rows
    are exact no-ops: identity W rows / zero train masks targeting workers
    idle in that round.

    ``col_sparse=True`` packs the column-sparse contraction instead: W rows
    are restricted to the horizon-max bucket of each round's nonzero-column
    union (``PlannedRound.mix_cols`` when the planner resolved it, else
    re-derived), and the union's ``col_ids`` ride in ``ctrl``.

    ``shards > 1`` selects the shard-local padding layout of
    ``aggregation.padded_rows`` throughout (sorted ids, per-shard padding
    candidates); a sharded planner resolves ``mix_cols`` with the same shard
    count, keeping padding columns inside the union.

    Returns ``(w_rows (H, K_mix, N | U) f32, ctrl (H, K_mix [+ U] +
    2*K_train) i32, ts (H,) i32)`` — three host arrays, so the whole horizon
    pays three H2D transfers instead of 3·H.
    """
    from repro.core.aggregation import (bucket_size, col_union_mask,
                                        mixing_rows, mixing_rows_cols,
                                        padded_rows, plan_buckets)

    n = plans[0].W.shape[0]
    buckets = [plan_buckets(p.active, p.links, min_bucket) for p in plans]
    k_mix = max(b[0] for b in buckets)
    k_train = max(b[1] for b in buckets)
    h = len(plans)
    ts = np.zeros((h,), np.int32)
    if col_sparse:
        def cols_of(p):
            return (p.mix_cols if getattr(p, "mix_cols", None) is not None
                    else col_union_mask(p.active, p.links, shards))

        u = max(bucket_size(int(cols_of(p).sum()), n, min_bucket)
                for p in plans) if k_mix else 0
        if u >= n:
            u = n
        w_rows_h = np.zeros((h, k_mix, u), np.float32)
        ctrl_h = np.zeros((h, k_mix + u + 2 * k_train), np.int32)
        for i, p in enumerate(plans):
            w_sub, mix_ids, col_ids = mixing_rows_cols(
                p.W, p.active, p.links, min_bucket, pad_to=k_mix,
                col_pad_to=u, cols_mask=cols_of(p), shards=shards)
            train_ids, train_mask = padded_rows(p.active, min_bucket,
                                                pad_to=k_train, shards=shards)
            if k_mix:
                w_rows_h[i] = w_sub
            ctrl_h[i] = pack_round_ctrl(mix_ids, train_ids, train_mask,
                                        col_ids=col_ids)
            ts[i] = p.t
        return w_rows_h, ctrl_h, ts
    w_rows_h = np.zeros((h, k_mix, n), np.float32)
    ctrl_h = np.zeros((h, k_mix + 2 * k_train), np.int32)
    for i, p in enumerate(plans):
        w_rows, mix_ids = mixing_rows(p.W, p.active, p.links, min_bucket,
                                      pad_to=k_mix, shards=shards)
        train_ids, train_mask = padded_rows(p.active, min_bucket,
                                            pad_to=k_train, shards=shards)
        if k_mix:
            w_rows_h[i] = w_rows
        ctrl_h[i] = pack_round_ctrl(mix_ids, train_ids, train_mask)
        ts[i] = p.t
    return w_rows_h, ctrl_h, ts


def pack_chunk(plans, key, *, min_bucket: int = 8, col_sparse: bool = False,
               shards: int = 1) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``pack_horizon`` specialized to a bucket-uniform ``chunk_spans`` chunk.

    The drive loops' packer: every plan in a chunk shares the
    ``bucket_key`` triple ``key`` by construction, so the per-plan bucket
    re-derivation (``plan_buckets`` + column-union counting) and the
    general-purpose gather helpers collapse into one direct loop — the padded
    shapes are ``key`` itself.  Uses ``PlannedRound.mix_rows`` (the
    non-identity row ids the planner already resolved) when present.  Output
    is BIT-IDENTICAL to ``pack_horizon`` on the same chunk (pinned by
    tests/test_pipeline.py) at roughly half the host cost.

    Falls back to ``pack_horizon`` verbatim for the cases the fast loop does
    not specialize: sharded padding layouts (``shards > 1``), all-idle chunks
    (``k_mix == 0``), and the degenerate full-width column union
    (``u >= N`` — ``mixing_rows_cols`` switches to ``col_ids = arange(N)``
    there).
    """
    from repro.core.aggregation import col_union_mask

    n = plans[0].W.shape[0]
    k_mix, k_train = int(key[0]), int(key[1])
    u = int(key[2]) if col_sparse else 0
    if shards > 1 or k_mix == 0 or (col_sparse and u >= n):
        return pack_horizon(plans, min_bucket=min_bucket,
                            col_sparse=col_sparse, shards=shards)
    h = len(plans)
    w = np.zeros((h, k_mix, u if col_sparse else n), np.float32)
    ctrl = np.empty((h, k_mix + (u if col_sparse else 0) + 2 * k_train),
                    np.int32)
    ts = np.empty((h,), np.int32)
    for i, p in enumerate(plans):
        rows = (p.mix_rows if getattr(p, "mix_rows", None) is not None
                else np.flatnonzero(p.active | p.links.any(axis=1)))
        k = len(rows)
        if k_mix > k:
            # the unsharded padding rule: the globally-first idle row,
            # repeated (shard_pad_candidates with shards == 1) — the
            # candidate is planner-resolved (PlannedRound.mix_pad) on the
            # pipelined path
            cand = getattr(p, "mix_pad", None)
            if cand is None:
                mask = np.zeros(n, bool)
                mask[rows] = True
                cand = np.flatnonzero(~mask)[:1]
            rows = np.concatenate(
                [rows, cand[np.arange(k_mix - k) % len(cand)]])
        if col_sparse:
            cols = np.flatnonzero(
                p.mix_cols if getattr(p, "mix_cols", None) is not None
                else col_union_mask(p.active, p.links, shards))
            ut = len(cols)
            col_ids = (np.concatenate([cols, np.zeros(u - ut, cols.dtype)])
                       if u > ut else cols)
            sub = p.W[rows[:, None], col_ids[None, :]]
            sub[:, ut:] = 0.0          # padded columns contribute nothing
            w[i] = sub
        else:
            w[i] = p.W[rows]
        trows = (p.train_rows if getattr(p, "train_rows", None) is not None
                 else np.flatnonzero(p.active))
        kt = len(trows)
        if k_train > kt:
            cand = getattr(p, "train_pad", None)
            if cand is None:
                cand = np.flatnonzero(~p.active)[:1]
            trows = np.concatenate(
                [trows, cand[np.arange(k_train - kt) % len(cand)]])
        c = ctrl[i]
        c[:k_mix] = rows
        off = k_mix
        if col_sparse:
            c[off:off + u] = col_ids
            off += u
        c[off:off + k_train] = trows
        c[off + k_train:] = p.active[trows]
        ts[i] = p.t
    return w, ctrl, ts


@functools.partial(jax.jit,
                   static_argnames=("spec", "lr", "local_steps", "batch_size",
                                    "kernels", "col_sparse", "fused_sgd",
                                    "with_losses", "mix_is_train", "shd"),
                   donate_argnums=(0,))
def mega_round_step(buf: jnp.ndarray, w_rows: jnp.ndarray, ctrl: jnp.ndarray,
                    ts: jnp.ndarray, data_x: jnp.ndarray, data_y: jnp.ndarray,
                    part_idx: jnp.ndarray, part_sizes: jnp.ndarray, key,
                    *, spec: FS.FlatSpec, lr: float, local_steps: int,
                    batch_size: int, kernels=None,
                    col_sparse: bool = False, fused_sgd: bool = False,
                    with_losses: bool = True, mix_is_train: bool = False,
                    shd=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """H horizon-planned rounds as ONE donated ``lax.scan`` dispatch.

    The control plane is model-value-independent, so ``core.planner`` resolves
    H rounds of WAA/PTCA/staleness bookkeeping on host and this scan replays
    them back-to-back on device — one dispatch + three H2D transfers per
    horizon instead of per round, which is the entire host↔device round-trip
    cost of the steady regime.  Inputs are the ``pack_horizon`` stacks:
    ``w_rows (H, K_mix, N)``, ``ctrl (H, K_mix + 2*K_train)``, ``ts (H,)``
    round indices.

    Batch sampling is buffer-independent, so the whole horizon's minibatches
    are drawn OUTSIDE the scan as one batched op (each round still keyed by
    fold_in(key, t) + per-worker fold_in, exactly like ``round_step``, so any
    horizon split yields bit-identical buffers); only the mix + SGD — the
    part that actually depends on the evolving buffer — runs per scan step.
    ``col_sparse``/``fused_sgd`` select the column-sparse contraction and
    the unrolled SGD lowering exactly as in ``round_step`` (with
    ``pack_horizon(col_sparse=True)`` stacks: ``w_rows (H, K_mix, U)`` and
    the per-round ``col_ids`` riding in ``ctrl``); ``shd`` (static) runs the
    whole scan mesh-sharded with the buffer row-partitioned across steps.
    Returns (new buffer, (H, N) per-round losses).
    """
    k_mix = w_rows.shape[1]
    u = w_rows.shape[2] if col_sparse and k_mix else 0
    mix_ids, col_ids, train_ids, masks = split_ctrl(ctrl, k_mix, u)
    k_train = train_ids.shape[1]                   # (H, k) segments per round
    if k_train:
        with jax.named_scope("sample"):
            keys = jax.vmap(jax.random.fold_in, (None, 0))(key, ts)
            xb, yb = jax.vmap(
                lambda k, ids: sample_batches_device(
                    k, ids, data_x, data_y, part_idx[ids], part_sizes[ids],
                    local_steps, batch_size))(keys, train_ids)
    else:
        xb = yb = jnp.zeros((ts.shape[0],), jnp.float32)        # scan filler

    # the scan step's ops carry one stable scope name, ``mega_round``
    if col_ids is not None:
        def body(b, xs):
            w, mids, cids, tids, mask, x, y = xs
            with jax.named_scope("mega_round"):
                return _mix_train_body(b, w, mids, cids, tids, mask, x, y,
                                       spec, lr, kernels, fused_sgd,
                                       with_losses, mix_is_train, shd)

        return jax.lax.scan(body, buf, (w_rows, mix_ids, col_ids, train_ids,
                                        masks, xb, yb))

    def body(b, xs):
        w, mids, tids, mask, x, y = xs
        with jax.named_scope("mega_round"):
            return _mix_train_body(b, w, mids, None, tids, mask, x, y, spec,
                                   lr, kernels, fused_sgd, with_losses,
                                   mix_is_train, shd)

    return jax.lax.scan(body, buf, (w_rows, mix_ids, train_ids, masks, xb, yb))
