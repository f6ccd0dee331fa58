"""DFL over the model zoo: a device-resident, planner-driven LM fleet.

The protocol layer is unchanged — DySTop only needs param pytrees, a local
step, and byte counts — which is exactly the arch-agnosticism claim of
DESIGN.md §4, demonstrated end-to-end here on the SAME engine as the
simulation plane:

  * ``LMFleet`` holds all N replicas' params AND optimizer state as two
    resident flat buffers — ``(N, P)`` / ``(N, S)`` f32, ravel metadata in a
    ``flat_state.FleetSpec`` — flattened ONCE at init; pytrees are
    materialized only at checkpoint/eval-by-pytree boundaries (the
    ``stacked_params`` / ``stacked_opt`` properties).
  * ``core.planner.HorizonPlanner`` drives the control plane; bucket-uniform
    chunks of ``PlannedRound``s (``core.planner.chunk_spans``) dispatch as
    ONE donated ``lax.scan`` mega-round (``LMEngine``), with row- or
    column-sparse Eq. 4 mixing picked per chunk by the
    ``aggregation.prefer_cols`` traffic model and the ``mix_is_train``
    fusion feeding Eq. 4 output straight into Eq. 5.
  * local training is a GATHERED-ACTIVE-ROW step: only the k activated
    workers' rows are read, one after another, through AD + the generic
    ``Optimizer.update`` (adam/sgd/adafactor — any state pytree), and
    written back in place.  The tests hold it to a plain per-call-flatten
    reference (``tests/lm_reference.py``).

CPU-budget note: use smoke-geometry configs (``registry.get_smoke_config``)
for interactive runs; the code path is identical for full configs on real
hardware.
"""
from __future__ import annotations

import dataclasses
import functools
import pathlib
from typing import Any, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.checkpoint import io as CIO
from repro.configs.base import ModelConfig
from repro.core.aggregation import prefer_cols
from repro.core.planner import (HorizonPlanner, PlannedRound, bucket_key,
                                chunk_spans, mix_is_train)
from repro.core.scenarios import resolve_scenario
from repro.core.trace import Trace
from repro.data.synthetic import make_token_stream
from repro.dfl import flat_state as FS
from repro.dfl import worker as WK
from repro.dfl.network import (EdgeNetwork, NetworkConfig,
                               heterogeneous_compute_times)
from repro.dfl.pipeline import DispatchPipeline, count_dispatch
from repro.kernels.config import KernelConfig
from repro.models import registry as R
from repro.optim import Optimizer, get_optimizer
from repro.sharding.rules import shard_map

Params = Dict[str, Any]


@dataclasses.dataclass
class LMFleet:
    """N worker replicas of one architecture, device-resident for life.

    ``pbuf`` (N, P) and ``obuf`` (N, S) are the ONLY materialized storage;
    ``spec`` carries the ravel metadata for both.  The ``stacked_params`` /
    ``stacked_opt`` properties materialize (and, on assignment, re-flatten)
    the stacked pytrees — that round-trip is exact (f32 storage holds bf16
    params and int32 step counters losslessly) and is paid only at those
    boundaries, never per round.
    """
    cfg: ModelConfig
    pbuf: jnp.ndarray               # (N, P) f32 resident params
    obuf: jnp.ndarray               # (N, S) f32 resident optimizer state
    spec: FS.FleetSpec
    optimizer: Optimizer
    n_workers: int

    @property
    def stacked_params(self) -> Params:
        """Stacked param pytree (leaves (N, ...)) — checkpoint/reference
        view."""
        return FS.unflatten(self.pbuf, self.spec.params)

    @stacked_params.setter
    def stacked_params(self, value: Params) -> None:
        self.pbuf, pspec = FS.flatten_stacked(value)
        self.spec = FS.FleetSpec(params=pspec, opt=self.spec.opt)

    @property
    def stacked_opt(self) -> Params:
        return FS.unflatten(self.obuf, self.spec.opt)

    @stacked_opt.setter
    def stacked_opt(self, value: Params) -> None:
        self.obuf, ospec = FS.flatten_stacked(value)
        self.spec = FS.FleetSpec(params=self.spec.params, opt=ospec)

    @property
    def model_bytes(self) -> int:
        """Bytes of one replica at its shipped dtypes (Eq. 10 pricing)."""
        return FS.nbytes_of(self.spec.params)

    @property
    def opt_bytes(self) -> int:
        return FS.nbytes_of(self.spec.opt)


@functools.lru_cache(maxsize=None)
def _cached_optimizer(name: str, lr: float) -> Optimizer:
    """One ``Optimizer`` instance per (name, lr): optimizers are frozen and
    stateless, and a stable instance keys the jit/engine caches so repeated
    ``run_lm_federation`` calls (tests, benchmark reps) stay compile-warm."""
    return get_optimizer(name, lr)


def init_fleet(cfg: ModelConfig, n_workers: int, optimizer: str = "adam",
               lr: float = 1e-3, seed: int = 0) -> LMFleet:
    """All workers start from w_0 (paper Thm. 1's shared init) — flattened
    ONCE into the resident buffers; no pytree survives past this call.

    One worker's params and optimizer state are raveled into a row each and
    the row is broadcast to N: stacking N pytree copies first would hold
    the fleet twice over, which does not fit one chip at published widths.
    """
    opt = _cached_optimizer(optimizer, lr)
    params, _ = R.init_params(cfg, jax.random.PRNGKey(seed))
    opt_state = opt.init(params)

    def stacked_spec(tree):
        return FS.spec_of(jax.tree.map(
            lambda l: jax.ShapeDtypeStruct((n_workers,) + l.shape, l.dtype),
            tree))

    spec = FS.FleetSpec(params=stacked_spec(params),
                        opt=stacked_spec(opt_state))
    prow = FS.ravel_row(params, spec.params)
    orow = FS.ravel_row(opt_state, spec.opt)
    del params, opt_state
    pbuf = jnp.broadcast_to(prow, (n_workers, prow.shape[0]))
    obuf = jnp.broadcast_to(orow, (n_workers, orow.shape[0]))
    return LMFleet(cfg=cfg, pbuf=pbuf, obuf=obuf, spec=spec, optimizer=opt,
                   n_workers=n_workers)


def worker_streams(cfg: ModelConfig, n_workers: int, batch: int, seq: int,
                   seed: int = 0, noniid_offset: bool = True,
                   skip_rounds: int = 0
                   ) -> Iterator[Dict[str, np.ndarray]]:
    """Per-worker token batches.  Non-IID-ness: each worker samples from a
    different slice of a long stream (distinct local distributions, the LM
    analogue of the Dirichlet class skew).

    Vectorized: one zero-copy ``sliding_window_view`` over the stream, one
    fancy-indexed gather per yield — replacing the per-worker per-batch
    Python slicing loop.  The per-worker ``rng.integers`` draws are kept
    EXACTLY as the scalar loop made them (same call order, same bounds): the
    rng stream is the trajectory, so only the transform is vectorized.

    ``skip_rounds`` (checkpoint/resume): fast-forward the stream past that
    many yields by burning the identical rng draws WITHOUT paying the window
    gathers — the first yield afterwards is bit-identical to yield
    ``skip_rounds + 1`` of a fresh stream.
    """
    stream = make_token_stream(cfg.vocab_size, 400_000, seed=seed)
    n = len(stream) - seq - 1
    rng = np.random.default_rng(seed)
    slice_len = n // n_workers if noniid_offset else n
    # row s of the view is stream[s : s + seq + 1] — tokens + shifted labels
    windows = np.lib.stride_tricks.sliding_window_view(stream, seq + 1)

    def draw(w: int) -> np.ndarray:
        lo = w * slice_len % max(n - slice_len, 1) if noniid_offset else 0
        return rng.integers(lo, lo + max(slice_len - seq - 1, 1), size=batch)

    for _ in range(skip_rounds):
        for w in range(n_workers):
            draw(w)
    while True:
        starts = np.empty((n_workers, batch), np.int64)
        for w in range(n_workers):
            starts[w] = draw(w)
        win = windows[starts]                   # ONE gather: (W, B, seq + 1)
        yield {"tokens": np.ascontiguousarray(win[..., :-1]),
               "labels": np.ascontiguousarray(win[..., 1:]),
               "loss_mask": np.ones((n_workers, batch, seq), np.float32)}


# --------------------------------------------------------------------------- #
# the resident engine: gathered-active-row rounds as lax.scan mega-dispatches
# --------------------------------------------------------------------------- #


_ENGINE_CACHE: Dict[tuple, "LMEngine"] = {}


def get_lm_engine(cfg: ModelConfig, optimizer: Optimizer,
                  spec: FS.FleetSpec, kernels=None,
                  shd=None) -> "LMEngine":
    """One ``LMEngine`` per (cfg, optimizer, spec, kernels, shd): the
    engine owns the jitted scan variants, so sharing it across runs keeps
    repeated federations (benchmark reps, test A/Bs) compile-warm.
    ``kernels`` (a frozen, hashable ``KernelConfig``) is part of the cache
    key, so reference and Pallas engines never share jits."""
    key = (cfg, optimizer, spec, kernels, shd)
    if key not in _ENGINE_CACHE:
        _ENGINE_CACHE[key] = LMEngine(cfg, optimizer, spec,
                                      kernels=kernels, shd=shd)
    return _ENGINE_CACHE[key]


class LMEngine:
    """Jitted round dispatch for one fleet's (cfg, optimizer, spec) triple.

    ``dispatch_chunk`` executes a bucket-uniform chunk of ``PlannedRound``s
    as ONE donated ``lax.scan``: per scan step, Eq. 4 mixes the k
    non-identity rows (row- or column-sparse exactly like the simulation
    plane, via ``worker.mix_flat`` / ``mix_flat_cols``), then the activated
    rows of BOTH buffers run one AD train step each through the generic
    ``Optimizer.update`` and are written back — inactive rows, bucket
    padding included, are never touched, so model-plane work is O(k), not
    O(N).  Under the ``mix_is_train`` fusion (mix rows == train rows, every
    DySTop round) the mixed sub-buffer feeds the train step directly,
    skipping the intermediate scatter.

    Jits are cached per (col_sparse, fuse) variant; shapes bucket through
    ``pack_chunk``, so the compile count stays O(log N) per variant.

    ``shd`` (a ``sharding.rules.FleetSharding``) runs the engine mesh-
    sharded: ``pbuf``/``obuf`` stay row-partitioned over the fleet axis
    across dispatches, the mix lowers to the collective contractions of
    ``kernels.aggregate`` (union all_gather / shard-local slabs + psum), and
    each shard trains the activated rows it holds.
    """

    def __init__(self, cfg: ModelConfig, optimizer: Optimizer,
                 spec: FS.FleetSpec, kernels=None, shd=None):
        self.cfg, self.opt, self.spec = cfg, optimizer, spec
        self.kernels = kernels
        self.shd = shd
        self._mega_cache: dict = {}

    def _train_one(self, pvec, state, t, l):
        """One worker's AD train step on its flat params row and its
        optimizer state; returns the new params and optimizer state raveled
        into (P,) / (S,) rows, and the loss."""
        cfg, opt, spec = self.cfg, self.opt, self.spec
        with jax.named_scope("fwd_bwd"):
            params = FS.unravel_row(pvec, spec.params)
            batch = {"tokens": t, "labels": l,
                     "loss_mask": jnp.ones(t.shape, jnp.float32)}
            (loss, _), grads = jax.value_and_grad(
                lambda p: R.compute_loss(cfg, p, batch), has_aux=True)(params)
        with jax.named_scope(opt.name):
            new_p, new_s = opt.update(grads, state, params)
        with jax.named_scope("write_back"):
            return (FS.ravel_row(new_p, spec.params),
                    FS.ravel_row(new_s, spec.opt), loss)

    def _train_rows(self, pbuf, obuf, sub, tids, mask, tok, lab):
        """Train the k gathered rows one after another, each written back in
        place; returns (pbuf, obuf, (N,) losses).  ``sub`` holds the mixed
        rows of the fused path (None: read row ``tids[i]`` of ``pbuf``).

        In sequence, not vmapped, for two reasons.  Memory: a vmap over k
        workers keeps k copies of every per-worker temporary (bf16 params,
        grads, updated f32 rows), which for smollm-135m at its published
        widths does not fit one 16 GB chip; in sequence one worker's are live
        at a time.  Numerics: every row runs the same program whatever the
        bucket size k, so ``min_bucket`` and the per-call-flatten reference
        (which maps over workers the same way) round alike.

        A padding row (``mask[i] == 0``: an idle row, ``padded_rows``) skips
        its whole step by a ``lax.cond``: it is neither read, trained nor
        written, so its rows and its zero loss slot come back as they went
        in.  The untaken branch passes the carry through, so the buffers
        stay in place.

        With ``shd`` each shard runs the loop over all k ids and trains the
        real rows it holds (the same predicate, narrowed to the shard's
        block), so ``pbuf``/``obuf`` rows never leave their shard; the
        per-row losses (zero elsewhere) meet in one psum.
        """
        n = pbuf.shape[0]
        shd = self.shd

        def loop(pb, ob, sub, first, blk, losses):
            def body(i, carry):
                r = tids[i] - first

                def train(c):
                    pb, ob, ls = c
                    with jax.named_scope("gather"):
                        pvec = (sub[i] if sub is not None else
                                jax.lax.dynamic_index_in_dim(pb, r, 0, False))
                        # leaf by leaf: a gathered (S,) row is copied once
                        # more on the TPU, a GB of temporaries at 135M
                        state = FS.unravel_row_at(ob, r, self.spec.opt)
                    new_p, new_o, loss = self._train_one(pvec, state, tok[i],
                                                         lab[i])
                    with jax.named_scope("write_back"):
                        put = jax.lax.dynamic_update_index_in_dim
                        return (put(pb, new_p, r, 0), put(ob, new_o, r, 0),
                                ls.at[tids[i]].set(loss))

                real = mask[i] > 0
                if blk is not None:
                    real = real & (r >= 0) & (r < blk)
                return jax.lax.cond(real, train, lambda c: c, carry)

            return jax.lax.fori_loop(0, tids.shape[0], body, (pb, ob, losses))

        losses = jnp.zeros((n,), jnp.float32)
        if shd is None:
            return loop(pbuf, obuf, sub, 0, None, losses)

        ax = shd.axis

        def per_shard(pb, ob, *sub_):
            blk = pb.shape[0]
            first = jax.lax.axis_index(ax) * blk
            pb, ob, ls = loop(pb, ob, sub_[0] if sub_ else None, first, blk,
                              losses)
            return pb, ob, jax.lax.psum(ls, ax)

        # check_vma=False: the model's Pallas kernels (KernelConfig pallas)
        # run inside, and under JAX 0.9 the check needs every pallas_call
        # output shape to declare its varying axes
        rows, rep = PartitionSpec(ax), PartitionSpec()
        args = (pbuf, obuf) + ((sub,) if sub is not None else ())
        return shard_map(per_shard, mesh=shd.mesh,
                         in_specs=(rows, rows) + (rep,) * (len(args) - 2),
                         out_specs=(rows, rows, rep), check_vma=False)(*args)

    def _round_body(self, pbuf, obuf, w, mids, cids, tids, mask, tok, lab,
                    fuse: bool):
        """One round of the scan; ``tok``/``lab`` are the activated rows'
        batches, (k_train, B, S) in train-row order."""
        shd = self.shd

        def pin(pb, ob, ls):
            if shd is None:
                return pb, ob, ls
            return (jax.lax.with_sharding_constraint(pb, shd.rows()),
                    jax.lax.with_sharding_constraint(ob, shd.rows()),
                    jax.lax.with_sharding_constraint(ls, shd.replicated()))

        k_mix, k_train = w.shape[0], tids.shape[0]
        if fuse and k_mix and k_train:
            # mix rows == train rows: Eq. 4 output feeds Eq. 5 directly
            with jax.named_scope("mix"):
                sub = WK._mix_rows(pbuf, w, cids, self.kernels, shd)
            return pin(*self._train_rows(pbuf, obuf, sub, tids, mask,
                                         tok, lab))
        if k_mix:
            with jax.named_scope("mix"):
                pbuf = (WK.mix_flat_cols(pbuf, w, mids, cids, self.kernels,
                                         shd=shd)
                        if cids is not None
                        else WK.mix_flat(pbuf, w, mids, self.kernels,
                                         shd=shd))
        if k_train:
            return pin(*self._train_rows(pbuf, obuf, None, tids, mask,
                                         tok, lab))
        return pin(pbuf, obuf, jnp.zeros((pbuf.shape[0],), jnp.float32))

    def _mega(self, col_sparse: bool, fuse: bool):
        key = (col_sparse, fuse)
        if key in self._mega_cache:
            return self._mega_cache[key]

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def mega(pbuf, obuf, w_rows, ctrl, tokens, labels):
            k_mix = w_rows.shape[1]
            u = w_rows.shape[2] if col_sparse and k_mix else 0
            mix_ids, col_ids, train_ids, masks = WK.split_ctrl(ctrl, k_mix, u)
            # the scan step's ops carry one stable scope name, ``mega_round``
            if col_ids is not None:
                def body(c, xs):
                    w, mi, ci, ti, m, tk, lb = xs
                    with jax.named_scope("mega_round"):
                        pb, ob, ls = self._round_body(c[0], c[1], w, mi, ci,
                                                      ti, m, tk, lb, fuse)
                    return (pb, ob), ls
                xs = (w_rows, mix_ids, col_ids, train_ids, masks,
                      tokens, labels)
            else:
                def body(c, xs):
                    w, mi, ti, m, tk, lb = xs
                    with jax.named_scope("mega_round"):
                        pb, ob, ls = self._round_body(c[0], c[1], w, mi, None,
                                                      ti, m, tk, lb, fuse)
                    return (pb, ob), ls
                xs = (w_rows, mix_ids, train_ids, masks, tokens, labels)
            (pbuf, obuf), losses = jax.lax.scan(body, (pbuf, obuf), xs)
            return pbuf, obuf, losses

        self._mega_cache[key] = mega
        return mega

    def dispatch_chunk(self, pbuf, obuf, chunk: List[PlannedRound],
                       tokens: np.ndarray, labels: np.ndarray, *, key,
                       col_sparse: bool, fuse: bool, trace: Trace,
                       min_bucket: int = 8
                       ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """One bucket-uniform chunk -> one donated scan dispatch.

        ``tokens``/``labels`` are the full-N per-round batches (H, N, B, S).
        The k activated rows are gathered on HOST (by the padded train-id
        segments packed into ``ctrl``), so only (H, k, B, S) crosses the
        H2D boundary; padding rows skip their train step, so gathering by
        padded ids is value-exact.  ``key`` is the chunk's ``bucket_key``:
        the uniform-bucket packer (``worker.pack_chunk``) takes its shapes
        from it, and all four host arrays stage with ONE fused non-blocking
        ``jax.device_put``.  ``trace`` (the call's ``core.trace.Trace``)
        takes the ``pack``, ``stage`` and ``enqueue`` spans and the chunk's
        counters (``pipeline.count_dispatch``).

        Returns (new pbuf, new obuf, (H, N) per-round losses — zero rows for
        idle workers).
        """
        shards = self.shd.n_shards if self.shd is not None else 1
        with trace.span("pack"):
            w, c, _ = WK.pack_chunk(chunk, key, min_bucket=min_bucket,
                                    col_sparse=col_sparse, shards=shards)
            if self.shd is not None and not (col_sparse and w.shape[1]):
                w = WK.pad_w_cols(w, pbuf.shape[0])
            k_mix = w.shape[1]
            u = w.shape[2] if col_sparse and k_mix else 0
            # one ctrl-layout definition: the same split the device scan
            # performs
            _, _, tids, _ = WK.split_ctrl(c, k_mix, u)
            h_ix = np.arange(len(chunk))[:, None]
            tokens = tokens[h_ix, tids]                      # (H, k, B, S)
            labels = labels[h_ix, tids]
        with trace.span("stage"):
            if self.shd is not None:
                put = self.shd.put
                w_j, c_j = put(w), put(c)
                tk_j, lb_j = put(tokens), put(labels)
            else:
                w_j, c_j, tk_j, lb_j = jax.device_put((w, c, tokens, labels))
        with trace.span("enqueue"):
            out = self._mega(col_sparse, fuse)(pbuf, obuf, w_j, c_j, tk_j,
                                               lb_j)
        count_dispatch(trace, len(chunk), k_mix, tids.shape[-1],
                       w.nbytes + c.nbytes + tokens.nbytes + labels.nbytes,
                       WK.narrow_mix(self.kernels, w.shape, bool(u), shards))
        return out

    @functools.cached_property
    def eval_global(self):
        """Jitted Eq. 11 eval: ``alpha @ pbuf`` + unravel + one forward.

        With ``shd`` the forward runs replicated inside a ``shard_map``:
        the partitioner cannot split the model's Mosaic kernels, so every
        device computes the same loss on the replicated global row
        (``check_vma=False`` for the reason in ``_train_rows``)."""
        cfg, spec, shd = self.cfg, self.spec, self.shd

        def loss_of(row, tokens, labels):
            batch = {"tokens": tokens, "labels": labels,
                     "loss_mask": jnp.ones(tokens.shape, jnp.float32)}
            loss, _ = R.compute_loss(cfg, FS.unravel_row(row, spec.params),
                                     batch)
            return loss

        if shd is not None:
            rep = PartitionSpec()
            loss_of = shard_map(loss_of, mesh=shd.mesh, in_specs=(rep,) * 3,
                                out_specs=rep, check_vma=False)

        @jax.jit
        def ev(pbuf, alpha, tokens, labels):
            with jax.named_scope("eval"):
                return loss_of(FS.weighted_row(pbuf, alpha), tokens, labels)

        return ev


# --------------------------------------------------------------------------- #
# planner-driven federation driver (both planes share the control plane)
# --------------------------------------------------------------------------- #


@dataclasses.dataclass
class LMRunConfig:
    """LM-plane run configuration (the SimConfig of the LM fleet).

    One engine: the device-resident gathered-active-row ``LMEngine`` with
    ``scan_horizon`` mega-rounds behind the async dispatch pipeline
    (``pipeline_depth`` chunks in flight, >= 1; see ``SimConfig``).
    ``min_bucket=2``: LM fleets are small (8-64 workers), so fine-grained
    shape buckets keep the gathered row set near the true activation count.

    ``mesh_shards > 1`` row-partitions ``pbuf`` / ``obuf`` over the 1-D
    fleet mesh — N pads to a shard multiple with permanently-idle rows,
    control trajectories stay bit-identical, model state agrees to f32
    reduction-order tolerance.
    """
    n_workers: int = 8
    n_rounds: int = 30
    batch: int = 4
    seq: int = 64
    optimizer: str = "adam"
    lr: float = 1e-3
    scan_horizon: int = 8
    pipeline_depth: int = 1           # in-flight chunks behind the staged one
                                      #   (>= 1; 1 = double buffering) —
                                      #   bit-identical at any depth
    mesh_shards: int = 1
    min_bucket: int = 2
    eval_every: int = 5
    seed: int = 0
    tau_bound: int = 4
    bandwidth_budget: float = 6.0
    link_timeout_s: float = 5.0
    sync_link_timeout_s: float = 30.0
    comm_range_m: float = 80.0
    compute_sigma: float = 0.6
    kernels: Optional["KernelConfig"] = None  # kernel-plane config (see
                                      #   SimConfig.kernels): backend="pallas"
                                      #   routes Eq. 4 mixing through the
                                      #   panel kernels AND the zoo forward
                                      #   passes through flash_attention /
                                      #   ssd_chunk / moe_router (via
                                      #   ModelConfig.kernels); composes with
                                      #   mesh_shards via shard_map
    failure_prob: float = 0.0         # stochastic edge dynamics (as SimConfig)
    failure_persist: float = 0.5
    scenario: Optional[object] = None # fault plane (core.scenarios): None,
                                      #   a preset name, or a ScenarioSchedule
    checkpoint_every: int = 0         # rounds between snapshots; 0 = off
    checkpoint_dir: Optional[str] = None
    checkpoint_keep: int = 3

    def __post_init__(self):
        for f in ("failure_prob", "failure_persist"):
            v = getattr(self, f)
            if not (0.0 <= v <= 1.0):
                raise ValueError(
                    f"LMRunConfig.{f} must be a probability in [0, 1], got "
                    f"{v} — out-of-range values silently degenerate the "
                    f"edge-dynamics mask to 'never' or 'always'")
        for f in ("link_timeout_s", "sync_link_timeout_s", "lr",
                  "bandwidth_budget", "comm_range_m"):
            v = getattr(self, f)
            if v <= 0:
                raise ValueError(f"LMRunConfig.{f} must be > 0, got {v}")
        for f in ("n_workers", "n_rounds", "batch", "seq", "eval_every",
                  "scan_horizon", "pipeline_depth", "mesh_shards",
                  "min_bucket"):
            v = getattr(self, f)
            if v < 1:
                raise ValueError(f"LMRunConfig.{f} must be >= 1, got {v}")
        if self.checkpoint_every < 0:
            raise ValueError(f"LMRunConfig.checkpoint_every must be >= 0 "
                             f"(0 disables snapshots), got "
                             f"{self.checkpoint_every}")
        if self.checkpoint_every > 0 and not self.checkpoint_dir:
            raise ValueError(
                "LMRunConfig.checkpoint_every > 0 needs checkpoint_dir: "
                "pass the directory snapshots should land in")
        if self.kernels is not None and not isinstance(self.kernels,
                                                       KernelConfig):
            raise ValueError(
                f"LMRunConfig.kernels must be a kernels.config.KernelConfig "
                f"(or None for the reference default), got "
                f"{type(self.kernels).__name__}")
        if self.kernels is None:
            self.kernels = KernelConfig()
        self.kernels.check_executable("LMRunConfig.kernels")


@dataclasses.dataclass
class LMHistory:
    """Trajectory of one LM federation run (units as ``simulator.History``:
    sim_time in simulated seconds, comm in GB, staleness in rounds,
    ``wall_s`` and the ``*_wall_s`` spans in real host seconds).

    Host time and ``counts`` as ``simulator.History`` has them, written by
    ``core.trace.Trace``, with one more span: ``stream`` (the round's token
    batches drawn from ``worker_streams``).  Here ``pack`` also stacks the
    chunk's token batches, ``eval`` reads the queued per-round losses back,
    and ``drain`` (host blocked on the device, not the device's execute
    time) also takes the last read of the losses.  Emitted by
    ``benchmarks/run.py --json`` via the lm_fleet suite."""
    rounds: List[int] = dataclasses.field(default_factory=list)
    sim_time: List[float] = dataclasses.field(default_factory=list)
    comm_gb: List[float] = dataclasses.field(default_factory=list)
    loss_global: List[float] = dataclasses.field(default_factory=list)
    loss_local: List[float] = dataclasses.field(default_factory=list)
    staleness_avg: List[float] = dataclasses.field(default_factory=list)
    staleness_max: List[int] = dataclasses.field(default_factory=list)
    round_durations: List[float] = dataclasses.field(default_factory=list)
    round_active: List[int] = dataclasses.field(default_factory=list)
    round_loss: List[float] = dataclasses.field(default_factory=list)
    wall_s: float = 0.0
    eval_wall_s: float = 0.0
    setup_wall_s: float = 0.0
    plan_wall_s: float = 0.0
    stream_wall_s: float = 0.0
    pack_wall_s: float = 0.0
    stage_wall_s: float = 0.0
    enqueue_wall_s: float = 0.0
    drain_wall_s: float = 0.0
    snapshot_wall_s: float = 0.0
    counts: Dict[str, int] = dataclasses.field(default_factory=dict)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def run_lm_federation(mechanism, cfg: ModelConfig, run: LMRunConfig,
                      resume_from: Optional[str] = None
                      ) -> Tuple[LMFleet, LMHistory]:
    """Federate N replicas of ``cfg`` under ``mechanism``, planner-driven.

    The ``HorizonPlanner`` owns ALL control state exactly as in
    ``run_simulation``; one token-stream draw happens per planned round in
    plan order, so the batch trajectory — like the control trajectory — is
    bit-for-bit independent of ``scan_horizon`` and ``pipeline_depth``.

    ``resume_from`` (see ``run_simulation``): a snapshot file or checkpoint
    directory from a ``checkpoint_every`` run of the same config; setup
    replays from the seed, then the resident buffers (f32 storage holds the
    bf16/int32 leaves losslessly, so the round-trip is bitwise), full planner
    state, rng streams, and history are restored, and the token stream
    fast-forwards past the checkpointed rounds (``worker_streams``
    ``skip_rounds``) — the continuation is bit-identical.
    """
    hist = LMHistory()
    tr = Trace(hist)
    with tr.span("setup"):
        n = run.n_workers
        if run.kernels is not None and cfg.kernels != run.kernels:
            # one kernel plane per run: the fleet's forward pass follows the
            # same KernelConfig that drives the Eq. 4/5 aggregation kernels
            cfg = dataclasses.replace(cfg, kernels=run.kernels)
        shd = None
        if run.mesh_shards > 1:
            from repro.sharding.rules import FleetSharding
            shd = FleetSharding.create(run.mesh_shards)
        rng = np.random.default_rng(run.seed)
        fleet = init_fleet(cfg, n, optimizer=run.optimizer, lr=run.lr,
                           seed=run.seed)
        if shd is not None:
            fleet.pbuf = shd.put_rows_padded(fleet.pbuf)
            fleet.obuf = shd.put_rows_padded(fleet.obuf)
        streams = worker_streams(cfg, n, run.batch, run.seq, seed=run.seed)
        ev = next(worker_streams(cfg, 1, run.batch, run.seq,
                                 seed=run.seed + 1))
        eval_tok = jnp.asarray(ev["tokens"][0])
        eval_lab = jnp.asarray(ev["labels"][0])
        net = EdgeNetwork(NetworkConfig(n_workers=n,
                                        comm_range_m=run.comm_range_m), rng)
        h_i = heterogeneous_compute_times(n, 1.0, rng, sigma=run.compute_sigma)
        model_bytes = float(fleet.model_bytes)
        scen = resolve_scenario(run.scenario, n, run.n_rounds, dist=net.dist,
                                comm_range_m=net.cfg.comm_range_m)
        planner = HorizonPlanner(
            mechanism, h_i=h_i, in_range=net.in_range(),
            exp_link_time=net.expected_link_time(model_bytes),
            model_bytes=model_bytes, class_counts=np.ones((n, 2)),
            data_sizes=np.ones(n), net=net, rng=rng, tau_bound=run.tau_bound,
            bandwidth_budget=run.bandwidth_budget,
            link_timeout_s=run.link_timeout_s,
            sync_link_timeout_s=run.sync_link_timeout_s,
            failure_prob=run.failure_prob, failure_persist=run.failure_persist,
            mesh_shards=run.mesh_shards, scenario=scen)
        alpha = jnp.full((n,), 1.0 / n, jnp.float32)
        # Eq. 11 weights over the PADDED row axis: padding rows weigh zero
        alpha_eval = alpha if shd is None else shd.put(
            jnp.concatenate([alpha, jnp.zeros((shd.pad(n),), jnp.float32)]))

        # --- crash-safe resume: overwrite the deterministic setup's mutable
        # state (resident buffers, planner, rng stream, history but its host
        # times) and fast-forward the token stream past the checkpointed
        # rounds
        if resume_from is not None:
            ck = pathlib.Path(resume_from)
            if ck.is_dir():
                found = CIO.latest_checkpoint(ck)
                if found is None:
                    raise FileNotFoundError(
                        f"resume_from={ck} is a directory with no "
                        f"ckpt_round*.npz snapshot in it")
                ck = found
            arr_tmpl = {k: np.zeros_like(v)
                        for k, v in planner.state_dict()["arrays"].items()}
            model_tmpl = {
                "pbuf": np.zeros((n, int(fleet.pbuf.shape[1])), np.float32),
                "obuf": np.zeros((n, int(fleet.obuf.shape[1])), np.float32)}
            model, arrays, extra = CIO.load_checkpoint(ck, model_tmpl,
                                                       arr_tmpl)
            saved_cfg = extra.get("config", {})
            checks = {"plane": "lm", "n_workers": n, "seed": run.seed,
                      "mesh_shards": run.mesh_shards,
                      "scenario": scen.schedule.name if scen else None}
            for k, want in checks.items():
                if k in saved_cfg and saved_cfg[k] != want:
                    raise ValueError(
                        f"resume config mismatch: snapshot {ck.name} was "
                        f"written with {k}={saved_cfg[k]!r} but this run has "
                        f"{k}={want!r} — resuming must use the identical "
                        f"configuration")
            planner.load_state({"arrays": arrays,
                                "scalars": extra["planner_scalars"],
                                "rng_state": extra["planner_rng"]})
            pbuf, obuf = jnp.asarray(model["pbuf"]), jnp.asarray(model["obuf"])
            if shd is not None:   # rebuild padded residency as init did
                pbuf = shd.put_rows_padded(pbuf)
                obuf = shd.put_rows_padded(obuf)
            fleet.pbuf, fleet.obuf = pbuf, obuf
            streams = worker_streams(cfg, n, run.batch, run.seq, seed=run.seed,
                                     skip_rounds=int(extra["round"]))
            for k, v in extra["history"].items():
                if hasattr(hist, k) and not k.endswith("wall_s"):
                    setattr(hist, k, v)

        engine = get_lm_engine(cfg, fleet.optimizer, fleet.spec,
                               kernels=run.kernels, shd=shd)
        # async dispatch pipeline (as run_simulation): overlaps host
        # plan/pack/stage with the device scan
        pipe = DispatchPipeline(run.pipeline_depth, tr)

    pending: List[Tuple[PlannedRound, Dict[str, np.ndarray]]] = []
    # per entry: one chunk's (H, N) device loss block with its H active
    # masks, so no per-round slice ops land on the dispatch critical path
    # and nothing is fetched before a history boundary
    loss_rows: List[Tuple[Any, List[np.ndarray]]] = []

    def flush():
        plans = [p for p, _ in pending]
        with tr.span("pack"):
            spans = list(chunk_spans(plans, n, min_bucket=run.min_bucket,
                                     mesh_shards=run.mesh_shards))
        for lo, hi, key in spans:
            chunk = plans[lo:hi]
            col = prefer_cols(key[0], key[2], n)
            fuse = all(mix_is_train(p) for p in chunk)
            with tr.span("pack"):
                tokens = np.stack([b["tokens"] for _, b in pending[lo:hi]])
                labels = np.stack([b["labels"] for _, b in pending[lo:hi]])
            fleet.pbuf, fleet.obuf, losses = engine.dispatch_chunk(
                fleet.pbuf, fleet.obuf, chunk, tokens, labels, key=key,
                col_sparse=col, fuse=fuse, min_bucket=run.min_bucket,
                trace=tr)
            loss_rows.append((losses, [p.active for p in chunk]))
            # the loss block is the non-donated output of the chunk's
            # executable — the in-flight token (pbuf/obuf are donated into
            # the next dispatch, see DispatchPipeline)
            pipe.submit(losses)
        pending.clear()

    def drain_losses():
        """Materialize queued per-round losses (device sync happens at eval
        boundaries only, so round dispatches stay queued in between)."""
        for losses, actives in loss_rows:
            for row, active in zip(np.asarray(losses), actives):
                row = row[:len(active)]          # drop shard padding
                hist.round_loss.append(float(row[active].mean())
                                       if active.any() else 0.0)
        loss_rows.clear()

    def save_snapshot(t: int) -> None:
        """Atomic full-state snapshot (see ``run_simulation``).  The f32
        residency buffers hold the bf16/int32 leaves losslessly, so writing
        them is the bitwise checkpoint of the whole fleet."""
        snap = planner.state_dict()
        pb, ob = fleet.pbuf, fleet.obuf
        pb = pb if pb.shape[0] == n else pb[:n]
        with tr.span("drain"):
            jax.block_until_ready(pb)
        model = {"pbuf": np.asarray(pb),
                 "obuf": np.asarray(ob if ob.shape[0] == n else ob[:n])}
        extra = {
            "round": t,
            "planner_scalars": snap["scalars"],
            "planner_rng": snap["rng_state"],
            "history": hist.to_dict(),
            "config": {"plane": "lm", "n_workers": n, "seed": run.seed,
                       "mesh_shards": run.mesh_shards,
                       "arch": cfg.arch_id, "optimizer": run.optimizer,
                       "scenario": scen.schedule.name if scen else None},
        }
        CIO.save_checkpoint(CIO.checkpoint_path(run.checkpoint_dir, t),
                            model, opt_state=snap["arrays"], extra=extra)
        CIO.prune_checkpoints(run.checkpoint_dir, run.checkpoint_keep)

    while planner.t < run.n_rounds:
        with tr.span("plan"):
            p = planner.plan_round()
            # resolve the shape-bucket key at plan time (memoized on the
            # plan; as run_simulation) so chunk_spans only does lookups
            bucket_key(p, n, min_bucket=run.min_bucket,
                       mesh_shards=run.mesh_shards)
        with tr.span("stream"):
            b = next(streams)             # one draw per planned round
        hist.round_durations.append(p.duration)
        hist.round_active.append(int(p.active.sum()))
        pending.append((p, b))
        do_eval = p.t % run.eval_every == 0 or p.t == run.n_rounds
        do_ckpt = (run.checkpoint_every > 0
                   and p.t % run.checkpoint_every == 0)
        at_boundary = scen is not None and (p.t + 1) in scen.boundaries
        read_back = do_eval or do_ckpt or at_boundary
        if read_back or len(pending) >= run.scan_horizon:
            flush()
            # read-back boundaries drain: eval / drain_losses /
            # save_snapshot must see round-consistent resident buffers
            if read_back:
                pipe.drain()
        if do_eval:
            with tr.span("drain"):
                jax.block_until_ready(fleet.pbuf)
            with tr.span("eval"):
                drain_losses()
                lg = float(engine.eval_global(fleet.pbuf, alpha_eval,
                                              eval_tok, eval_lab))
                hist.rounds.append(p.t)
                hist.sim_time.append(planner.sim_clock)
                hist.comm_gb.append(planner.comm_bytes / 1e9)
                hist.loss_global.append(lg)
                hist.loss_local.append(hist.round_loss[-1])
                hist.staleness_avg.append(float(planner.st.tau.mean()))
                hist.staleness_max.append(int(planner.st.tau.max()))
        if do_ckpt:
            # after the eval (snapshot history carries the eval point) and
            # with losses drained, so round_loss is complete up to round t
            with tr.span("snapshot"):
                drain_losses()
                save_snapshot(p.t)

    flush()
    pipe.drain()
    with tr.span("drain"):
        drain_losses()
    if shd is not None and fleet.pbuf.shape[0] != n:
        fleet.pbuf = fleet.pbuf[:n]       # shed the shard padding: callers
        fleet.obuf = fleet.obuf[:n]       #   see the (N, ·) contract
    tr.finish()
    return fleet, hist
