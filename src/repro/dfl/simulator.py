"""Event-driven ADFL simulator (paper sections III, VI).

Time model:
  h_t^{i,cmp} = max(h_i - time-since-last-activation, 0)      (Eq. 7)
  H_t^i       = h^cmp + max over pulled in-links of h^com     (Eq. 8)
  H_t         = max over activated workers of H_t^i           (Eq. 9)
Bandwidth:
  B_t^i = (#in-links + #out-links) * b                        (Eq. 10)
Communication overhead metric = total model-transfer bytes.

Synchronous mechanisms (MATCHA, GossipFL) pay the FULL local-training time of
every worker every round (the straggler effect the paper measures).
"""
from __future__ import annotations

import dataclasses
import pathlib
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import io as CIO
from repro.core.aggregation import (mixing_rows, mixing_rows_cols,
                                    padded_rows, prefer_cols)
from repro.core.planner import (HorizonPlanner, PlannedRound, bucket_key,
                                chunk_spans, mix_is_train)
from repro.core.scenarios import resolve_scenario
from repro.core.trace import Trace
from repro.dfl.pipeline import DispatchPipeline, count_dispatch
from repro.core.protocol import Mechanism
from repro.data.partition import dirichlet_partition
from repro.data.synthetic import (ClassificationData, make_classification,
                                  train_test_split)
from repro.dfl import flat_state as FS
from repro.dfl import worker as WK
from repro.dfl.network import EdgeNetwork, NetworkConfig, heterogeneous_compute_times
from repro.kernels.config import KernelConfig


@dataclasses.dataclass
class SimConfig:
    """Simulation-plane configuration.

    One engine: the fleet's models live in one resident, donated (N, P)
    buffer, and each bucket-uniform chunk of planned rounds runs as one
    jitted dispatch of Eq. 4 mixing, on-device batch sampling and masked
    Eq. 5 SGD over the gathered active rows (``dfl.worker.round_step`` /
    ``mega_round_step``).  Per chunk the engine picks the row- or
    column-sparse contraction from the bucketed shapes
    (``aggregation.prefer_cols``) and, for the sim-plane MLP, the fused SGD
    lowering (``worker.fused_sgd_supported``).

    ``scan_horizon``: the control plane is model-value-independent, so
    ``core.planner.HorizonPlanner`` resolves up to this many rounds of
    WAA/PTCA/staleness bookkeeping ahead on host and the engine executes
    them as ONE donated ``lax.scan`` mega-dispatch — amortizing the
    per-round host↔device dispatch that dominates steady-regime cost.
    Horizons are chopped at eval / history points and at the round cap, so
    histories are identical at any horizon; ``scan_horizon=1`` dispatches
    per round via ``round_step``.

    ``pipeline_depth``: the async dispatch pipeline
    (``dfl.pipeline.DispatchPipeline``) lets the host plan, pack and stage
    chunk H+1 while the device executes chunk H, with at most ``depth``
    chunks in flight (1 = double buffering).  Trajectories are
    bit-identical at any depth — evals, snapshots and scenario-event
    flushes drain the pipeline first, so every read-back sees a
    round-consistent buffer (pinned by tests/test_pipeline.py, including
    SIGKILL-resume via scripts/chaos_check.py).
    """
    n_workers: int = 100
    n_rounds: int = 300               # round cap
    max_sim_time: Optional[float] = None   # stop at this simulated wall-clock;
                                      #   evals then happen on a time grid (the
                                      #   paper compares mechanisms at equal
                                      #   TIME — async runs many more rounds)
    phi: float = 1.0                  # Dirichlet non-IID level (1.0 = IID)
    tau_bound: int = 5
    V: float = 10.0
    batch_size: int = 32
    local_steps: int = 2
    lr: float = 0.05
    hidden: int = 64
    base_compute_s: float = 1.0
    compute_sigma: float = 0.75       # lognormal spread of worker speeds: the
                                      #   paper's testbed spans Jetson Nano ->
                                      #   Orin (~10x); 0.75 gives p95/p5 ~ 12x
    bandwidth_budget: float = 8.0     # transfers of size b per worker per round
    link_timeout_s: float = 5.0       # pull abort/retry ceiling: a faded link
                                      #   never stalls a round longer than this
                                      #   (async pulls degrade gracefully)
    sync_link_timeout_s: float = 30.0 # sync barriers CANNOT abort (the round
                                      #   needs every member) but do eventually
                                      #   retransmit once the channel recovers;
                                      #   this is the stall+retry ceiling
    model_bytes_scale: float = 25.0   # time/bandwidth accounting prices a
                                      #   paper-scale CNN (~0.7MB) rather than
                                      #   the 27KB MLP proxy we can afford to
                                      #   train on CPU; transfer ~= 1 batch
                                      #   time over a median link, as in VI-A
    failure_prob: float = 0.0         # edge dynamics: per-round chance a worker
                                      #   goes down (unreachable + can't train)
    failure_persist: float = 0.5      # chance a down worker stays down
    eval_every: int = 10
    target_accuracy: Optional[float] = None
    seed: int = 0
    kernels: Optional[KernelConfig] = None  # kernel-plane config (backend /
                                      #   interpret policy / block sizes);
                                      #   None = KernelConfig() = reference
                                      #   jnp lowerings.  backend="pallas"
                                      #   routes Eq. 4 mixing through the
                                      #   panel kernels and Eq. 5 through the
                                      #   VMEM-fused SGD kernel (interpret
                                      #   mode off-TPU — the CI oracle);
                                      #   composes with mesh_shards via
                                      #   per-shard shard_map
    scan_horizon: int = 8             # plan this many rounds ahead and
                                      #   execute them as one lax.scan
                                      #   mega-dispatch (see class
                                      #   docstring); 1 = per-round dispatch
    pipeline_depth: int = 1           # max chunks in flight on the async
                                      #   dispatch pipeline (see class
                                      #   docstring); >= 1.  Bit-identical
                                      #   trajectories at any depth
    mesh_shards: int = 1              # partition the resident
                                      #   (N, P) buffer + dataset row-wise
                                      #   over a 1-D device mesh
                                      #   (launch.mesh.make_fleet_mesh); the
                                      #   worker axis pads to a shard
                                      #   multiple with permanently-idle
                                      #   rows.  1 = single-device engine;
                                      #   >1 needs
                                      #   that many jax devices (CPU: set
                                      #   XLA_FLAGS=--xla_force_host_
                                      #   platform_device_count=K); both
                                      #   kernel backends compose (the
                                      #   Pallas path via shard_map panels).
                                      #   Control-plane trajectories are
                                      #   bit-identical at any shard count;
                                      #   learning curves agree to f32
                                      #   reduction-order tolerance
    min_bucket: int = 8               # smallest power-of-two
                                      #   shape bucket for gathered-row /
                                      #   column-union padding (the per-plane
                                      #   knob — the LM plane's small fleets
                                      #   default to LMRunConfig.min_bucket=2;
                                      #   the big sim fleets keep 8 so compile
                                      #   count stays O(log N)).  Any value
                                      #   yields bit-identical trajectories —
                                      #   bucket padding only adds zero-weight
                                      #   rows/columns — it trades compiled
                                      #   shape count against wasted row slots
    n_samples: int = 20000
    dim: int = 32
    scenario: Optional[object] = None # fault-injection plane (core.scenarios):
                                      #   None, a preset name ("churn20",
                                      #   "blackout", "straggler_tail",
                                      #   "mobile"), or a ScenarioSchedule.
                                      #   Overlays are rng-free, so a scenario
                                      #   replays bit-identically at every
                                      #   horizon, depth and shard count
    checkpoint_every: int = 0         # rounds between atomic snapshots
                                      #   (checkpoint/io); 0 = off.  Snapshot
                                      #   rounds force a chunk flush in EVERY
                                      #   run so resumed and uninterrupted
                                      #   trajectories share flush boundaries
    checkpoint_dir: Optional[str] = None   # where snapshots land (required
                                      #   when checkpoint_every > 0)
    checkpoint_keep: int = 3          # prune to this many newest snapshots

    def __post_init__(self):
        for f in ("failure_prob", "failure_persist"):
            v = getattr(self, f)
            if not (0.0 <= v <= 1.0):
                raise ValueError(
                    f"SimConfig.{f} must be a probability in [0, 1], got "
                    f"{v} — out-of-range values silently degenerate the "
                    f"edge-dynamics mask to 'never' or 'always'")
        for f in ("link_timeout_s", "sync_link_timeout_s", "base_compute_s",
                  "lr", "model_bytes_scale", "bandwidth_budget"):
            v = getattr(self, f)
            if v <= 0:
                raise ValueError(f"SimConfig.{f} must be > 0, got {v} — a "
                                 f"non-positive value makes Eq. 7-9 round "
                                 f"durations meaningless")
        for f in ("n_workers", "n_rounds", "batch_size", "local_steps",
                  "eval_every", "scan_horizon", "pipeline_depth",
                  "mesh_shards", "min_bucket"):
            v = getattr(self, f)
            if v < 1:
                raise ValueError(f"SimConfig.{f} must be >= 1, got {v}")
        if self.checkpoint_every < 0:
            raise ValueError(f"SimConfig.checkpoint_every must be >= 0 "
                             f"(0 disables snapshots), got "
                             f"{self.checkpoint_every}")
        if self.checkpoint_every > 0 and not self.checkpoint_dir:
            raise ValueError(
                "SimConfig.checkpoint_every > 0 needs checkpoint_dir: pass "
                "the directory snapshots should land in")
        if self.kernels is not None and not isinstance(self.kernels,
                                                       KernelConfig):
            raise ValueError(
                f"SimConfig.kernels must be a kernels.config.KernelConfig "
                f"(or None for the reference default), got "
                f"{type(self.kernels).__name__}")
        if self.kernels is None:
            self.kernels = KernelConfig()
        self.kernels.check_executable("SimConfig.kernels")


@dataclasses.dataclass
class History:
    """Per-eval-point trajectory of one simulation run.

    Units: ``sim_time`` is simulated edge wall-clock SECONDS (sum of Eq. 9
    round durations — the paper's x-axis); ``comm_gb`` cumulative transfer
    volume in GB (Eq. 10 accounting at ``model_bytes_scale`` pricing);
    ``staleness_avg``/``staleness_max`` are in ROUNDS since last activation
    (Eq. 6).

    Host time (real seconds of this call, benchmark accounting, not
    simulation state; ``core.trace.Trace`` writes them): ``wall_s`` is the
    whole call, and each ``<span>_wall_s`` the self time of the host span of
    that name — ``setup`` (data, partition, init, resume), ``plan``
    (``planner.plan_round`` + the bucket key), ``pack`` (chunk splitting +
    control-tensor packing), ``stage`` (the H2D ``device_put``),
    ``enqueue`` (the jitted step call, entry to return), ``drain`` (host
    blocked on the device: pipeline back-pressure, boundary drains and the
    blocks before an eval or a snapshot reads the buffer), ``eval`` and
    ``snapshot``.  These partition the call up to the loop's own
    bookkeeping.  ``drain`` is waiting, not the device's execute time: that
    overlaps the other spans, and only the profiler's device trace gives
    it.  ``counts`` holds the trace's counters (dispatches, rows,
    H2D bytes, back-pressure waits, compilations by span); unlike the
    times, it is restored on resume, so it describes the whole trajectory.
    """
    rounds: List[int] = dataclasses.field(default_factory=list)
    sim_time: List[float] = dataclasses.field(default_factory=list)
    comm_gb: List[float] = dataclasses.field(default_factory=list)
    acc_global: List[float] = dataclasses.field(default_factory=list)
    acc_local: List[float] = dataclasses.field(default_factory=list)
    loss_global: List[float] = dataclasses.field(default_factory=list)
    staleness_avg: List[float] = dataclasses.field(default_factory=list)
    staleness_max: List[int] = dataclasses.field(default_factory=list)
    completion_time: Optional[float] = None     # first time target acc reached
    completion_comm_gb: Optional[float] = None
    wall_s: float = 0.0
    eval_wall_s: float = 0.0      # host wall spent in eval passes
    setup_wall_s: float = 0.0     # one-time setup before the round loop (data
                                  #   synthesis, partition, init); wall_s -
                                  #   eval_wall_s - setup_wall_s is pure
                                  #   per-round cost (control + model plane),
                                  #   what the round-engine benchmark reports
    round_durations: List[float] = dataclasses.field(default_factory=list)
    round_active: List[int] = dataclasses.field(default_factory=list)
    plan_wall_s: float = 0.0      # host wall in planner.plan_round
    pack_wall_s: float = 0.0      # chunk split + control-tensor packing
    stage_wall_s: float = 0.0     # H2D device_put staging
    enqueue_wall_s: float = 0.0   # jitted step calls, entry to return
    drain_wall_s: float = 0.0     # host blocked on the device
    snapshot_wall_s: float = 0.0  # save_snapshot, less its drain
    counts: Dict[str, int] = dataclasses.field(default_factory=dict)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def run_simulation(mechanism: Mechanism, cfg: SimConfig,
                   data: Optional[ClassificationData] = None,
                   test: Optional[ClassificationData] = None,
                   record_history_for_bound: bool = False,
                   resume_from: Optional[str] = None) -> History:
    """Run (or resume) one simulation-plane federation.

    ``resume_from``: a snapshot file (or a checkpoint directory, meaning its
    newest snapshot) written by a ``checkpoint_every`` run of the SAME config.
    Setup replays deterministically from ``cfg.seed`` (consuming the identical
    setup rng draws), then the saved model rows, full planner control state,
    numpy rng stream, and history are restored — so the continued run is
    bit-identical on the control plane and f32-equal on the learning curve to
    the uninterrupted run.
    """
    if resume_from is not None and record_history_for_bound:
        raise ValueError("resume_from cannot record a bound log: the "
                         "pre-kill rounds' active/W history is not "
                         "checkpointed")
    hist = History()
    tr = Trace(hist)
    with tr.span("setup"):
        rng = np.random.default_rng(cfg.seed)

        # --- data ---
        if data is None:
            full = make_classification(cfg.n_samples, cfg.dim,
                                       seed=cfg.seed)
            data, test_split = train_test_split(full, 0.2, seed=cfg.seed)
            test = test or test_split
        assert test is not None, "pass `test` when supplying `data`"
        parts, class_counts = dirichlet_partition(data, cfg.n_workers,
                                                  cfg.phi, seed=cfg.seed)
        data_sizes = np.array([len(p) for p in parts], np.float64)
        alpha = jnp.asarray(data_sizes / data_sizes.sum(), jnp.float32)

        # --- environment ---
        net = EdgeNetwork(NetworkConfig(n_workers=cfg.n_workers), rng)
        in_range = net.in_range()
        h_i = heterogeneous_compute_times(cfg.n_workers, cfg.base_compute_s,
                                          rng, sigma=cfg.compute_sigma)

        # --- models ---
        key = jax.random.PRNGKey(cfg.seed)
        stacked = WK.init_stacked(key, cfg.n_workers, cfg.dim, cfg.hidden,
                                  data.n_classes)
        model_bytes = WK.param_bytes(
            jax.tree.map(lambda l: l[0], stacked)) * cfg.model_bytes_scale
        exp_link_time = net.expected_link_time(model_bytes)

        batch_key = jax.random.PRNGKey(cfg.seed + 0x5EED)
        buf, flat_spec = FS.flatten_stacked(stacked)
        del stacked                     # the flat buffer IS the storage
        data_x = jnp.asarray(data.x)    # device-resident dataset
        data_y = jnp.asarray(data.y)
        max_part = max(len(p) for p in parts)
        part_idx = np.zeros((cfg.n_workers, max_part), np.int32)
        for i, p in enumerate(parts):
            part_idx[i, :len(p)] = p    # padding never sampled
        # (uniform draws < the true size)
        part_sizes = data_sizes.astype(np.int32)
        shd = None
        if cfg.mesh_shards > 1:
            from repro.sharding.rules import FleetSharding
            shd = FleetSharding.create(cfg.mesh_shards)
            # pad the worker axis to a shard multiple (jax NamedShardings
            # need even splits); padding rows are permanently idle — never
            # activated, mixed, or evaluated — so zeros are fine.  The
            # resident dataset partitions row-wise across the mesh too
            # (sample padding is never indexed: part_idx holds real ids
            # only)
            row_pad = shd.pad(cfg.n_workers)
            if row_pad:
                part_idx = np.pad(part_idx, ((0, row_pad), (0, 0)))
                part_sizes = np.pad(part_sizes, (0, row_pad),
                                    constant_values=1)
            buf = shd.put_rows_padded(buf)
            data_x = shd.put_rows_padded(data_x)
            data_y = shd.put_rows_padded(data_y)
            part_idx = shd.put_rows(jnp.asarray(part_idx))
            part_sizes = shd.put_rows(jnp.asarray(part_sizes))
            batch_key = shd.put(batch_key)
        else:
            part_idx = jnp.asarray(part_idx)
            part_sizes = jnp.asarray(part_sizes)

        # --- control plane: the horizon planner owns all mutable control
        # state (staleness, pull counts, readiness clocks, failure mask, sim
        # clock) and replays Alg. 1 bookkeeping round-by-round —
        # model-value-independent, so it can run arbitrarily far ahead of
        # the device dispatches
        scen = resolve_scenario(cfg.scenario, cfg.n_workers, cfg.n_rounds,
                                dist=net.dist,
                                comm_range_m=net.cfg.comm_range_m)
        planner = HorizonPlanner(
            mechanism, h_i=h_i, in_range=in_range,
            exp_link_time=exp_link_time, model_bytes=model_bytes,
            class_counts=class_counts, data_sizes=data_sizes, net=net,
            rng=rng, tau_bound=cfg.tau_bound,
            bandwidth_budget=cfg.bandwidth_budget,
            link_timeout_s=cfg.link_timeout_s,
            sync_link_timeout_s=cfg.sync_link_timeout_s,
            failure_prob=cfg.failure_prob,
            failure_persist=cfg.failure_persist,
            mesh_shards=cfg.mesh_shards, scenario=scen)
        x_test = jnp.asarray(test.x)
        y_test = jnp.asarray(test.y)

        bound_log = ({"active": [], "W": []} if record_history_for_bound
                     else None)

        # --- crash-safe resume: overwrite the deterministic setup's mutable
        # state with the snapshot.  Setup above consumed the exact same rng
        # draws as the original run's setup, so only the planner state, the
        # model rows and the history need restoring.  The history's host
        # times stay this call's own.
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
            model_tmpl = {"buf": np.zeros((cfg.n_workers, int(buf.shape[1])),
                                          np.float32)}
            model, arrays, extra = CIO.load_checkpoint(ck, model_tmpl,
                                                       arr_tmpl)
            saved_cfg = extra.get("config", {})
            for k in ("plane", "n_workers", "seed", "mesh_shards",
                      "scenario"):
                want = {"plane": "sim",
                        "scenario": scen.schedule.name if scen else None
                        }.get(k, getattr(cfg, k, None))
                if k in saved_cfg and saved_cfg[k] != want:
                    raise ValueError(
                        f"resume config mismatch: snapshot {ck.name} was "
                        f"written with {k}={saved_cfg[k]!r} but this run has "
                        f"{k}={want!r} — resuming must use the identical "
                        f"configuration")
            planner.load_state({"arrays": arrays,
                                "scalars": extra["planner_scalars"],
                                "rng_state": extra["planner_rng"]})
            restored = jnp.asarray(model["buf"])
            # rebuild the padded+sharded residency exactly as first init
            buf = (shd.put_rows_padded(restored) if shd is not None
                   else restored)
            for k, v in extra["history"].items():
                if hasattr(hist, k) and not k.endswith("wall_s"):
                    setattr(hist, k, v)
        # the fused SGD lowering hand-differentiates the sim-plane MLP; any
        # other architecture plugged into the flat buffer falls back to the
        # AD scan
        fused_sgd = WK.fused_sgd_supported(flat_spec)
        pipe = DispatchPipeline(cfg.pipeline_depth, tr)
        put = shd.put if shd is not None else None
        n_rows = cfg.n_workers + (shd.pad(cfg.n_workers) if shd else 0)

    def flush(plans):
        """Dispatch the pending planned rounds to the model plane (Eq. 4+5).

        Consecutive rounds sharing one shape-bucket key
        (``core.planner.bucket_key``) go out as one ``lax.scan`` mega-round;
        ``core.planner.chunk_spans`` splits at bucket changes rather than
        padding to the horizon max, so no round ever pays a larger bucket
        than its own single-dispatch shape.  Per chunk the traffic model
        (``aggregation.prefer_cols``) picks the row- or column-sparse
        contraction from the bucketed (k_mix, u) shapes.  The host side is
        kept short so the device never waits on packing: the uniform-bucket
        packer (``worker.pack_chunk``, using the planner-resolved
        ``mix_rows``), ONE fused non-blocking ``jax.device_put`` per chunk,
        and no implicit block — ``pipe.submit`` bounds the in-flight chunks
        and the drive loop drains only at read-back boundaries."""
        nonlocal buf
        with tr.span("pack"):
            spans = list(chunk_spans(plans, cfg.n_workers,
                                     min_bucket=cfg.min_bucket,
                                     mesh_shards=cfg.mesh_shards))
        for lo, hi, key in spans:
            chunk = plans[lo:hi]
            col = prefer_cols(key[0], key[2], cfg.n_workers)
            if len(chunk) > 1:
                with tr.span("pack"):
                    host = WK.pack_chunk(
                        chunk, key, min_bucket=cfg.min_bucket,
                        col_sparse=col, shards=cfg.mesh_shards)
                    if not col:
                        host = (WK.pad_w_cols(host[0], n_rows),) + host[1:]
                    mit = fused_sgd and all(mix_is_train(p) for p in chunk)
                with tr.span("stage"):
                    w_j, c_j, ts_j = (tuple(map(put, host)) if put is not None
                                      else jax.device_put(host))
                with tr.span("enqueue"):
                    buf, done = WK.mega_round_step(
                        buf, w_j, c_j, ts_j, data_x, data_y, part_idx,
                        part_sizes, batch_key, spec=flat_spec, lr=cfg.lr,
                        local_steps=cfg.local_steps,
                        batch_size=cfg.batch_size, kernels=cfg.kernels,
                        col_sparse=col, fused_sgd=fused_sgd,
                        with_losses=False, mix_is_train=mit, shd=shd)
            else:
                p = chunk[0]
                with tr.span("pack"):
                    if col:
                        w_rows, mix_ids, col_ids = mixing_rows_cols(
                            p.W, p.active, p.links, cols_mask=p.mix_cols,
                            min_bucket=cfg.min_bucket,
                            shards=cfg.mesh_shards)
                    else:
                        w_rows, mix_ids = mixing_rows(
                            p.W, p.active, p.links,
                            min_bucket=cfg.min_bucket,
                            shards=cfg.mesh_shards)
                        w_rows = WK.pad_w_cols(w_rows, n_rows)
                        col_ids = None
                    train_ids, train_mask = padded_rows(
                        p.active, min_bucket=cfg.min_bucket,
                        shards=cfg.mesh_shards)
                    host = (w_rows, WK.pack_round_ctrl(
                        mix_ids, train_ids, train_mask, col_ids=col_ids))
                    mit = fused_sgd and mix_is_train(p)
                with tr.span("stage"):
                    w_j, c_j = (tuple(map(put, host)) if put is not None
                                else jax.device_put(host))
                with tr.span("enqueue"):
                    buf, done = WK.round_step(
                        buf, w_j, c_j, data_x, data_y, part_idx, part_sizes,
                        batch_key, np.int32(p.t), spec=flat_spec, lr=cfg.lr,
                        local_steps=cfg.local_steps,
                        batch_size=cfg.batch_size, kernels=cfg.kernels,
                        col_sparse=col, fused_sgd=fused_sgd,
                        with_losses=False, mix_is_train=mit, shd=shd)
            count_dispatch(tr, len(chunk), key[0], key[1],
                           sum(a.nbytes for a in host),
                           WK.narrow_mix(cfg.kernels, host[0].shape, col,
                                         cfg.mesh_shards))
            # track the NON-donated output: the buffer itself is donated
            # into the next chunk's dispatch, so it cannot be the in-flight
            # token; the loss output of the SAME executable materializes
            # exactly when the chunk finishes
            pipe.submit(done)

    def save_snapshot(t: int) -> None:
        """Atomic full-state snapshot: model rows + complete planner control
        state + rng stream + history.  Called only at drained flush
        boundaries, so the buffer is round-consistent when read back."""
        snap = planner.state_dict()
        view = buf if buf.shape[0] == cfg.n_workers else buf[:cfg.n_workers]
        with tr.span("drain"):
            jax.block_until_ready(view)
        extra = {
            "round": t,
            "planner_scalars": snap["scalars"],
            "planner_rng": snap["rng_state"],
            "history": hist.to_dict(),
            "config": {"plane": "sim", "n_workers": cfg.n_workers,
                       "seed": cfg.seed, "mesh_shards": cfg.mesh_shards,
                       "scenario": scen.schedule.name if scen else None},
        }
        CIO.save_checkpoint(CIO.checkpoint_path(cfg.checkpoint_dir, t),
                            {"buf": np.asarray(view)},
                            opt_state=snap["arrays"], extra=extra)
        CIO.prune_checkpoints(cfg.checkpoint_dir, cfg.checkpoint_keep)

    pending: list[PlannedRound] = []
    stop = False
    while planner.t < cfg.n_rounds and not stop:
        with tr.span("plan"):
            p = planner.plan_round()
            # resolve the round's shape-bucket key at plan time (memoized on
            # the plan): chunk_spans then only does lookups — bucketing is
            # control-plane work and belongs with the planner, not on the
            # dispatch critical path
            bucket_key(p, cfg.n_workers, min_bucket=cfg.min_bucket,
                       mesh_shards=cfg.mesh_shards)
        t = p.t
        sim_clock = planner.sim_clock
        hist.round_durations.append(p.duration)
        hist.round_active.append(int(p.active.sum()))
        if bound_log is not None:
            bound_log["active"].append(p.active.copy())
            bound_log["W"].append(p.W.copy())
        pending.append(p)

        # eval/history points are horizon boundaries: the planner is driven
        # one round at a time exactly so the chunk is chopped wherever the
        # per-round loop would have evaluated — histories are identical at
        # any scan_horizon
        if cfg.max_sim_time is not None:
            grid = cfg.max_sim_time / 12.0
            crossed = (int(sim_clock / grid)
                       > int((sim_clock - p.duration) / grid))
            do_eval = (crossed or sim_clock >= cfg.max_sim_time
                       or t == cfg.n_rounds)
            stop = sim_clock >= cfg.max_sim_time
        else:
            do_eval = t % cfg.eval_every == 0 or t == cfg.n_rounds
        # snapshot rounds are forced flush boundaries in EVERY checkpointing
        # run (resumed or not), so both share chunk splits; scenario event
        # boundaries also flush, keeping lax.scan mega-rounds from straddling
        # a fault-phase change (alignment, not correctness — overlays are
        # per-round and chunk splits are bit-exact anyway)
        do_ckpt = cfg.checkpoint_every > 0 and t % cfg.checkpoint_every == 0
        at_boundary = scen is not None and (t + 1) in scen.boundaries
        read_back = (do_eval or stop or t == cfg.n_rounds or do_ckpt
                     or at_boundary)
        if read_back or len(pending) >= cfg.scan_horizon:
            flush(pending)
            pending = []
            # read-back boundaries drain the pipeline: eval and
            # save_snapshot must see a round-consistent buffer, and a
            # scenario-event flush keeps host plan-ahead from racing past
            # the fault-phase change it just chopped the chunk for
            if read_back:
                pipe.drain()
        if do_eval:
            # drain queued round dispatches first so their device time is
            # charged to the rounds, not to the eval
            with tr.span("drain"):
                jax.block_until_ready(buf)
            with tr.span("eval"):
                # flat-native eval: Eq. 11 global model is one alpha @ buf
                # matvec; no stacked pytree is materialized.  A padded
                # sharded buffer evals its first N rows only (padding rows
                # are idle replicas of w_0 and must not enter the means)
                view = buf if buf.shape[0] == cfg.n_workers \
                    else buf[:cfg.n_workers]
                accg, lossg = WK.evaluate_global_flat(
                    view, alpha, x_test, y_test, spec=flat_spec)
                accl, _ = WK.evaluate_stacked_flat(view, x_test, y_test,
                                                   spec=flat_spec)
                hist.rounds.append(t)
                hist.sim_time.append(sim_clock)
                hist.comm_gb.append(planner.comm_bytes / 1e9)
                hist.acc_global.append(float(accg))
                hist.acc_local.append(float(accl))
                hist.loss_global.append(float(lossg))
                hist.staleness_avg.append(float(planner.st.tau.mean()))
                hist.staleness_max.append(int(planner.st.tau.max()))
                if (cfg.target_accuracy is not None
                        and hist.completion_time is None
                        and float(accg) >= cfg.target_accuracy):
                    hist.completion_time = sim_clock
                    hist.completion_comm_gb = planner.comm_bytes / 1e9
        if do_ckpt:
            # after the eval so a snapshot at an eval round carries that
            # round's history point — the resumed run never re-evals it
            with tr.span("snapshot"):
                save_snapshot(t)

    pipe.drain()
    tr.finish()
    if bound_log is not None:
        hist.bound_log = bound_log  # type: ignore[attr-defined]
    return hist
