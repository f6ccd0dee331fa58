"""Plain per-round references the resident engines are held to.

Both planes run one engine: chunked, pipelined, row- or column-sparse by
the traffic model, fused wherever the spec allows.  The references here
replay the rounds that engine planned, one at a time, through the plain
pytree forms of Eq. 4 and Eq. 5, and rebuild the history at the cadence the
config sets:

  * sim — ``sim_reference`` replays ``run_simulation``'s ``bound_log``
    (``active``, ``W`` per round, ``record_history_for_bound=True``) through
    ``aggregation.apply_mixing`` (dense W @ models) and ``worker.local_train``
    (masked SGD over all N workers), evaluated by ``worker.evaluate_global``
    / ``evaluate_stacked``;
  * LM — ``lm_reference`` replays the recorded plans through the
    per-call-flatten helpers below: ``fleet_mix_stacked`` (flatten the
    stacked pytree, mix, unflatten) and the masked train-all-N step
    (``make_fleet_step``), with ``worker_streams`` at the run's seed.

Control readings (clock, comm, staleness) are the planner's own, recorded
round by round by ``recorded_plans`` (the way ``chipbench/faults.py``
patches ``HorizonPlanner.plan_round``), so a reference history matches the
engine's exactly on the control plane whenever the drive loop planned,
chunked and evaluated the same rounds.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregation import apply_mixing, mixing_rows
from repro.core.planner import HorizonPlanner
from repro.data.partition import dirichlet_partition
from repro.data.synthetic import make_classification, train_test_split
from repro.dfl import flat_state as FS
from repro.dfl import lm_worker as LW
from repro.dfl import worker as WK
from repro.dfl.simulator import History, run_simulation
from repro.models import registry as R
from repro.optim import Optimizer

Params = Dict[str, Any]

@contextlib.contextmanager
def recorded_plans():
    """Record every ``PlannedRound`` the planner hands out, with the
    planner's control readings after it: ``[(plan, readings), ...]``."""
    rec: List[tuple] = []
    orig = HorizonPlanner.plan_round

    def plan_round(self):
        p = orig(self)
        rec.append((p, {"duration": p.duration,
                        "sim_clock": self.sim_clock,
                        "comm_gb": self.comm_bytes / 1e9,
                        "tau_avg": float(self.st.tau.mean()),
                        "tau_max": int(self.st.tau.max())}))
        return p

    HorizonPlanner.plan_round = plan_round
    try:
        yield rec
    finally:
        HorizonPlanner.plan_round = orig


def _record_control(hist, t: int, r: dict) -> None:
    hist.rounds.append(t)
    hist.sim_time.append(r["sim_clock"])
    hist.comm_gb.append(r["comm_gb"])
    hist.staleness_avg.append(r["tau_avg"])
    hist.staleness_max.append(r["tau_max"])


# --------------------------------------------------------------------------- #
# sim plane
# --------------------------------------------------------------------------- #


def sample_batches_host(parts, data, cfg, rng: np.random.Generator):
    """Per-worker numpy minibatches for all N workers:
    (N, local_steps, batch, dim) / (N, local_steps, batch)."""
    n = cfg.n_workers
    xb = np.empty((n, cfg.local_steps, cfg.batch_size, data.x.shape[1]),
                  np.float32)
    yb = np.empty((n, cfg.local_steps, cfg.batch_size), np.int32)
    for i in range(n):
        idx = rng.choice(parts[i], size=(cfg.local_steps, cfg.batch_size))
        xb[i] = data.x[idx]
        yb[i] = data.y[idx]
    return jnp.asarray(xb), jnp.asarray(yb)


def sim_reference(cfg, hist, readings: List[dict], data=None, test=None,
                  batches: str = "device") -> History:
    """The engine run ``hist`` (with its ``bound_log``) replayed round by
    round on stacked pytrees.

    ``batches="device"`` draws each round's minibatches with the engine's
    own key stream (``worker.sample_batches_device`` keyed by round and
    worker id), so model fields agree to f32 rounding; ``"host"`` draws them
    from a numpy stream, so only the learning dynamics agree.
    """
    assert cfg.max_sim_time is None, "the reference evaluates by round"
    log = hist.bound_log
    assert len(log["active"]) == len(readings)
    if data is None:
        full = make_classification(cfg.n_samples, cfg.dim, seed=cfg.seed)
        data, test = train_test_split(full, 0.2, seed=cfg.seed)
    parts, _ = dirichlet_partition(data, cfg.n_workers, cfg.phi,
                                   seed=cfg.seed)
    sizes = np.array([len(p) for p in parts], np.float64)
    alpha = jnp.asarray(sizes / sizes.sum(), jnp.float32)
    stacked = WK.init_stacked(jax.random.PRNGKey(cfg.seed), cfg.n_workers,
                              cfg.dim, cfg.hidden, data.n_classes)
    x_test, y_test = jnp.asarray(test.x), jnp.asarray(test.y)
    batch_key = jax.random.PRNGKey(cfg.seed + 0x5EED)
    batch_rng = np.random.default_rng(cfg.seed + 0x5EED)
    part_idx = np.zeros((cfg.n_workers, max(len(p) for p in parts)),
                        np.int32)
    for i, p in enumerate(parts):
        part_idx[i, :len(p)] = p
    ids = jnp.arange(cfg.n_workers)
    data_x, data_y = jnp.asarray(data.x), jnp.asarray(data.y)
    part_idx, part_sizes = jnp.asarray(part_idx), jnp.asarray(
        sizes.astype(np.int32))

    ref = History()
    for t, (active, W, r) in enumerate(zip(log["active"], log["W"],
                                           readings), start=1):
        if batches == "device":
            xb, yb = WK.sample_batches_device(
                jax.random.fold_in(batch_key, t), ids, data_x, data_y,
                part_idx, part_sizes, cfg.local_steps, cfg.batch_size)
        else:
            xb, yb = sample_batches_host(parts, data, cfg, batch_rng)
        stacked = apply_mixing(jnp.asarray(W), stacked, kernels=cfg.kernels)
        stacked, _ = WK.local_train(stacked, xb, yb, jnp.asarray(active),
                                    lr=cfg.lr, local_steps=cfg.local_steps)
        ref.round_durations.append(r["duration"])
        ref.round_active.append(int(active.sum()))
        if t % cfg.eval_every == 0 or t == cfg.n_rounds:
            accg, lossg = WK.evaluate_global(stacked, alpha, x_test, y_test)
            accl, _ = WK.evaluate_stacked(stacked, x_test, y_test)
            _record_control(ref, t, r)
            ref.acc_global.append(float(accg))
            ref.acc_local.append(float(accl))
            ref.loss_global.append(float(lossg))
    return ref


def run_sim_with_reference(mechanism, cfg, batches: str = "device", **kw):
    """``run_simulation`` with its bound log and plan readings recorded,
    and its reference replay: returns (engine History, reference History)."""
    with recorded_plans() as rec:
        hist = run_simulation(mechanism, cfg, record_history_for_bound=True,
                              **kw)
    for (p, _), active, W in zip(rec, hist.bound_log["active"],
                                 hist.bound_log["W"]):
        np.testing.assert_array_equal(p.active, active)
        np.testing.assert_array_equal(p.W, W)
    return hist, sim_reference(cfg, hist, [r for _, r in rec],
                               data=kw.get("data"), test=kw.get("test"),
                               batches=batches)


# --------------------------------------------------------------------------- #
# LM plane: the per-call-flatten helpers
# --------------------------------------------------------------------------- #


def fleet_mix_stacked(stacked_params: Params, W: np.ndarray,
                      active: np.ndarray, links: np.ndarray,
                      kernels=None) -> Params:
    """Eq. 4 over a STACKED param pytree, re-flattening per call: flatten
    the whole fleet, run the gather -> (k, N) @ (N, P) -> scatter
    contraction, unflatten back to the pytree the masked step consumes."""
    buf, spec = FS.flatten_stacked(stacked_params)
    w_rows, row_ids = mixing_rows(np.asarray(W, np.float32), active, links)
    buf = WK.mix_flat(buf, jnp.asarray(w_rows), jnp.asarray(row_ids),
                      kernels=kernels)
    return FS.unflatten(buf, spec)


def make_fleet_step(fleet: LW.LMFleet):
    """Masked per-worker train step over STACKED pytrees: trains ALL N
    workers, one after another as the resident engine does, and masks the
    inactive updates away."""
    return _fleet_step(fleet.cfg, fleet.optimizer)


@functools.lru_cache(maxsize=None)
def _fleet_step(cfg, opt: Optimizer):
    def one(params, opt_state, batch, active):
        def loss_fn(p):
            return R.compute_loss(cfg, p, batch)

        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        new_p, new_s = opt.update(grads, opt_state, params)
        a = active.astype(jnp.float32)

        def mix(n, o):
            am = a.astype(n.dtype).reshape((1,) * n.ndim)
            return n * am + o * (1 - am)

        return (jax.tree.map(mix, new_p, params),
                jax.tree.map(mix, new_s, opt_state), loss)

    return jax.jit(lambda *xs: jax.lax.map(lambda x: one(*x), xs))


def fleet_eval_stacked(cfg, stacked_params: Params,
                       batch: Dict[str, jnp.ndarray],
                       alpha: jnp.ndarray) -> float:
    """Eq. 11 eval through the stacked pytree (per-leaf tensordot)."""
    gm = jax.tree.map(lambda l: jnp.tensordot(alpha, l.astype(jnp.float32),
                                              axes=1).astype(l.dtype),
                      stacked_params)
    loss, _ = R.compute_loss(cfg, gm, batch)
    return float(loss)


def fleet_eval(fleet: LW.LMFleet, batch: Dict[str, jnp.ndarray],
               alpha: jnp.ndarray) -> float:
    """Eq. 11 eval of the resident fleet: one ``alpha @ pbuf`` matvec plus
    a static unravel."""
    gm = FS.unravel_row(FS.weighted_row(fleet.pbuf, alpha),
                        fleet.spec.params)
    loss, _ = R.compute_loss(fleet.cfg, gm, batch)
    return float(loss)


@dataclasses.dataclass
class LMReference:
    hist: LW.LMHistory
    pbuf: np.ndarray
    obuf: np.ndarray


def lm_reference(cfg, run, recorded: List[tuple]) -> LMReference:
    """The recorded plans of one ``run_lm_federation`` call replayed round
    by round through the per-call-flatten helpers."""
    if run.kernels is not None and cfg.kernels != run.kernels:
        cfg = dataclasses.replace(cfg, kernels=run.kernels)
    n = run.n_workers
    fleet = LW.init_fleet(cfg, n, optimizer=run.optimizer, lr=run.lr,
                          seed=run.seed)
    sp, so = fleet.stacked_params, fleet.stacked_opt
    step = make_fleet_step(fleet)
    streams = LW.worker_streams(cfg, n, run.batch, run.seq, seed=run.seed)
    ev = next(LW.worker_streams(cfg, 1, run.batch, run.seq,
                                seed=run.seed + 1))
    eval_batch = {"tokens": jnp.asarray(ev["tokens"][0]),
                  "labels": jnp.asarray(ev["labels"][0])}
    eval_batch["loss_mask"] = jnp.ones(eval_batch["tokens"].shape,
                                       jnp.float32)
    alpha = jnp.full((n,), 1.0 / n, jnp.float32)
    hist = LW.LMHistory()
    for p, r in recorded:
        batch = {k: jnp.asarray(v) for k, v in next(streams).items()}
        sp = fleet_mix_stacked(sp, p.W, p.active, p.links,
                               kernels=run.kernels)
        sp, so, losses = step(sp, so, batch, jnp.asarray(p.active))
        losses = np.asarray(losses)
        hist.round_durations.append(r["duration"])
        hist.round_active.append(int(p.active.sum()))
        hist.round_loss.append(float(losses[p.active].mean())
                               if p.active.any() else 0.0)
        if p.t % run.eval_every == 0 or p.t == run.n_rounds:
            _record_control(hist, p.t, r)
            hist.loss_global.append(fleet_eval_stacked(cfg, sp, eval_batch,
                                                       alpha))
            hist.loss_local.append(hist.round_loss[-1])
    pb, _ = FS.flatten_stacked(sp)
    ob, _ = FS.flatten_stacked(so)
    return LMReference(hist, np.asarray(pb), np.asarray(ob))


def run_lm_with_reference(mechanism, cfg, run):
    """``run_lm_federation`` with its plans recorded, and its reference
    replay: returns (fleet, engine LMHistory, LMReference)."""
    with recorded_plans() as rec:
        fleet, hist = LW.run_lm_federation(mechanism, cfg, run)
    return fleet, hist, lm_reference(cfg, run, rec)
