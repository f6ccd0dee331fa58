"""Fused round engine: per-round dispatch vs scan mega-rounds, the mix and
SGD lowerings in isolation, and the dispatch pipeline's host phases.

Four layers are measured at N=100 workers, steady partial activation
(DySTop — the regime the mechanism targets):

* per-round vs scan — end-to-end simulations at ``scan_horizon=1`` and 8 at
  the default model scale; here the model plane (16 workers x 2 SGD steps)
  dominates, so amortizing dispatch buys a bounded win.
* mix plane — ``mix_flat`` (row-sparse (k, N) @ (N, P)) vs ``mix_flat_cols``
  (gather-union (k, u) @ (u, P)) on a real steady-regime W at the edge-proxy
  model scale, buffers donated exactly like the engine's round dispatch.
  Both are engine paths: ``aggregation.prefer_cols`` picks one per chunk,
  and its gather-cost constant is read off these rows.
* SGD plane — the fused unrolled lowering and the Pallas fused-SGD kernel
  on the gathered active rows.
* dispatch plane — the horizon scheduler's actual target: the same steady
  control trajectory executed with per-round ``round_step`` dispatches vs
  ``mega_round_step`` scans over a paper-testbed-scale edge model proxy
  (the Jetson-class CNNs of the paper and the large-N DFL deployment
  regimes are tiny per-worker models, where per-round dispatch IS the
  cost).  Host planning is identical in both paths and excluded; this is
  rounds/sec of the engine itself.  The ``max_workers=8`` mix-dominated
  variant runs the column-sparse + fused-SGD mega path on the SAME plans.

    PYTHONPATH=src python -m benchmarks.round_engine
    PYTHONPATH=src python -m benchmarks.run --only round_engine --quick
"""
from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregation import (mixing_rows, mixing_rows_cols,
                                    padded_rows, plan_buckets_cols)
from repro.core.planner import HorizonPlanner
from repro.core.protocol import DySTop
from repro.data.partition import dirichlet_partition
from repro.data.synthetic import make_classification, train_test_split
from repro.dfl import flat_state as FS
from repro.dfl import worker as WK
from repro.dfl.network import (EdgeNetwork, NetworkConfig,
                               heterogeneous_compute_times)
from repro.dfl.simulator import SimConfig, run_simulation
from repro.kernels import fused_sgd as FSGD
from repro.kernels.config import KernelConfig

from benchmarks.common import emit


def _cfg(rounds: int, workers: int,
         kernels: Optional[KernelConfig] = None,
         scan_horizon: int = 1) -> SimConfig:
    return SimConfig(n_workers=workers, n_rounds=rounds, phi=0.5, lr=0.1,
                     eval_every=rounds, seed=0, kernels=kernels,
                     scan_horizon=scan_horizon)


def _mech(max_workers: Optional[int]) -> DySTop:
    return DySTop(V=10.0, t_thre=20, max_neighbors=7, max_workers=max_workers)


def _us_per_round(rounds: int, workers: int, max_workers: Optional[int],
                  kernels: Optional[KernelConfig] = None,
                  scan_horizon: int = 1, reps: int = 3) -> float:
    # warmup run (full length, so both PTCA phases and every active-row shape
    # bucket get compiled), then per-round cost from `wall_s - eval_wall_s -
    # setup_wall_s` (the simulator separates eval passes and one-time setup
    # from round work, syncing queued dispatches before evals so device time
    # is charged to the rounds).  Best of `reps` runs: the floor is robust to
    # scheduler noise on small boxes.
    kw = dict(kernels=kernels, scan_horizon=scan_horizon)
    run_simulation(_mech(max_workers), _cfg(rounds, workers, **kw))

    def one() -> float:
        h = run_simulation(_mech(max_workers), _cfg(rounds, workers, **kw))
        return (h.wall_s - h.eval_wall_s - h.setup_wall_s) / rounds * 1e6

    return min(one() for _ in range(reps))


def _steady_env(workers: int, dim: int, hidden: int, max_workers: int,
                n_plan: int, bucket_cols: bool = True,
                mesh_shards: int = 1):
    """Plan a bucket-uniform steady DySTop control run + the flat-buffer
    model-plane inputs, shared by the mix-plane and dispatch-plane benches.
    ``mesh_shards`` makes the planner resolve shard-aware column unions (the
    sharded-dispatch bench needs padding candidates inside the union)."""
    rng = np.random.default_rng(0)
    full = make_classification(8000, dim, seed=0)
    data, _ = train_test_split(full, 0.2, seed=0)
    parts, class_counts = dirichlet_partition(data, workers, 0.5, seed=0)
    data_sizes = np.array([len(p) for p in parts], np.float64)
    net = EdgeNetwork(NetworkConfig(n_workers=workers), rng)
    h_i = heterogeneous_compute_times(workers, 1.0, rng, sigma=0.75)
    model_bytes = 4 * dim * hidden * 25.0
    planner = HorizonPlanner(
        _mech(max_workers), h_i=h_i, in_range=net.in_range(),
        exp_link_time=net.expected_link_time(model_bytes),
        model_bytes=model_bytes, class_counts=class_counts,
        data_sizes=data_sizes, net=net, rng=rng, tau_bound=5,
        bandwidth_budget=8.0, link_timeout_s=5.0, sync_link_timeout_s=30.0,
        mesh_shards=mesh_shards)
    plans = planner.plan(n_plan)
    # drop the burn-in, keep a bucket-uniform steady run so the mega path is
    # whole scan chunks (run_simulation splits chunks the same way; with
    # ``bucket_cols`` the key includes the column-union bucket so the
    # column-sparse engine sees uniform (k, u) shapes too)
    from repro.core.aggregation import plan_buckets

    key_fn = plan_buckets_cols if bucket_cols else plan_buckets
    plans = [p for p in plans[8:] if key_fn(p.active, p.links)
             == key_fn(plans[8].active, plans[8].links)]

    stacked = WK.init_stacked(jax.random.PRNGKey(0), workers, dim, hidden,
                              data.n_classes)
    buf, spec = FS.flatten_stacked(stacked)
    max_part = max(len(p) for p in parts)
    part_idx = np.zeros((workers, max_part), np.int32)
    for i, p in enumerate(parts):
        part_idx[i, :len(p)] = p
    return (plans, buf, spec, jnp.asarray(data.x), jnp.asarray(data.y),
            jnp.asarray(part_idx), jnp.asarray(data_sizes.astype(np.int32)))


def _mix_plane(workers: int, dim: int = 8, hidden: int = 8,
               max_workers: int = 8, reps: int = 200) -> tuple:
    """Row-sparse vs column-sparse mix on a real steady W, donated buffers.

    The mix-dominated regime: N=100, steady partial activation with a
    bounded neighborhood (k=8 rows, union u=64 < N columns), edge-proxy
    model scale.  Both paths include the scatter-back, exactly the engine's
    per-round mix.  Returns (us row-sparse, us column-sparse).

    Expectation management: the contraction drops k·N·P -> k·u·P flops and
    buffer-read traffic, but on CPU one dense skinny BLAS gemm is extremely
    efficient and the jnp lowering pays the union gather as a separate slab
    copy — measured parity-to-modest-win at N=100.  The TPU Pallas kernel
    (``aggregate_rows_cols``) is where the cut shows up as HBM traffic: the
    (u, P) slab streams through VMEM panels instead of all N rows.
    """
    import functools

    plans, buf, _, _, _, _, _ = _steady_env(workers, dim, hidden,
                                            max_workers, 48)
    p = plans[0]
    w_rows, mix_ids = mixing_rows(p.W, p.active, p.links)
    w_sub, mix_ids2, col_ids = mixing_rows_cols(p.W, p.active, p.links)
    jr = (jnp.asarray(w_rows), jnp.asarray(mix_ids))
    jc = (jnp.asarray(w_sub), jnp.asarray(mix_ids2), jnp.asarray(col_ids))

    @functools.partial(jax.jit, donate_argnums=0)
    def rows(b):
        return WK.mix_flat(b, *jr)

    @functools.partial(jax.jit, donate_argnums=0)
    def cols(b):
        return WK.mix_flat_cols(b, *jc)

    best = {}
    for name, fn in (("rows", rows), ("cols", cols)):
        jax.block_until_ready(fn(jnp.array(buf)))       # compile
        t_best = float("inf")
        for _ in range(reps):
            b = jnp.array(buf)
            jax.block_until_ready(b)
            t0 = time.perf_counter()
            jax.block_until_ready(fn(b))
            t_best = min(t_best, time.perf_counter() - t0)
        best[name] = t_best * 1e6
    return best["rows"], best["cols"]


def _sgd_plane(k: int = 16, dim: int = 32, hidden: int = 64, ncls: int = 10,
               steps: int = 2, batch: int = 32, reps: int = 60) -> tuple:
    """The fused unrolled SGD lowering at the default model scale.

    Times ONLY the local-SGD jit over the gathered active rows (k workers x
    ``local_steps`` — the simulator's default shapes), isolated from host
    planning and dispatch noise.  Returns (us fused, us Pallas fused-SGD
    kernel).  The kernel row runs interpret mode on CPU — a
    correctness/cost floor on record, not a perf claim (docs/BENCHMARKS.md).
    """
    stacked = WK.init_stacked(jax.random.PRNGKey(0), k, dim, hidden, ncls,
                              same_init=False)
    buf, spec = FS.flatten_stacked(stacked)
    kx, ky = jax.random.split(jax.random.PRNGKey(1))
    xb = jax.random.normal(kx, (k, steps, batch, dim), jnp.float32)
    yb = jax.random.randint(ky, (k, steps, batch), 0, ncls)
    act = jnp.ones((k,), jnp.float32)
    fns = {
        "fused": jax.jit(lambda b: WK.local_sgd_flat_fused(
            b, xb, yb, act, spec, 0.05, with_losses=False)[0]),
        "kernel": jax.jit(lambda b: FSGD.fused_sgd(
            b, xb, yb, act, spec, 0.05, with_losses=False)[0]),
    }
    best = {n: float("inf") for n in fns}
    for fn in fns.values():
        jax.block_until_ready(fn(buf))              # compile
    for _ in range(reps):                           # interleaved best-of
        for name, fn in fns.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn(buf))
            best[name] = min(best[name], time.perf_counter() - t0)
    return best["fused"] * 1e6, best["kernel"] * 1e6


def _dispatch_plane(workers: int, horizon: int = 8, n_plan: int = 48,
                    dim: int = 8, hidden: int = 8, batch: int = 8,
                    steps: int = 1, reps: int = 12, max_workers: int = 16,
                    sparse_variant: bool = False) -> dict:
    """Steady-regime control trajectory executed per-round vs as mega-rounds.

    Plans ``n_plan`` rounds of REAL DySTop control (WAA + PTCA over a real
    edge network) once with the horizon planner, then times only the model
    plane: per-round ``round_step`` dispatches vs ``mega_round_step`` scans
    of ``horizon`` rounds, over an edge-proxy model (P ~ ``dim*hidden`` —
    the paper-testbed / large-N regime where dispatch dominates).  With
    ``sparse_variant`` the column-sparse + fused-SGD mega path runs the
    plans instead.  Returns a dict of us/round.
    """
    plans, buf, spec, data_x, data_y, part_idx, part_sizes = _steady_env(
        workers, dim, hidden, max_workers, n_plan,
        bucket_cols=sparse_variant)
    plans = plans[: len(plans) // horizon * horizon]
    assert len(plans) >= horizon, f"steady run too short: {len(plans)}"
    key = jax.random.PRNGKey(1)
    kw = dict(spec=spec, lr=0.05, local_steps=steps, batch_size=batch)

    def single_all(b):
        for p in plans:
            w_rows, mix_ids = mixing_rows(p.W, p.active, p.links)
            train_ids, train_mask = padded_rows(p.active)
            ctrl = WK.pack_round_ctrl(mix_ids, train_ids, train_mask)
            b, _ = WK.round_step(b, jnp.asarray(w_rows), jnp.asarray(ctrl),
                                 data_x, data_y, part_idx, part_sizes, key,
                                 np.int32(p.t), **kw)
        return b

    def mega_all(b):
        for i in range(0, len(plans), horizon):
            w, c, ts = WK.pack_horizon(plans[i:i + horizon])
            b, _ = WK.mega_round_step(b, jnp.asarray(w), jnp.asarray(c),
                                      jnp.asarray(ts), data_x, data_y,
                                      part_idx, part_sizes, key, **kw)
        return b

    def mega_sparse_all(b):
        # a column-sparse chunk as run_simulation issues it: column-sparse
        # mix + fused SGD + loss skip + mix-rows==train-rows
        for i in range(0, len(plans), horizon):
            chunk = plans[i:i + horizon]
            mit = all(not (p.links.any(axis=1) & ~p.active).any()
                      for p in chunk)
            w, c, ts = WK.pack_horizon(chunk, col_sparse=True)
            b, _ = WK.mega_round_step(b, jnp.asarray(w), jnp.asarray(c),
                                      jnp.asarray(ts), data_x, data_y,
                                      part_idx, part_sizes, key,
                                      col_sparse=True, fused_sgd=True,
                                      with_losses=False, mix_is_train=mit,
                                      **kw)
        return b

    variants = ([("mega_sparse", mega_sparse_all)] if sparse_variant
                else [("single", single_all), ("mega", mega_all)])
    state = {name: jnp.array(buf) for name, _ in variants}
    best = {name: float("inf") for name, _ in variants}
    for name, fn in variants:
        state[name] = fn(state[name])
        jax.block_until_ready(state[name])  # compile warmup
    # interleave the timed reps so load spikes on small shared boxes hit both
    # paths alike; best-of is then a fair floor for each
    for _ in range(reps):
        for name, fn in variants:
            t0 = time.time()
            state[name] = fn(state[name])
            jax.block_until_ready(state[name])
            best[name] = min(best[name], (time.time() - t0) / len(plans) * 1e6)
    return best


def pipeline_main(rounds: int = 320, workers: int = 100,
                  reps: int = 5) -> None:
    """Dispatch-plane rows for the async pipeline: the drive loop's host
    cost per round and its per-phase breakdown.

    Steady DySTop control (max_workers=8 — stable shape buckets) at the
    edge-proxy model scale with ``scan_horizon=16`` — the dispatch-bound
    regime the pipeline targets.  Per-round cost excludes eval, setup AND
    host planning (overlapped by the pipelined loop on multi-core hosts):
    what is left is pack + stage + dispatch + device wait.  Best of
    ``reps`` runs.
    """
    cfg = SimConfig(n_workers=workers, n_rounds=rounds, phi=0.5, lr=0.1,
                    dim=8, hidden=8, batch_size=8, local_steps=1,
                    n_samples=4000, scan_horizon=16, eval_every=rounds,
                    seed=0)

    def one():
        h = run_simulation(_mech(8), cfg)
        return ((h.wall_s - h.eval_wall_s - h.setup_wall_s
                 - h.plan_wall_s) / rounds * 1e6, h)

    run_simulation(_mech(8), cfg)                   # compile warmup
    pipe, h1 = min((one() for _ in range(reps)), key=lambda r: r[0])
    emit(f"round_engine/dispatch_pipelined_{workers}w", pipe,
         "steady scan16 drive loop, pipeline_depth=1: fast uniform-bucket "
         "packer + one fused non-blocking device_put + bounded in-flight "
         "chunks")
    for phase, val in (("plan", h1.plan_wall_s), ("pack", h1.pack_wall_s),
                       ("stage", h1.stage_wall_s),
                       ("drain", h1.drain_wall_s)):
        emit(f"round_engine/pipeline_phase_{phase}_{workers}w",
             val / rounds * 1e6,
             f"depth-1 {phase} host wall per round (History phase "
             f"breakdown; drain ~= device execute)")


def sharded_main(quick: bool = False, workers: int = 100,
                 horizon: int = 8) -> None:
    """Sharded-dispatch row: the SAME steady mega-round trajectory executed
    on the single-device engine vs the mesh-sharded engine (ISSUE 5).

    Emits only when the backend exposes >= 2 devices — CI's multi-device
    lane runs it under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
    The numbers are PLUMBING PROOF, not a perf claim: emulated host devices
    time-slice the same cores and pay real collective overhead with none of
    the memory-capacity or bandwidth win, so sharded us/round is expected to
    be slower here (docs/BENCHMARKS.md).  The row exists so the sharded
    dispatch path is exercised end to end and its cost is on record; real
    speedups are a hardware claim.
    """
    import sys

    from repro.sharding.rules import FleetSharding

    n_dev = len(jax.devices())
    if n_dev < 2:
        print("# round_engine_sharded: skipped — single-device backend "
              "(set XLA_FLAGS=--xla_force_host_platform_device_count=8)",
              file=sys.stderr)
        return
    shards = min(8, n_dev)
    n_plan = 24 if quick else 48
    reps = 4 if quick else 10
    plans, buf, spec, data_x, data_y, part_idx, part_sizes = _steady_env(
        workers, 8, 8, 8, n_plan, bucket_cols=True, mesh_shards=shards)
    plans = plans[: len(plans) // horizon * horizon]
    assert len(plans) >= horizon, f"steady run too short: {len(plans)}"
    key = jax.random.PRNGKey(1)
    kw = dict(spec=spec, lr=0.05, local_steps=1, batch_size=8,
              col_sparse=True, fused_sgd=True, with_losses=False)
    shd = FleetSharding.create(shards)
    row_pad = shd.pad(workers)

    def mk_state(sharded: bool):
        b = jnp.array(buf)
        return shd.put_rows_padded(b) if sharded else b

    ops = {
        False: dict(data_x=data_x, data_y=data_y, part_idx=part_idx,
                    part_sizes=part_sizes, key=key, put=jnp.asarray,
                    shd=None),
        True: dict(data_x=shd.put(data_x), data_y=shd.put(data_y),
                   part_idx=shd.put_rows(jnp.asarray(np.pad(
                       np.asarray(part_idx), ((0, row_pad), (0, 0))))),
                   part_sizes=shd.put_rows(jnp.asarray(np.pad(
                       np.asarray(part_sizes), (0, row_pad),
                       constant_values=1))),
                   key=shd.put(key), put=shd.put, shd=shd),
    }

    def mega_all(b, sharded: bool, kernels: Optional[KernelConfig] = None):
        from repro.core.planner import mix_is_train

        o = ops[sharded]
        for i in range(0, len(plans), horizon):
            chunk = plans[i:i + horizon]
            mit = all(mix_is_train(p) for p in chunk)
            w, c, ts = WK.pack_horizon(chunk, col_sparse=True,
                                       shards=shards if sharded else 1)
            b, _ = WK.mega_round_step(
                b, o["put"](w), o["put"](c), o["put"](ts), o["data_x"],
                o["data_y"], o["part_idx"], o["part_sizes"], o["key"],
                mix_is_train=mit, shd=o["shd"], kernels=kernels, **kw)
        return b
    pallas = KernelConfig(backend="pallas")
    variants = [("single_device", False, None), (f"sharded{shards}", True,
                                                 None),
                (f"sharded{shards}_kernel", True, pallas)]
    state = {name: mk_state(sharded) for name, sharded, _ in variants}
    best = {name: float("inf") for name, _, _ in variants}
    for name, sharded, kc in variants:
        state[name] = mega_all(state[name], sharded, kc)
        jax.block_until_ready(state[name])          # compile warmup
    for _ in range(reps):                           # interleaved best-of
        for name, sharded, kc in variants:
            t0 = time.time()
            state[name] = mega_all(state[name], sharded, kc)
            jax.block_until_ready(state[name])
            best[name] = min(best[name],
                             (time.time() - t0) / len(plans) * 1e6)
    single, shard = best["single_device"], best[f"sharded{shards}"]
    shard_k = best[f"sharded{shards}_kernel"]
    emit(f"round_engine_sharded/dispatch_scan{horizon}_{workers}w", single,
         "steady mega-rounds, single-device engine (same box, mesh idle)")
    emit(f"round_engine_sharded/dispatch_scan{horizon}_sharded{shards}_"
         f"{workers}w", shard,
         f"same plans on a {shards}-way fleet mesh (emulated host devices; "
         f"collective-overhead plumbing proof, not a perf claim)")
    emit(f"round_engine_sharded/sharded_dispatch_speedup_{workers}w",
         single / shard,
         f"sharded/single ratio {single / shard:.2f}x on emulated devices — "
         f"recorded for plumbing regression only; real speedups are a "
         f"hardware claim (docs/BENCHMARKS.md)")
    emit(f"round_engine_sharded/dispatch_scan{horizon}_sharded{shards}_"
         f"kernel_{workers}w", shard_k,
         f"same sharded plans with KernelConfig(backend='pallas'): "
         f"shard_map panel mix + fused-SGD kernel rows (interpret mode on "
         f"emulated devices — plumbing proof that the kernel plane composes "
         f"with the mesh, not a perf claim)")


def main(rounds: int = 80, workers: int = 100) -> None:
    # headline: steady partial activation (max_workers=16), default model
    fused = _us_per_round(rounds, workers, max_workers=16)
    emit(f"round_engine/fused_{workers}w", fused,
         "one donated dispatch per round (scan_horizon=1)")
    scan = _us_per_round(rounds, workers, max_workers=16, scan_horizon=8)
    emit(f"round_engine/fused_scan8_{workers}w", scan,
         "mega-rounds + per-chunk mix contraction + fused SGD (the engine)")
    emit(f"round_engine/scan_speedup_{workers}w", fused / scan,
         f"end-to-end {fused / scan:.2f}x vs per-round dispatch (model plane "
         f"dominates at default scale)")
    # SGD plane: the fused unrolled lowering at the simulator's default
    # shapes (k=16 x 2 steps x batch 32)
    sgd_fused, sgd_kernel = _sgd_plane()
    emit(f"round_engine/sgd_fused_{workers}w", sgd_fused,
         "fused unrolled manual-backward SGD (the sim-plane MLP lowering)")
    emit(f"round_engine/sgd_fused_kernel_{workers}w", sgd_kernel,
         "Pallas VMEM-resident fused-SGD kernel, same shapes (interpret "
         "mode on CPU — cost-on-record, the perf claim is TPU-only)")
    # mix plane: row-sparse vs column-sparse contraction on a real steady W
    # (k=8 active rows, u=64-column union < N=100), edge-proxy model scale
    mix_r, mix_c = _mix_plane(workers)
    emit(f"round_engine/mix_rows_{workers}w", mix_r,
         "row-sparse mix_flat: (k, N) @ (N, P) + scatter, donated buffer")
    emit(f"round_engine/mix_cols_{workers}w", mix_c,
         "column-sparse mix_flat_cols: gather-union (k, u) @ (u, P)")
    emit(f"round_engine/mix_cols_speedup_{workers}w", mix_r / mix_c,
         f"column-sparse mix is {mix_r / mix_c:.2f}x on CPU BLAS "
         f"(N={workers} steady, edge-proxy model; flops drop k*N*P -> "
         f"k*u*P — the traffic win lands on TPU where the Pallas kernel "
         f"streams the (u, P) slab through VMEM)")
    # dispatch plane: same steady control, edge-proxy model — the horizon
    # scheduler's target regime (paper-testbed-scale workers, large-N sims)
    d16 = _dispatch_plane(workers, horizon=16, n_plan=80)
    emit(f"round_engine/dispatch_single_{workers}w", d16["single"],
         "steady control executed as per-round round_step dispatches")
    emit(f"round_engine/dispatch_scan16_{workers}w", d16["mega"],
         "same rounds as lax.scan mega-rounds (sampling hoisted off the scan)")
    emit(f"round_engine/dispatch_scan_speedup_{workers}w",
         d16["single"] / d16["mega"],
         f"mega-rounds are {d16['single'] / d16['mega']:.2f}x rounds/sec at "
         f"the dispatch plane (edge-proxy model, N={workers} steady, "
         f"horizon 16)")
    # mix-dominated dispatch plane (max_workers=8 ⇒ union u=64 < N): the
    # column-sparse + fused-SGD mega path
    d8 = _dispatch_plane(workers, horizon=16, n_plan=96, max_workers=8,
                         sparse_variant=True)
    emit(f"round_engine/dispatch_scan16_sparse_{workers}w", d8["mega_sparse"],
         "mega-rounds with column-sparse mix + fused SGD, mix-dominated "
         "regime")
    fused_k = _us_per_round(rounds, workers, max_workers=16,
                            kernels=KernelConfig(backend="pallas"))
    emit(f"round_engine/fused_kernel_{workers}w", fused_k,
         "fused + KernelConfig(backend='pallas'): panel mix AND fused-SGD "
         "kernel (interpret mode on CPU; compiles on TPU)")
    # secondary: uncapped bursty activation (all-N flush rounds; bucket
    # changes every round, so scan chunks degrade to single dispatches)
    fused_b = _us_per_round(rounds, workers, max_workers=None,
                            scan_horizon=8)
    emit(f"round_engine/fused_{workers}w_burst", fused_b,
         "uncapped V=10 activation (1-active / all-active flush cycles), "
         "scan_horizon=8")
    # async dispatch pipeline rows; longer run so the scan16 chunks amortize
    # warmup-independent noise
    pipeline_main(rounds=rounds * 4, workers=workers)


if __name__ == "__main__":
    from benchmarks.common import header
    header()
    main()
