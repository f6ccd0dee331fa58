"""Quickstart: a 20-worker DySTop federation in ~30 seconds on CPU.

Shows the full public API surface: synthetic non-IID data, the edge-network
model, WAA + PTCA coordination, Pallas-kernel aggregation, and the metrics
the paper reports (accuracy vs simulated wall-clock, communication, staleness).

    PYTHONPATH=src python examples/quickstart.py
"""
from repro.core.protocol import DySTop
from repro.dfl.simulator import SimConfig, run_simulation
from repro.kernels.config import KernelConfig
from repro.launch.cache import enable_compile_cache


def main():
    enable_compile_cache()
    cfg = SimConfig(
        n_workers=20,
        n_rounds=80,
        phi=0.4,                 # strongly non-IID (Dirichlet)
        tau_bound=5,             # staleness constraint (paper Eq. 12c)
        V=10.0,                  # Lyapunov trade-off (paper Eq. 34)
        lr=0.1,
        eval_every=20,
        kernels=KernelConfig(backend="pallas"),  # Pallas kernel plane
                                 # (interpret-mode on CPU)
        seed=0,
    )
    mech = DySTop(V=cfg.V, t_thre=25, max_neighbors=5)
    hist = run_simulation(mech, cfg)

    print(f"{'round':>6} {'sim-time(s)':>12} {'comm(GB)':>9} "
          f"{'acc(global)':>12} {'stale(avg/max)':>15}")
    for i, r in enumerate(hist.rounds):
        print(f"{r:6d} {hist.sim_time[i]:12.1f} {hist.comm_gb[i]:9.4f} "
              f"{hist.acc_global[i]:12.3f} "
              f"{hist.staleness_avg[i]:7.2f}/{hist.staleness_max[i]:<4d}")
    print(f"\nwall-clock: {hist.wall_s:.1f}s; staleness stayed bounded and "
          f"accuracy climbed under non-IID data — that's DySTop working.")


if __name__ == "__main__":
    main()
