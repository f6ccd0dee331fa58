"""Benchmark harness entry point — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]
                                            [--json PATH]

Prints ``name,us_per_call,derived`` CSV.  `us_per_call` is wall-clock
microseconds per simulated round (or kernel call); `derived` carries the
paper metric for that table.  ``--json PATH`` additionally writes the same
rows as machine-readable JSON (plus run metadata) — the CI benchmark-smoke
job and ``BENCH_*.json`` trajectory tracking consume this.
"""
from __future__ import annotations

import argparse
import json
import platform
import sys
import time

import jax

from benchmarks import (arena, bound_check, comm_overhead, completion_time,
                        convergence_curves, kernels_bench, lm_fleet,
                        neighbor_sweep, phase_ablation, roofline,
                        round_engine, scenarios, serving, staleness_sweep,
                        v_sweep)
from benchmarks.common import header, records
from repro.launch.cache import enable_compile_cache

SUITES = {
    # paper Fig. 4 / Fig. 20
    "completion_time": lambda q: completion_time.main(rounds=120 if q else 240),
    # paper Figs. 5-6 / 8-9 / 11-12 / 22-25
    "convergence_curves": lambda q: convergence_curves.main(rounds=120 if q else 240),
    # paper Figs. 7/10/13 / 21
    "comm_overhead": lambda q: comm_overhead.main(rounds=120 if q else 240),
    # paper Figs. 14-15
    "staleness_sweep": lambda q: staleness_sweep.main(rounds=100 if q else 200),
    # paper Fig. 16
    "v_sweep": lambda q: v_sweep.main(rounds=100 if q else 200),
    # paper Figs. 17-18
    "neighbor_sweep": lambda q: neighbor_sweep.main(rounds=100 if q else 200),
    # paper Fig. 3
    "phase_ablation": lambda q: phase_ablation.main(rounds=100 if q else 200),
    # Theorem 1 bound evaluated on recorded histories
    "bound_check": lambda q: bound_check.main(rounds=60 if q else 120),
    # kernel microbenchmarks (the sharded-panel row emits only with >= 2
    # devices — CI's multi-device lane runs this suite on 8 emulated devices)
    "kernels": lambda q: kernels_bench.main(quick=q),
    # fused device-resident round engine: dispatch, mix and SGD planes
    "round_engine": lambda q: round_engine.main(rounds=40 if q else 80),
    # mesh-sharded dispatch plumbing proof (emits only with >= 2 devices;
    # CI's multi-device lane forces 8 emulated host devices)
    "round_engine_sharded": lambda q: round_engine.sharded_main(quick=q),
    # persistent-flat planner-driven LM fleet
    "lm_fleet": lambda q: lm_fleet.main(rounds=12 if q else 24),
    # scenario/fault-plane degradation curves: presets vs the
    # no-staleness-control ablation (ROADMAP item 2)
    "scenarios": lambda q: scenarios.main(rounds=80 if q else 160),
    # Table-I baseline arena: all five mechanisms head-to-head on the fused
    # engine, chasing the paper's 51.8%/57.1% headline reductions
    # (ROADMAP item 2, arena half)
    "arena": lambda q: arena.quick_main() if q else arena.main(),
    # traffic plane: the continuous-batching serving engine under each
    # arrival preset (tokens/sec, p50/p99 TTFT + per-token latency,
    # slot occupancy) — ROADMAP item 1, federation-to-serving pipeline
    "serving": lambda q: serving.main(quick=q),
    # deliverable (g): roofline table from the dry-run artifacts
    "roofline": lambda q: roofline.main(),
}


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None, choices=list(SUITES))
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write results as machine-readable JSON")
    args = ap.parse_args()

    header()
    t0 = time.time()
    for name, fn in SUITES.items():
        if args.only and name != args.only:
            continue
        t1 = time.time()
        try:
            fn(args.quick)
        except Exception as e:  # keep the harness going; report the failure
            print(f"{name}/ERROR,0.0,{type(e).__name__}: {e}", file=sys.stdout)
            raise
        print(f"# {name} done in {time.time() - t1:.1f}s", file=sys.stderr)
    total_s = time.time() - t0
    print(f"# total {total_s:.1f}s", file=sys.stderr)
    if args.json:
        payload = {
            "meta": {
                "quick": args.quick,
                "only": args.only,
                "total_s": round(total_s, 2),
                "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
                "jax_version": jax.__version__,
                "backend": jax.default_backend(),
                "platform": platform.platform(),
                "python": platform.python_version(),
            },
            "results": records(),
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {len(payload['results'])} rows to {args.json}",
              file=sys.stderr)


if __name__ == "__main__":
    main()
