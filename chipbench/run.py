#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell gives its
configuration (``chipbench/configs/<config>.json``, whose ``plane`` names
``chipbench/planes/<plane>.py``) and its traffic
(``chipbench/traffic/<traffic>.json``); each per-layer metric is read by
``chipbench/metrics/<metric>.py``.

A run: check that JAX sees the chips the cell asks for (there is no CPU
fallback), set up (build the inputs from the seed, compile every shape the
window uses, size the window), time the window on the host clock, read the
device's peak memory, compare what the window produced with the plain
reference, and print the result as the last line of standard output.  With
``--trace 1`` the window runs under the profiler and the result holds the
per-layer metrics instead of the end-to-end ones.
"""
from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import peaks as PK  # noqa: E402
import devtrace as TR  # noqa: E402


def log(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr, flush=True)


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, root: pathlib.Path = ROOT) -> dict:
    """The cell, its configuration, traffic, plane and metric readers, all
    resolved by name."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    reports = {m["name"] for m in bench["end_to_end"]
               if workload in m.get("workloads", [workload])}
    layer = [m for m in bench["per_layer"]
             if workload in m.get("workloads", [])
             or ("workloads" not in m and m["moves"] in reports)]
    return {"bench": bench, "cell": cell, "config": config,
            "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"]
                           if m["name"] in reports],
            "per_layer": layer,
            "plane": HERE / "planes" / f"{config['plane']}.py",
            "readers": {m["name"]: HERE / "metrics" / f"{m['name']}.py"
                        for m in layer}}


def check_device(chips: int) -> dict:
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise SystemExit(f"chipbench: needs a TPU, but JAX found "
                         f"{d0.platform!r} ({d0.device_kind}); there is no "
                         f"CPU fallback")
    if len(devs) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chip(s), JAX "
                         f"sees {len(devs)}")
    PK.peaks_for(d0.device_kind)
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


class Compiles:
    """Counts and times XLA compilations, and counts the programs read from
    the persistent cache instead, through ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.n, self.s, self.hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, duration: float, **_) -> None:
        if event.endswith("backend_compile_duration"):
            self.n += 1
            self.s += duration

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def __str__(self) -> str:
        return f"{self.n} compiled ({self.s:.3f} s), {self.hits} from cache"


def enable_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says); every program is kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def apply_precision(config: dict) -> str:
    """Run the program at the matmul precision its configuration states."""
    import jax
    prec = config.get("precision", {}).get("matmul", "default")
    if prec != "default":
        jax.config.update("jax_default_matmul_precision", prec)
    return prec


def peak_memory() -> int:
    import jax
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def prepare(workload: str, seed: int, seconds: float,
            root: pathlib.Path = ROOT, need_chip: bool = True,
            override: dict | None = None) -> dict:
    """Everything before the window: the device check, the inputs, and the
    set-up that compiles the window's shapes and sizes it.
    ``need_chip=False`` and ``override`` (``{"config": {...}, "traffic":
    {...}}``, merged into the files' values) let a CPU test drive every
    other step of a run at a small size."""
    import jax
    spec = load_cell(workload, root)
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    if override:
        config = _merge(config, override.get("config", {}))
        traffic = _merge(traffic, override.get("traffic", {}))
    device = (check_device(cell["chips"]) if need_chip else
              {"platform": jax.devices()[0].platform,
               "kind": jax.devices()[0].device_kind,
               "count": len(jax.devices())})
    cache = enable_cache() if need_chip else "off"
    log(f"device {device} jax {jax.__version__} compile cache {cache}")
    log(f"matmul precision {apply_precision(config)}")
    compiles = Compiles()
    plane = load_module(spec["plane"], f"plane_{config['plane']}")
    sess = plane.Session(config, traffic, seed)
    # each set-up step's line carries the programs built so far
    sess.setup(seconds, lambda msg: log(f"{msg} [{compiles}]"))
    setup_s = time.perf_counter() - _T_START
    log(f"setup_s {setup_s:.3f}: {compiles.n} compilations, "
        f"{compiles.s:.3f} s compiling ({100 * compiles.s / setup_s:.1f}%), "
        f"{compiles.hits} programs from the cache")
    return {"spec": spec, "cell": cell, "config": config, "traffic": traffic,
            "device": device, "compiles": compiles, "session": sess,
            "setup_s": setup_s, "need_chip": need_chip}


def measure(run: dict, trace: bool) -> dict:
    """The window, its metrics, and the comparison that decides
    ``correct``."""
    import jax
    spec, cell, config, traffic = (run["spec"], run["cell"], run["config"],
                                   run["traffic"])
    sess, compiles, need_chip = run["session"], run["compiles"], run["need_chip"]
    device = dict(run["device"])
    n0, s0 = compiles.n, compiles.s
    tdir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    t0 = time.perf_counter()
    sess.window()
    window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    log(f"window {window_s:.3f} s, {sess.units()} {sess.unit}, "
        f"compilations in window: {compiles.n - n0} "
        f"({compiles.s - s0:.3f} s)")
    memory = peak_memory()
    device["memory_peak_bytes"] = memory

    metrics: dict = {}
    result: dict = {}
    if trace:
        tr = TR.load(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        busy = TR.busy_s(tr)
        device["busy_s"] = busy
        device["window_s"] = window_s
        ctx = {"cell": cell, "config": config, "traffic": traffic,
               "session": sess, "spans": sess.spans(), "units": sess.units(),
               "window_s": window_s, "busy_s": busy, "trace": tr,
               "peaks": PK.peaks_for(device["kind"]) if need_chip else None}
        for m in spec["per_layer"]:
            reader = load_module(spec["readers"][m["name"]],
                                 "metric_" + m["name"].replace(".", "_"))
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = TR.breakdown(tr)
        del tr, ctx
    else:
        values = sess.end_to_end(window_s)
        values["setup_s"] = run["setup_s"]
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    sess.free()
    try:
        readings = sess.compare()
    finally:
        sess.close()
    # the traffic's limits name the numbers compared; a limit without a
    # reading fails
    limits = traffic["check"]["limits"]
    checks = {k: {"value": float(readings.get(k, math.inf)), "limit": lim}
              for k, lim in limits.items()}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    out = {"correct": correct, "attempted": sess.units(), "failed": 0,
           "metrics": metrics, "device": device}
    out.update(result)
    out["checks"] = checks
    return out


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(base[k], v) if isinstance(v, dict) and isinstance(
            base.get(k), dict) else v
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    out = measure(prepare(args.workload, args.seed, args.seconds),
                  bool(args.trace))
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
