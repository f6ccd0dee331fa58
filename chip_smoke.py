#!/usr/bin/env python
"""Bring-up smoke: the DySTop federation's main path on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the row-sharded fleet mesh

One process drives everything through the entry points a user calls
(``run_simulation``, ``run_lm_federation``, ``engine_from_checkpoint``), with
data and weights made from a seed.

One chip, in phases:

1. device: JAX must find a TPU; there is no CPU fallback.
2. sim plane at the paper's default geometry (N=100 workers, DySTop
   V=10, t_thre=20, 7 neighbours, 16 activations per round), once with the
   Pallas kernels compiled (``interpret=False``) and once with the jnp
   reference.  The control plane must be equal, the accuracy curves close,
   and the lowered mega-round must hold the aggregate-panel and fused-SGD
   kernels.
3. LM plane: smollm-135m at its published widths, 4 workers, Adam, on the
   Pallas kernels (snapshotting the fleet) and on the reference.
4. serving: 8 greedy requests from the Eq. 11 global model of phase 3's
   snapshot, through the checkpoint bridge.

``--chips 4`` runs only the sharded path and what it is compared with: both
planes at ``mesh_shards=4`` against ``mesh_shards=1``, and checks that the
resident LM buffer is split over four devices.

Every line but the last starts with ``#`` and is informational.  The last
line is one JSON object: ``{"ok": true, "device": {...}}``.  Any failure
raises, so the process exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import pathlib
import shutil
import sys
import tempfile
import time

import jax
import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

from repro.checkpoint.io import latest_checkpoint  # noqa: E402
from repro.core.protocol import DySTop  # noqa: E402
from repro.dfl import lm_worker as LW  # noqa: E402
from repro.dfl.simulator import SimConfig, run_simulation  # noqa: E402
from repro.kernels.config import KernelConfig  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.models import registry as R  # noqa: E402
from repro.serving import GenerationConfig  # noqa: E402
from repro.serving.bridge import engine_from_checkpoint  # noqa: E402

PALLAS = KernelConfig(backend="pallas", interpret=False)
REFERENCE = KernelConfig()
SIM_KW = dict(n_workers=100, n_rounds=32)
LM_ARCH = "smollm-135m"
LM_KW = dict(n_workers=4, n_rounds=8, batch=2, seq=256, optimizer="adam",
             scan_horizon=4, eval_every=4)
# Pallas against the jnp reference on the chip.  XLA's default f32 matmul on
# a TPU rounds its operands to bf16 (relative error 2^-9), while the Mosaic
# kernels keep f32, so the two runs are two roundings of one trajectory:
# sim accuracy on the held-out set may move by a few test samples per eval
# (absolute 0.05 allows 5 points), and the LM's bf16 weights may differ by
# an ulp after each mix, which moves a ~10-nat loss by far less than 2%.
SIM_ACC_TOL = 0.05
LM_LOSS_RTOL = 2e-2
N_REQUESTS = 8
NEW_TOKENS = 32

_compile_s = [0.0]


def info(msg: str) -> None:
    print(f"# {msg}", flush=True)


def _count_compile(event: str, duration: float, **_) -> None:
    if event.endswith("backend_compile_duration"):
        _compile_s[0] += duration


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def device_phase(chips: int) -> dict:
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, but JAX found platform "
                         f"{d0.platform!r} ({d0.device_kind}); there is no "
                         f"CPU fallback")
    require(len(devs) >= chips, f"--chips {chips} but JAX sees {len(devs)} "
                                f"device(s)")
    info(f"device kind={d0.device_kind} count={len(devs)} "
         f"jax={jax.__version__}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def _mech():
    return DySTop(V=10.0, t_thre=20, max_neighbors=7, max_workers=16)


def sim_run(label: str, kernels: KernelConfig, mesh_shards: int = 1):
    t0, c0 = time.perf_counter(), _compile_s[0]
    h = run_simulation(_mech(), SimConfig(**SIM_KW, kernels=kernels,
                                          mesh_shards=mesh_shards))
    info(f"sim.{label} wall_s={time.perf_counter() - t0:.3f} "
         f"compile_s={_compile_s[0] - c0:.3f} rounds={len(h.round_active)} "
         f"acc_global={h.acc_global}")
    require(all(math.isfinite(a) for a in h.acc_global),
            f"sim.{label}: non-finite accuracy {h.acc_global}")
    return h


def compare_sim(a, b, what: str) -> None:
    for f in ("sim_time", "comm_gb", "round_active"):
        require(getattr(a, f) == getattr(b, f),
                f"sim {what}: control plane differs in {f}")
    diff = float(np.max(np.abs(np.subtract(a.acc_global, b.acc_global))))
    info(f"sim {what}: control plane equal, max |acc diff|={diff:.6f} "
         f"(tol {SIM_ACC_TOL})")
    require(diff <= SIM_ACC_TOL, f"sim {what}: accuracy diff {diff} > "
                                 f"{SIM_ACC_TOL}")


def lowered_kernels(dump_dir: pathlib.Path) -> set:
    """Pallas kernel names in the dumped lowering of the sim mega-round."""
    names = set()
    for f in dump_dir.glob("*mega_round_step*.mlir"):
        text = f.read_text()
        for name in ("dystop_aggregate_panel", "dystop_fused_sgd"):
            if f'kernel_name = "{name}"' in text and "tpu_custom_call" in text:
                names.add(name)
    return names


def lm_run(label: str, kernels: KernelConfig, mesh_shards: int = 1,
           ckpt_dir=None):
    cfg = R.get_config(LM_ARCH)
    kw = dict(LM_KW, kernels=kernels, mesh_shards=mesh_shards)
    if ckpt_dir is not None:
        kw.update(checkpoint_every=LM_KW["n_rounds"],
                  checkpoint_dir=str(ckpt_dir))
    t0, c0 = time.perf_counter(), _compile_s[0]
    fleet, h = LW.run_lm_federation(_mech(), cfg, LW.LMRunConfig(**kw))
    wall = time.perf_counter() - t0
    tokens = sum(h.round_active) * LM_KW["batch"] * LM_KW["seq"]
    info(f"lm.{label} wall_s={wall:.3f} compile_s={_compile_s[0] - c0:.3f} "
         f"trained_tokens={tokens} tokens_per_s_incl_compile="
         f"{tokens / wall:.1f} loss_global={h.loss_global}")
    require(all(math.isfinite(x) for x in h.loss_global),
            f"lm.{label}: non-finite loss {h.loss_global}")
    return fleet, h


def compare_lm(a, b, what: str) -> None:
    for f in ("sim_time", "comm_gb", "round_active", "rounds"):
        require(getattr(a, f) == getattr(b, f),
                f"lm {what}: control plane differs in {f}")
    la, lb = np.asarray(a.loss_global), np.asarray(b.loss_global)
    rel = float(np.max(np.abs(la - lb) / np.abs(lb)))
    info(f"lm {what}: control plane equal, max rel loss diff={rel:.6f} "
         f"(tol {LM_LOSS_RTOL})")
    require(rel <= LM_LOSS_RTOL, f"lm {what}: loss diff {rel} > "
                                 f"{LM_LOSS_RTOL}")


def one_chip(scratch: pathlib.Path) -> None:
    # -- sim plane: Pallas (lowering dumped) against the reference ----------
    dump = scratch / "ir"
    jax.config.update("jax_dump_ir_to", str(dump))
    try:
        h_pal = sim_run("pallas", PALLAS)
    finally:
        jax.config.update("jax_dump_ir_to", "")
    found = lowered_kernels(dump)
    info(f"sim.pallas lowered mega-round kernels: {sorted(found)}")
    require(found == {"dystop_aggregate_panel", "dystop_fused_sgd"},
            f"sim.pallas: mega-round lowering lacks a Pallas kernel "
            f"(found {sorted(found)})")
    compare_sim(h_pal, sim_run("reference", REFERENCE), "pallas vs reference")

    # -- LM plane at published widths -----------------------------------------
    ckpt = scratch / "ckpt"
    cfg = R.get_config(LM_ARCH)
    info(f"lm arch={LM_ARCH} layers={cfg.n_layers} d_model={cfg.d_model} "
         f"heads={cfg.n_heads}x{cfg.resolved_head_dim} kv={cfg.n_kv_heads} "
         f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
         f"params={cfg.param_count() / 1e6:.1f}M")
    # only the histories are kept: each fleet's buffers are freed before the
    # next run allocates its own
    h_pal = lm_run("pallas", PALLAS, ckpt_dir=ckpt)[1]
    h_ref = lm_run("reference", REFERENCE)[1]
    compare_lm(h_pal, h_ref, "pallas vs reference")

    # -- serving from the Pallas fleet's snapshot -----------------------------
    snap = latest_checkpoint(ckpt)
    require(snap is not None, "lm.pallas wrote no snapshot")
    t0, c0 = time.perf_counter(), _compile_s[0]
    eng = engine_from_checkpoint(snap, dataclasses.replace(cfg, kernels=PALLAS),
                                 batch_slots=4, max_len=512)
    shutil.rmtree(ckpt)
    info("serve: cached decode takes the reference attention path "
         "(models/layers.py); the flash kernel covers only cache-free "
         "forwards")
    rng = np.random.default_rng(0)
    gen = GenerationConfig(max_new_tokens=NEW_TOKENS)
    rids = [eng.submit(rng.integers(0, cfg.vocab_size,
                                    int(rng.integers(32, 129))), gen)
            for _ in range(N_REQUESTS)]
    out = eng.run()
    wall = time.perf_counter() - t0
    for rid in rids:
        toks = out.get(rid, [])
        require(len(toks) == NEW_TOKENS and
                all(0 <= t < cfg.vocab_size for t in toks),
                f"serve: request {rid} returned {len(toks)} tokens {toks[:8]}")
    info(f"serve requests={len(rids)} new_tokens={NEW_TOKENS} ticks={eng.t} "
         f"wall_s={wall:.3f} compile_s={_compile_s[0] - c0:.3f} "
         f"first_request_tokens={out[rids[0]][:8]}")


def four_chips() -> None:
    h4 = sim_run("pallas.mesh4", PALLAS, mesh_shards=4)
    compare_sim(h4, sim_run("pallas.mesh1", PALLAS), "mesh 4 vs mesh 1")

    fleet4, h4 = lm_run("pallas.mesh4", PALLAS, mesh_shards=4)
    shards = fleet4.pbuf.addressable_shards
    devices = {s.device for s in shards}
    rows = [s.data.shape[0] for s in shards]
    n = LM_KW["n_workers"]
    info(f"lm.pallas.mesh4 pbuf shards: devices={sorted(d.id for d in devices)}"
         f" rows={rows}")
    require(len(devices) == 4 and rows == [n // 4] * 4,
            f"lm mesh 4: pbuf not split over 4 devices (rows {rows})")
    del fleet4, shards
    _, h1 = lm_run("pallas.mesh1", PALLAS)
    compare_lm(h4, h1, "mesh 4 vs mesh 1")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args()
    enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_count_compile)
    t0 = time.perf_counter()
    device = device_phase(args.chips)
    scratch = pathlib.Path(tempfile.mkdtemp(prefix="chip_smoke_"))
    try:
        if args.chips == 1:
            one_chip(scratch)
        else:
            four_chips()
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    info(f"total wall_s={time.perf_counter() - t0:.3f} "
         f"compile_s={_compile_s[0]:.3f}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
