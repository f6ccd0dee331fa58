"""Sim plane: one cell is one ``repro.dfl.simulator.run_simulation`` call.

The configuration file gives the fleet, the model, the dataset and the
engine; the traffic file gives the DySTop settings and how many rounds the
probe calls run.  The window is one call; its ``n_rounds`` is sized in
set-up so that the call lasts about ``--seconds``.

The run's seed draws the training and test data, passed in through
``run_simulation(data=, test=)``.  The program draws everything else (the
fleet's layout and speeds, the partition, w_0, the minibatches) from its
own ``SimConfig.seed``, which is the configuration's ``layout_seed``: with
the layout drawn from the run's seed, seeds differed in their work by up
to 30% in rounds/s (PERF.md).
"""
from __future__ import annotations

import math
import pathlib
import shutil
import tempfile
import types

import numpy as np

import gen
import ref_sim
from federation import Federation


class Session(Federation):
    unit = "rounds"

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.layout = config["layout_seed"]
        m = config["model"]
        full = gen.make_classification(config["data"]["n_samples"], m["dim"],
                                       n_classes=m["n_classes"], seed=seed)
        self.train, self.test = gen.train_test_split(
            full, config["data"]["test_frac"], seed)

    # -- the entry point ------------------------------------------------------
    def call(self, n_rounds: int, keep: bool = False):
        """One ``run_simulation`` call; it snapshots the fleet every
        ``checkpoint_every`` rounds into a fresh directory, which is kept
        (``self.snapshots``) only for the window."""
        from repro.core.protocol import DySTop
        from repro.dfl.simulator import SimConfig, run_simulation
        from repro.kernels.config import KernelConfig
        c, p = self.config, self.traffic["protocol"]
        snaps = tempfile.mkdtemp(prefix="chipbench_snap_")
        cfg = SimConfig(
            n_workers=c["n_workers"], n_rounds=n_rounds, phi=c["data"]["phi"],
            n_samples=c["data"]["n_samples"], dim=c["model"]["dim"],
            hidden=c["model"]["hidden"], seed=self.layout,
            kernels=KernelConfig(**c["kernels"]), checkpoint_dir=snaps,
            **c["run"])
        mech = DySTop(V=p["V"], t_thre=p["t_thre"],
                      max_neighbors=p["max_neighbors"],
                      max_workers=p["max_workers"])
        try:
            hist = run_simulation(mech, cfg, data=self.train, test=self.test)
        except BaseException:
            shutil.rmtree(snaps, ignore_errors=True)
            raise
        if keep:
            self.close()
            self.snapshots = snaps
        else:
            shutil.rmtree(snaps, ignore_errors=True)
        return hist

    def window(self):
        self._ref = None
        self.history = self.call(self.n_rounds, keep=True)
        return self.history

    # -- what the window did ---------------------------------------------------
    def units(self) -> int:
        return len(self.history.round_active)

    def end_to_end(self, window_s: float) -> dict:
        return {"sim_rounds_per_s": self.units() / window_s}

    def free(self) -> None:
        """The program's device state dies with its call."""

    def close(self) -> None:
        """Drop the window's snapshots."""
        if getattr(self, "snapshots", None):
            shutil.rmtree(self.snapshots, ignore_errors=True)
            self.snapshots = None

    def snapshot_change(self, t: int) -> dict:
        """Each leaf's change from w_0 in the window's own snapshot of round
        ``t``: the (N, P) buffer read back, its leaves in the flat layout's
        order (sorted names), less w_0 drawn from the layout seed."""
        path = pathlib.Path(self.snapshots) / f"ckpt_round{t:06d}.npz"
        with np.load(path, allow_pickle=False) as z:
            buf = np.asarray(z["params|buf"], np.float32)
        m = self.config["model"]
        p0 = ref_sim.init_mlp(self.layout, m["dim"], m["hidden"],
                              m["n_classes"])
        out, off = {}, 0
        for k in sorted(p0):
            size = int(np.prod(p0[k].shape))
            out[k] = buf[:, off:off + size] - np.asarray(p0[k]).reshape(1, -1)
            off += size
        return out

    # -- correctness -----------------------------------------------------------
    def reference(self, dtype: str = "float64"):
        """The plain reference's trajectory over the compared prefix."""
        c, tr = self.config, self.traffic
        n = c["n_workers"]
        parts, counts = gen.dirichlet_partition(
            self.train.y, self.train.n_classes, n, c["data"]["phi"],
            self.layout)
        sizes = np.array([len(q) for q in parts], np.float64)
        m = c["model"]
        n_params = (m["dim"] * m["hidden"] + m["hidden"] * m["hidden"]
                    + m["hidden"] * m["n_classes"] + 2 * m["hidden"]
                    + m["n_classes"])
        model_bytes = n_params * 4 * c["run"]["model_bytes_scale"]
        check = tr["check"]
        n_ctrl = min(self.n_rounds, check["control_rounds"])
        n_model = min(self.n_rounds, check["model_rounds"])
        ctrl_dtype = np.float64 if dtype == "float64" else np.float32
        ctrl = ref_sim.control_plane(
            n, c["network"], tr["protocol"], c["run"], counts, sizes,
            model_bytes, self.layout, max(n_ctrl, n_model), dtype=ctrl_dtype)
        model = ref_sim.model_plane(
            ctrl, n_model, c["run"]["eval_every"], m, c["run"], self.layout,
            self.train, self.test, parts, sizes,
            precision="highest" if dtype == "float64" else "high")
        return n_ctrl, ctrl, model

    def control(self):
        """The reference at the precision below the configuration's (float32
        control plane, ``high`` matmuls), shaped like the window's
        ``History`` so that it can stand in the program's place."""
        n_ctrl, ctrl, model = self.reference("float32")
        ev = [t - 1 for t in model["rounds"]]
        return types.SimpleNamespace(
            round_active=ctrl["n_active"], round_durations=ctrl["duration"],
            rounds=model["rounds"], loss_global=model["loss_global"],
            change=model["change"],
            **{k: [ctrl[k][i] for i in ev] for k in (
                "sim_time", "comm_gb", "staleness_avg", "staleness_max")})

    def compare(self, h=None) -> dict:
        """The numbers compared, each with the reading it gives: the
        window's ``History`` (or a stand-in) against the plain reference."""
        h = self.history if h is None else h
        if getattr(self, "_ref", None) is None:
            self._ref = self.reference("float64")
        n_ctrl, ctrl, model = self._ref
        mism, gap = self.control_gaps(h, ctrl, n_ctrl)
        # signed: the window's eval rounds its inputs, which averages out
        # over the evals, where slower or wrong training does not
        loss = [(h.loss_global[h.rounds.index(t)] - ref) / ref
                for t, ref in zip(model["rounds"], model["loss_global"])
                if t in h.rounds]
        return {"active_mismatches": mism, "control_rel_gap": gap,
                "loss_mean_rel_gap": abs(float(np.mean(loss))) if loss
                else math.inf,
                "change_norm_gap": _change_gap(
                    self._change(h, model["rounds"][-1] if model["rounds"]
                                 else 0), model["change"])}

    def _change(self, h, t: int):
        if hasattr(h, "change"):
            return h.change
        try:
            return self.snapshot_change(t)
        except (OSError, KeyError, TypeError):
            return None


def _change_gap(prog, ref) -> float:
    """The worst leaf's gap between the norms of the program's and the
    reference's change from w_0, over the reference's norm of that leaf or
    of the median leaf, whichever is larger."""
    if prog is None:
        return math.inf
    norms = {k: float(np.linalg.norm(v)) for k, v in ref.items()}
    floor = float(np.median(list(norms.values())))
    return max(abs(float(np.linalg.norm(prog[k])) - norms[k])
               / max(norms[k], floor, 1e-30) for k in ref)

