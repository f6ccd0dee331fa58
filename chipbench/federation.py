"""What the federation planes share: the set-up that compiles and sizes the
window, the program's host spans, and the comparison of the control plane.

A plane's ``Session`` subclasses ``Federation`` and defines ``call(n_rounds)``
(one call of the program's entry point with the run's seed), ``window()``,
what the window did and the model-plane comparison.
"""
from __future__ import annotations

import math
import time

SPANS = ("plan_wall_s", "pack_wall_s", "stage_wall_s", "drain_wall_s",
         "eval_wall_s", "setup_wall_s", "wall_s")


class Federation:
    n_rounds = 0
    history = None

    def setup(self, seconds: float, log) -> None:
        """Compile every shape the window will use, then size it.

        A probe call compiles the probe's shapes, a second one times them,
        and a call of the window's own length and seed compiles whatever
        the longer trajectory adds: the window repeats that call."""
        probe = self.traffic["probe_rounds"]
        step = self.config["run"]["eval_every"]
        self.call(probe)
        t0 = time.perf_counter()
        h = self.call(probe)
        total = time.perf_counter() - t0
        # the call's fixed cost (inputs, init) and its cost per round, from
        # the program's own set-up span
        fixed = min(h.setup_wall_s, total)
        rate = probe / max(total - fixed, 1e-9)
        want = max(seconds - fixed, 0.0) * rate
        self.n_rounds = max(probe, step * math.ceil(want / step))
        log(f"probe {probe} rounds: {fixed:.3f} s fixed + {rate:.2f} "
            f"rounds/s; window of {self.n_rounds} rounds")
        if self.n_rounds > probe:
            self.call(self.n_rounds)

    def spans(self) -> dict:
        return {k: getattr(self.history, k) for k in SPANS}

    @staticmethod
    def control_gaps(h, ctrl: dict, n_ctrl: int) -> tuple:
        """The window's (or a stand-in's) control plane against the
        reference's over the first ``n_ctrl`` rounds: the count of
        mismatched activations and staleness maxima, and the widest
        relative gap of the Eq. 9 durations and of sim time, comm bytes and
        mean staleness at each eval."""
        mism = sum(int(a != b) for a, b in
                   zip(h.round_active[:n_ctrl], ctrl["n_active"][:n_ctrl]))
        mism += abs(len(h.round_active[:n_ctrl]) - n_ctrl)
        gaps = [rel(a, b) for a, b in zip(h.round_durations[:n_ctrl],
                                          ctrl["duration"][:n_ctrl])]
        for i, t in enumerate(h.rounds):
            if t > n_ctrl:
                break
            for k in ("sim_time", "comm_gb", "staleness_avg"):
                gaps.append(rel(getattr(h, k)[i], ctrl[k][t - 1]))
            mism += int(h.staleness_max[i] != ctrl["staleness_max"][t - 1])
        return float(mism), max(gaps) if gaps else math.inf


def rel(a: float, b: float) -> float:
    if a == b:
        return 0.0
    if not (math.isfinite(a) and math.isfinite(b)):
        return math.inf
    return abs(a - b) / max(abs(b), 1e-30)
