"""LM plane: one cell is one ``repro.dfl.lm_worker.run_lm_federation`` call.

The configuration file gives the model (at its published widths), the fleet
and the engine; the traffic file gives the DySTop settings, the per-worker
batch and sequence, and how many rounds the probe calls run.  The window is
one call with the run's seed (weights made on the device inside the call);
its ``n_rounds`` is sized in set-up so that the call lasts about
``--seconds``.

``correct`` compares the window with the plain reference: the control
plane and the first rounds' training losses.  Where the traffic's check
sets ``fleet``, the reference replays all of the window's rounds and the
fleet the call returns is compared too, leaf by leaf (each leaf's change
from w_0 and its Adam first moment, as norms over the fleet).
"""
from __future__ import annotations

import functools
import gc
import math
import types

import numpy as np

import gen
import ref_lm
import ref_sim
import work
from federation import Federation, rel


class Session(Federation):
    unit = "trained tokens"

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self._ref = self._fleet = self._prog = None
        self.read_fleet = bool(traffic["check"].get("fleet", False))

    # -- the entry point ------------------------------------------------------
    def call(self, n_rounds: int, keep: bool = False):
        """One ``run_lm_federation`` call; the fleet it returns is kept
        (``keep``) only for the window, until ``free`` reads it."""
        from repro.configs.base import ModelConfig
        from repro.core.protocol import DySTop
        from repro.dfl import lm_worker as LW
        from repro.kernels.config import KernelConfig
        c, tr = self.config, self.traffic
        p, b, m = tr["protocol"], tr["batch"], c["model"]
        cfg = ModelConfig(
            arch_id=c["name"], family="dense",
            n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
            n_heads=m["num_attention_heads"],
            n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
            d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
            norm_eps=m["rms_norm_eps"], rope_theta=m["rope_theta"],
            tie_embeddings=m["tie_word_embeddings"])
        run = LW.LMRunConfig(
            n_workers=c["n_workers"], n_rounds=n_rounds, batch=b["batch"],
            seq=b["seq"], seed=self.seed,
            comm_range_m=c["network"]["comm_range_m"],
            optimizer="adam", lr=c["adam"]["lr"],
            kernels=KernelConfig(**c["kernels"]), **c["run"])
        mech = DySTop(V=p["V"], t_thre=p["t_thre"],
                      max_neighbors=p["max_neighbors"],
                      max_workers=p["max_workers"])
        fleet, hist = LW.run_lm_federation(mech, cfg, run)
        if keep:
            self._fleet = fleet
        del fleet                      # otherwise frees the resident buffers
        return hist

    def window(self):
        self._fleet = self._prog = None
        self.history = self.call(self.n_rounds, keep=self.read_fleet)
        return self.history

    # -- what the window did ---------------------------------------------------
    def tokens(self) -> int:
        b = self.traffic["batch"]
        return sum(self.history.round_active) * b["batch"] * b["seq"]

    def units(self) -> int:
        return self.tokens()

    def end_to_end(self, window_s: float) -> dict:
        return {"lm_tokens_per_s": self.tokens() / window_s}

    def mix_shapes(self):
        """(k, u) of every round's Eq. 4 in the window: the mixed rows and
        the rows they read, from the reference control plane."""
        ctrl = self._control_plane(np.float64, len(self.history.round_active))
        out = []
        for W, active in zip(ctrl["W"], ctrl["active"]):
            rows = np.flatnonzero((W != np.eye(len(W))).any(1) | active)
            if len(rows):
                out.append((len(rows), int((W[rows] != 0).any(0).sum())))
        return out

    def free(self) -> None:
        """Read the window's fleet (``_fleet_norms``), then drop it, so that
        the reference finds the chip's memory free."""
        fleet, self._fleet = self._fleet, None
        if fleet is not None:
            self._prog = self._fleet_norms(fleet)
        del fleet
        gc.collect()

    def _fleet_norms(self, fleet):
        """Each leaf's change from w_0 and Adam first moment, as norms over
        the fleet, read from the resident buffers the window returned.  The
        leaves are named as ``ref_lm.named`` names them; w_0 is the
        reference's own, drawn from the seed.  None where the program's
        leaves do not match the reference's."""
        import jax.numpy as jnp
        base = ref_lm.named(ref_lm.init_params(self.seed,
                                               self.config["model"]))
        ps, os_ = fleet.spec.params, fleet.spec.opt
        names = _leaf_names(ps.treedef)
        if sorted(names) != sorted(base) or any(
                tuple(base[k].shape) != tuple(s)
                for k, s in zip(names, ps.shapes)):
            return None
        w0 = jnp.concatenate([base[k].astype(jnp.float32).reshape(-1)
                              for k in names])
        del base
        change = _segment_norms(ps.offsets, ps.sizes, True)(fleet.pbuf, w0)
        mu = [(k[3:], o, n) for k, o, n in zip(_leaf_names(os_.treedef),
                                               os_.offsets, os_.sizes)
              if k.startswith("mu.")]
        moment = _segment_norms(tuple(o for _, o, _ in mu),
                                tuple(n for *_, n in mu), False)(fleet.obuf,
                                                                 w0)
        return {"change": dict(zip(names, np.asarray(change).tolist())),
                "moment": dict(zip([k for k, _, _ in mu],
                                   np.asarray(moment).tolist()))}

    def close(self) -> None:
        """Nothing is written outside the process."""

    # -- correctness -----------------------------------------------------------
    def _control_plane(self, dtype, n_rounds: int):
        c, tr = self.config, self.traffic
        n = c["n_workers"]
        m = c["model"]
        n_norm = (2 * m["num_hidden_layers"] + 1) * m["hidden_size"]
        model_bytes = float(2 * (work.lm_param_count(m) - n_norm) + 4 * n_norm)
        run = dict(c["run"], base_compute_s=1.0)
        return ref_sim.control_plane(
            n, c["network"], tr["protocol"], run, np.ones((n, 2)),
            np.ones(n), model_bytes, self.seed, n_rounds, dtype=dtype)

    def reference(self, fp8: bool = False):
        """The plain reference over the compared rounds (all of the
        window's where the fleet is read); ``fp8`` computes it one step below
        the configuration's precision (the control): float8 model plane,
        float32 control plane."""
        c, tr = self.config, self.traffic
        n_ctrl = min(self.n_rounds, tr["check"]["control_rounds"])
        n_model = (self.n_rounds if self.read_fleet else
                   min(self.n_rounds, tr["check"]["loss_rounds"]))
        ctrl = self._control_plane(np.float32 if fp8 else np.float64,
                                   max(n_ctrl, n_model))
        b = tr["batch"]
        m = c["model"]
        streams = gen.worker_streams(m["vocab_size"], c["n_workers"],
                                     b["batch"], b["seq"], self.seed)
        batches = [next(streams) for _ in range(n_model)]
        model = ref_lm.model_plane(ctrl, n_model, m, c["adam"], self.seed,
                                   batches, fp8=fp8, fleet=self.read_fleet)
        return n_ctrl, ctrl, model

    def control(self):
        """The reference with every weight and matmul input in float8
        (e4m3), shaped like the window's ``LMHistory``."""
        n_ctrl, ctrl, model = self.reference(fp8=True)
        every = self.config["run"]["eval_every"]
        ev = list(range(every, len(ctrl["n_active"]) + 1, every))
        return types.SimpleNamespace(
            round_active=ctrl["n_active"], round_durations=ctrl["duration"],
            rounds=ev, round_loss=model["round_loss"],
            change=model.get("change"), moment=model.get("moment"),
            **{k: [ctrl[k][t - 1] for t in ev] for k in (
                "sim_time", "comm_gb", "staleness_avg", "staleness_max")})

    def compare(self, h=None) -> dict:
        """The numbers compared, each with the reading it gives: the
        window's ``LMHistory`` and fleet (or a stand-in) against the plain
        reference."""
        if h is None:
            h = self.history
            if self._fleet is not None:
                self.free()
            prog = self._prog or {}
        else:
            prog = vars(h)
        # one replay per window length: faults planted under the same
        # seed's window compare with the same reference
        if self._ref is None or self._ref[0] != self.n_rounds:
            self._ref = (self.n_rounds, self.reference())
        n_ctrl, ctrl, model = self._ref[1]
        mism, gap = self.control_gaps(h, ctrl, n_ctrl)
        n_loss = self.traffic["check"]["loss_rounds"]
        steps = [rel(a, b) for a, b in zip(h.round_loss[:n_loss],
                                            model["round_loss"][:n_loss])
                 if b != 0.0]
        out = {"active_mismatches": mism, "control_rel_gap": gap,
               "step_loss_rel_gap": max(steps) if steps else math.inf}
        if self.read_fleet:
            # leaves whose gradient is nought to rounding in the reference
            # move under Adam by round-off alone: left out by its moment
            mom = model["moment"]
            floor = 1e-3 * float(np.median(list(mom.values())))
            keep = [k for k, v in mom.items() if v >= floor]
            out["change_norm_gap"] = _norm_gap(prog.get("change"),
                                               model["change"], keep)
            out["moment_norm_gap"] = _norm_gap(prog.get("moment"), mom, keep)
        return out


def _leaf_names(treedef) -> list:
    """The program's leaf paths, in its flat layout's order, as
    ``ref_lm.named`` names the model's leaves (``mu.``/``nu.`` in front for
    the optimizer's moments)."""
    import jax
    tree = jax.tree_util.tree_unflatten(treedef, range(treedef.num_leaves))
    out = []
    for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        pre = keys.pop(0) + "." if keys[0] in ("mu", "nu") else ""
        if not keys:
            out.append(pre.rstrip("."))
        elif keys[0] == "blocks":
            out.append(f"{pre}blocks.{keys[-1]}")
        else:
            out.append(pre + keys[0])
    return out


@functools.lru_cache(maxsize=None)
def _segment_norms(offsets: tuple, sizes: tuple, less_base: bool):
    """A jitted (N, P) buffer, (P,) base -> each column segment's norm over
    all rows (of ``buf - base`` where ``less_base``)."""
    import jax
    import jax.numpy as jnp

    def norms(buf, base):
        out = []
        for o, n in zip(offsets, sizes):
            d = buf[:, o:o + n]
            if less_base:
                d = d - base[None, o:o + n]
            out.append(jnp.sum(d * d))
        return jnp.sqrt(jnp.stack(out))

    return jax.jit(norms)


def _norm_gap(prog, ref: dict, keep: list) -> float:
    """The worst leaf's gap between the program's and the reference's norm,
    over the reference's norm of that leaf or of the median leaf, whichever
    is larger."""
    if not prog or not keep or any(k not in prog for k in keep):
        return math.inf
    floor = float(np.median([ref[k] for k in keep]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], floor, 1e-30)
               for k in keep)
