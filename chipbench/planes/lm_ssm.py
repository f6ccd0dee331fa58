"""LM plane over a Mamba-2 model: the ``planes/lm.py`` session, with the
model built from a Mamba-2 configuration and replayed by ``ref_ssm``.

Everything else (the window, its sizing, the control plane, the
comparison that decides ``correct``) is ``planes/lm.py``'s, loaded from
that file as a module of its own, whose reference is ``ref_ssm``.
"""
from __future__ import annotations

import importlib.util
import pathlib

import numpy as np

import ref_sim
import ref_ssm
import work_ssm


def _lm_plane():
    path = pathlib.Path(__file__).resolve().parent / "lm.py"
    spec = importlib.util.spec_from_file_location("plane_lm_of_ssm", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # its reference replays, and its fleet reads name, the Mamba-2 model
    mod.ref_lm = ref_ssm
    return mod


_LM = _lm_plane()


class Session(_LM.Session):

    def call(self, n_rounds: int, keep: bool = False):
        """One ``run_lm_federation`` call of the Mamba-2 configuration."""
        from repro.configs.base import ModelConfig, SSMConfig
        from repro.core.protocol import DySTop
        from repro.dfl import lm_worker as LW
        from repro.kernels.config import KernelConfig
        c, tr = self.config, self.traffic
        p, b, m = tr["protocol"], tr["batch"], c["model"]
        cfg = ModelConfig(
            arch_id=c["name"], family="ssm",
            n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
            n_heads=1, n_kv_heads=1, d_ff=m["intermediate_size"],
            vocab_size=m["vocab_size"],
            ssm=SSMConfig(d_state=m["state_size"], head_dim=m["head_dim"],
                          expand=m["expand"], chunk_size=m["chunk_size"],
                          conv_width=m["conv_kernel"]),
            norm_eps=m["layer_norm_epsilon"],
            tie_embeddings=m["tie_word_embeddings"],
            scale_embeddings=m["scale_embeddings"])
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

    def ssd_chunks(self) -> int:
        """Intra-chunk SSD calls of the window, one per chunk of a layer's
        forward: every activated worker's step and every Eq. 11 eval runs
        the model once on batch x seq tokens.  Bucket padding rows skip
        their step."""
        m, b = self.config["model"], self.traffic["batch"]
        runs = sum(self.history.round_active) + len(self.history.rounds)
        return (runs * m["num_hidden_layers"] * b["batch"] * b["seq"]
                // m["chunk_size"])

    def _control_plane(self, dtype, n_rounds: int):
        c, tr = self.config, self.traffic
        n, m = c["n_workers"], c["model"]
        f32 = work_ssm.f32_param_count(m)
        model_bytes = float(2 * (work_ssm.ssm_param_count(m) - f32) + 4 * f32)
        run = dict(c["run"], base_compute_s=1.0)
        return ref_sim.control_plane(
            n, c["network"], tr["protocol"], run, np.ones((n, 2)),
            np.ones(n), model_bytes, self.seed, n_rounds, dtype=dtype)
