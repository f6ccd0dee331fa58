"""Shared by the fault tests: small sizes of each plane and the check that
a run failed its comparison."""
import json
import math
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import faults as FA  # noqa: E402
import run as RUN  # noqa: E402

BENCH = json.loads((HERE.parent / "BENCHMARK.json").read_text())
CELLS = {w["name"]: w for w in BENCH["workloads"]}
SMALL = {
    "sim": {"config": {"n_workers": 8, "data": {"n_samples": 800},
                       "run": {"checkpoint_every": 20, "scan_horizon": 4}},
            "traffic": {"probe_rounds": 10, "protocol": {"max_workers": 3},
                        "check": {"control_rounds": 40, "model_rounds": 20}}},
    "lm": {"config": {"model": {"hidden_size": 64, "intermediate_size": 128,
                                "num_attention_heads": 2,
                                "num_key_value_heads": 1, "head_dim": 32,
                                "num_hidden_layers": 2, "vocab_size": 512},
                      "run": {"eval_every": 4}},
           "traffic": {"batch": {"batch": 2, "seq": 16}, "probe_rounds": 8}},
}


def plane_of(cell):
    cfg = next(c for c in BENCH["configs"]
               if c["name"] == CELLS[cell]["config"])
    return json.loads((HERE.parent / cfg["file"]).read_text())["plane"]


def failed(out):
    return not out["correct"] and any(
        not (math.isfinite(c["value"]) and c["value"] <= c["limit"])
        for c in out["checks"].values())


CASES = [None, "control", "unchanged", "half_batch", "plan_altered",
         "loss_altered"]


def check_case(plane, run, fault):
    """A clean window is correct; the control and each fault are not."""
    if fault is None:
        out = RUN.measure(run, trace=False)
        assert out["correct"], out["checks"]
        assert list(out)[-1] == "checks"
        return
    if fault == "control":
        sess = run["session"]
        sess.window()
        readings = sess.compare(sess.control())
        sess.close()
        limits = run["traffic"]["check"]["limits"]
        assert any(v > limits[k] for k, v in readings.items()
                   if k in limits), readings
        return
    with FA.FAULTS[plane][fault]():
        out = RUN.measure(run, trace=False)
    assert failed(out), out["checks"]
