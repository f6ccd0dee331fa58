"""Every cell resolves its files by name, and a run without a TPU fails."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
ROOT = HERE.parent
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
sys.path.insert(0, str(HERE))

import run as RUN  # noqa: E402


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_by_name(cell):
    spec = RUN.load_cell(cell)
    assert spec["plane"].is_file()
    plane = RUN.load_module(spec["plane"], "plane_" + cell)
    assert hasattr(plane, "Session")
    assert spec["traffic"]["check"]["limits"]
    assert spec["end_to_end"] and any(
        m["name"] == "setup_s" for m in spec["end_to_end"])
    assert spec["per_layer"]
    for name, path in spec["readers"].items():
        reader = RUN.load_module(path, "metric_" + name.replace(".", "_"))
        assert callable(reader.read)


def test_configs_and_metrics_are_used():
    cells = BENCH["workloads"]
    assert {c["name"] for c in BENCH["configs"]} == {w["config"] for w in cells}
    names = {w["name"] for w in cells}
    for m in BENCH["per_layer"]:
        assert set(m["workloads"]) <= names
        assert (HERE / "metrics" / f"{m['name']}.py").is_file()
    for c in BENCH["configs"]:
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    cell = BENCH["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload", cell,
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "correct" not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_importing_the_harness_leaves_jax_unloaded():
    code = ("import sys; sys.path.insert(0, %r); import run, gen, ref_sim, "
            "devtrace, work, peaks, faults; print('jax' in sys.modules)"
            % str(HERE))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert proc.stdout.strip() == "False", proc.stderr
