"""Device time by named scope (``chipbench/scopes.py``): on a CPU profile
of a small jitted program, and on a hand-made device plane that names its
ops as a TPU trace does and holds the real HLO proto of that program."""
import glob
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import scopes as SC  # noqa: E402


@jax.jit
def _step(x):
    with jax.named_scope("mega_round"):
        with jax.named_scope("mix"):
            y = x @ x
        with jax.named_scope("write_back"):
            y = jnp.sort(y, axis=0)
    return y


def _x():
    return jnp.asarray(np.random.default_rng(0).normal(size=(128, 128)),
                       jnp.float32)


@pytest.fixture(scope="module")
def cpu_xspace(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("prof"))
    x = _x()
    _step(x).block_until_ready()
    jax.profiler.start_trace(d)
    for _ in range(3):
        _step(x).block_until_ready()
    jax.profiler.stop_trace()
    found = glob.glob(d + "/**/*.xplane.pb", recursive=True)
    return pathlib.Path(found[0]).read_bytes()


def test_cpu_profile_is_charged_to_the_innermost_scope(cpu_xspace):
    paths = SC.op_paths(cpu_xspace)
    assert any("/mix/" in p for p, _, _ in paths)
    secs = SC.scope_seconds(cpu_xspace)
    assert secs.get("mix", 0) > 0 and secs.get("write_back", 0) > 0
    assert "mega_round" not in secs        # every op has an inner scope
    leaves = SC.devtrace.leaves(paths)
    assert sum(secs.values()) == pytest.approx(
        sum(d for _, _, d in leaves) / 1e9)


def test_innermost_scope():
    assert SC.innermost("jit(f)/mega_round/while/body/mix/dot", SC.SCOPES) \
        == "mix"
    assert SC.innermost("jit(f)/mega_round/add", SC.SCOPES) == "mega_round"
    assert SC.innermost("jit(f)/reshape", SC.SCOPES) == SC.NO_SCOPE


# -- a hand-made XSpace, in protobuf wire form ------------------------------
def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _f(no, v):
    if isinstance(v, int):
        return _varint(no << 3) + _varint(v)
    v = v.encode() if isinstance(v, str) else v
    return _varint(no << 3 | 2) + _varint(len(v)) + v


def _plane(name, lines, events_md, stat_names):
    out = _f(2, name)
    for k, md in events_md.items():
        out += _f(4, _f(1, k) + _f(2, md))
    for k, s in stat_names.items():
        out += _f(5, _f(1, k) + _f(2, _f(1, k) + _f(2, s)))
    for lname, t0, evs in lines:
        line = _f(2, lname) + _f(3, t0)
        for mid, off_ps, dur_ps in evs:
            line += _f(4, _f(1, mid) + _f(2, off_ps) + _f(3, dur_ps))
        out += _f(3, line)
    return out


def test_a_tpu_style_plane_is_joined_through_the_module_line():
    x = _x()
    hlo = _step.lower(x).compile().runtime_executable().hlo_modules()[0]
    module = hlo.as_serialized_hlo_module_proto()
    mod_name, ops = SC.hlo_op_names(_f(1, module))
    insts = {SC.innermost(p, SC.SCOPES): i for i, p in ops.items()}
    assert {"mix", "write_back"} <= set(insts)
    meta = _plane("/host:metadata", [], {1: _f(2, mod_name) + _f(
        5, _f(1, 1) + _f(6, _f(1, module)))}, {1: "Hlo Proto"})
    # op events name their instruction by its HLO text, as a TPU's do; the
    # module comes from the "XLA Modules" line over them
    dev = _plane(
        "/device:TPU:0",
        [("XLA Modules", 1000, [(1, 0, 9_000_000)]),
         ("XLA Ops", 1000, [(2, 0, 2_000_000), (3, 3_000_000, 4_000_000),
                            (4, 7_000_000, 1_000_000)])],
        {1: _f(2, f"{mod_name}(7)"),
         2: _f(2, f"%{insts['mix']} = f32[128,128] dot(...)"),
         3: _f(2, f"%{insts['write_back']} = f32[128,128] sort(...)"),
         4: _f(2, "%not_in_the_module.3 = f32[] add(...)")},
        {})
    secs = SC.scope_seconds(_f(1, meta) + _f(1, dev))
    assert secs == pytest.approx({"mix": 2e-6, "write_back": 4e-6,
                                  SC.NO_SCOPE: 1e-6})
