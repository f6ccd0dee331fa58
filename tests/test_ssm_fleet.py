"""Mamba-2 on the LM fleet plane, at the smoke widths on the CPU.

The program's chunked SSD (the jnp dual form and the Pallas kernel in
interpret mode) against the benchmark's plain per-step recurrence
(``chipbench/ref_ssm.py``), a short federation against the same reference,
and the embedding-scale field.  Sequences hold 4 chunks, so the state
crosses chunk boundaries.
"""
import dataclasses
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.config import KernelConfig
from repro.models import registry as R
from repro.models import transformer as T

BENCH = pathlib.Path(__file__).resolve().parents[1] / "chipbench"
sys.path.insert(0, str(BENCH))

import ref_ssm  # noqa: E402
import run as RUN  # noqa: E402

SEQ = 128                      # 4 chunks of the smoke config's 32


def _smoke(dtype="bfloat16", backend="reference"):
    return dataclasses.replace(R.get_smoke_config("mamba2-2.7b"), dtype=dtype,
                               kernels=KernelConfig(backend=backend))


def _ref_model(cfg):
    """The configuration's ``model`` block as the reference reads it."""
    s = cfg.ssm
    return {"hidden_size": cfg.d_model, "num_hidden_layers": cfg.n_layers,
            "vocab_size": cfg.vocab_size,
            "embedding_rows": ((cfg.vocab_size + 255) // 256) * 256,
            "expand": s.expand, "head_dim": s.head_dim,
            "state_size": s.d_state, "conv_kernel": s.conv_width,
            "chunk_size": s.chunk_size, "layer_norm_epsilon": cfg.norm_eps}


def _as_ref(tree):
    """The program's parameter tree (or its gradient) in the reference's
    layout."""
    blk = tree["blocks"]["p0"]
    return {"embed": tree["embed"]["table"], "final_norm": tree["final_norm"],
            "blocks": {"ln1": blk["ln1"], **blk["ssm"]}}


def _tokens(cfg, seed=0, batch=2):
    rng = np.random.default_rng(seed)
    tok = rng.integers(0, cfg.vocab_size, (batch, SEQ + 1))
    return jnp.asarray(tok[:, :-1], jnp.int32), jnp.asarray(tok[:, 1:],
                                                            jnp.int32)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_chunked_ssd_matches_the_recurrence(backend):
    """Logits and every parameter's gradient of the whole model, in f32 at
    ``highest`` precision.  The chunked form sums the same terms as the
    recurrence in another order and forms the decays as exp of differences
    of cumulative sums instead of products of exps, so the two agree to f32
    rounding over 128 steps: at most 1.4e-6 measured (logits 4e-7).  1e-4
    leaves room for that and lies far below what a wrong chunk boundary
    gives: with the state dropped at each boundary the logits read 1.3e-1
    and the gradients 9e-2 to 8e-1."""
    cfg = _smoke("float32", backend)
    m = _ref_model(cfg)
    params, _ = R.init_params(cfg, jax.random.PRNGKey(3))
    # non-trivial norm weights and per-head skips, so that their gradients
    # and the paths through them are checked too
    params = jax.tree.map(
        lambda l: l + 0.1 * jax.random.normal(jax.random.PRNGKey(l.size),
                                              l.shape, l.dtype)
        if l.size < 4096 else l, params)
    tok, lab = _tokens(cfg)
    batch = {"tokens": tok, "labels": lab}

    def prog_loss(p):
        return R.compute_loss(cfg, p, batch)[0]

    with jax.default_matmul_precision("highest"):
        logits, _ = T.forward(cfg, params, tok)
        want = ref_ssm.forward(_as_ref(params), tok, m)
        g_prog = _as_ref(jax.grad(prog_loss)(params))
        g_ref = jax.grad(ref_ssm.loss_fn)(_as_ref(params), tok, lab, m)
    assert _rel(logits[..., :cfg.vocab_size], want) < 1e-4
    for k in ("embed", "final_norm"):
        assert _rel(g_prog[k], g_ref[k]) < 1e-4, k
    for k, g in g_ref["blocks"].items():
        assert _rel(g_prog["blocks"][k], g) < 1e-4, k


def test_unscaled_embedding_is_what_the_reference_reads():
    """The scaled embedding (every other family's) moves the logits far
    from the published Mamba-2's: the field is what the match above
    rests on."""
    cfg = dataclasses.replace(_smoke("float32"), scale_embeddings=True)
    params, _ = R.init_params(cfg, jax.random.PRNGKey(3))
    tok, _ = _tokens(cfg)
    with jax.default_matmul_precision("highest"):
        logits, _ = T.forward(cfg, params, tok)
        want = ref_ssm.forward(_as_ref(params), tok, _ref_model(cfg))
    assert _rel(logits[..., :cfg.vocab_size], want) > 1e-2


@pytest.mark.parametrize("arch,scaled", [("smollm-135m", True),
                                         ("mamba2-2.7b", False)])
def test_embedding_scale_follows_the_config(arch, scaled, monkeypatch):
    """What enters the first layer, bitwise: the table rows times
    sqrt(d_model) in the model's dtype where the configuration scales
    (every family but Mamba-2's, unchanged), the rows as they are where it
    does not."""
    cfg = R.get_smoke_config(arch)
    assert cfg.scale_embeddings is scaled
    params, _ = R.init_params(cfg, jax.random.PRNGKey(0))
    tok, _ = _tokens(cfg)
    seen = []
    real = T.constrain

    def spy(x, axes):
        seen.append(x)
        return real(x, axes)

    monkeypatch.setattr(T, "constrain", spy)
    T.forward(cfg, params, tok)
    x = params["embed"]["table"][tok].astype(jnp.dtype(cfg.dtype))
    if scaled:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    np.testing.assert_array_equal(np.asarray(seen[0]), np.asarray(x))


def _plane_session(seed):
    spec = RUN.load_cell("lm-mamba2l4-n2")
    cfg = _smoke()
    over = {"model": _ref_model(cfg) | {"num_heads": cfg.ssm.expand
                                        * cfg.d_model // cfg.ssm.head_dim},
            "n_workers": 3, "run": {"eval_every": 4}}
    config = RUN._merge(spec["config"], over)
    traffic = RUN._merge(spec["traffic"], {"batch": {"batch": 1, "seq": SEQ}})
    plane = RUN.load_module(spec["plane"], "plane_lm_ssm_test")
    return plane.Session(config, traffic, seed)


def test_federation_matches_the_reference_on_losses():
    """Three rounds of ``run_lm_federation`` on the Mamba-2 smoke config
    (Pallas forward in interpret mode, bf16 weights and activations)
    against the reference's replay in f32: the control plane exactly, each
    round's mean training loss within 2e-3 relative.  bf16 weights and
    activations through 2 layers put the program 1.7e-4 to 2.3e-4 from the
    reference (3 seeds, measured); the reference with float8 weights and
    matmul inputs (the control) reads 4.2e-3 to 1.2e-2, so 2e-3 lies
    between the two with room on both sides."""
    sess = _plane_session(2**31 + 5)
    sess.n_rounds = 3
    h = sess.window()
    assert len(h.round_loss) == 3 and sum(h.round_active) >= 3
    out = sess.compare()
    assert out["active_mismatches"] == 0 and out["control_rel_gap"] == 0
    assert out["step_loss_rel_gap"] < 2e-3, out
    assert sess.compare(sess.control())["step_loss_rel_gap"] > 2e-3


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_gradients_stay_finite_where_decay_overflows(backend):
    """At the published chunk of 256 the cumulative log-decay within a
    chunk reaches hundreds, and exp of the masked upper triangle
    (cum_q - cum_t for t > q) overflows f32.  The decay is masked before
    the exp, so the gradients stay finite and still match the recurrence.
    Here dt_bias = 2 gives log-decays of 2 to 34 a step, over 2^7 within a
    chunk of 32."""
    cfg = _smoke("float32", backend)
    m = _ref_model(cfg)
    params, _ = R.init_params(cfg, jax.random.PRNGKey(5))
    ssm = params["blocks"]["p0"]["ssm"]
    ssm["dt_bias"] = jnp.full_like(ssm["dt_bias"], 2.0)
    tok, lab = _tokens(cfg)
    batch = {"tokens": tok, "labels": lab}
    with jax.default_matmul_precision("highest"):
        g_prog = _as_ref(jax.grad(
            lambda p: R.compute_loss(cfg, p, batch)[0])(params))
        g_ref = jax.grad(ref_ssm.loss_fn)(_as_ref(params), tok, lab, m)
    for k, g in g_ref["blocks"].items():
        assert np.isfinite(np.asarray(g_prog["blocks"][k])).all(), k
        assert _rel(g_prog["blocks"][k], g) < 1e-4, k
