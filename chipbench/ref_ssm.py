"""Plain reference of one DySTop LM federation over a Mamba-2 model: the
control plane of ``ref_sim`` and the published Mamba-2 block in
straightforward ``jax.numpy``.

Written from the configuration alone; it imports nothing of the program
under test.  The model (arXiv 2405.21060, ``mamba_ssm``'s Mamba2 mixer
with one B/C group): token embedding (unscaled), then per layer a pre-norm
RMS norm, the fused in_proj into [z, x, B, C, dt], a depthwise causal conv
with SiLU over [x, B, C], dt = softplus(dt + dt_bias), A = -exp(A_log), the
per-step recurrence

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,    y_t = C_t h_t + D x_t,

the gated RMS norm rmsnorm(y * silu(z)), out_proj and the residual; a final
RMS norm and the tied head; mean token cross-entropy; Adam and the replay
of the rounds as ``ref_lm`` has them (``model_plane``).  The recurrence
is stepped token by token, not chunked, so that it checks the program's
chunking; its scan is checkpointed every ``chunk_size`` steps so that its
gradient fits.  Norm weights are stored as offsets from 1.  Weights are
drawn from the seed by the recipe of ``init_params``, stored in bfloat16
(norms and the per-head A_log, D, dt_bias in float32) as the configuration
states; the reference computes every activation in float32 at ``highest``
matmul precision.

The control (``fp8=True``) rounds every weight and every matmul input to
float8 e4m3 before use, the precision below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
import importlib.util
import pathlib

import numpy as np


def _dims(m: dict):
    d, p = m["hidden_size"], m["head_dim"]
    d_in = m["expand"] * d
    return d, d_in, d_in // p, p, m["state_size"], m["conv_kernel"]


def init_params(seed: int, m: dict):
    """w_0.  Key recipe: split(PRNGKey(seed), 4) -> embed, (unused), blocks,
    (unused); one key per layer, split(split(., 1)[0], 6)[0] is the
    mixer's, split 4: in_proj, out_proj, conv, (unused).  The table has
    ``embedding_rows`` rows; A_log = log of A evenly spaced over [1, 16],
    dt_bias = softplus^-1(0.01), D = 1, conv bias 0."""
    import jax
    import jax.numpy as jnp
    d, d_in, h, _, n, w = _dims(m)
    rows = m.get("embedding_rows", m["vocab_size"])
    bf = jnp.bfloat16
    conv_dim = d_in + 2 * n

    def normal(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * fan_in ** -0.5).astype(bf)

    k_embed, _, k_blocks, _ = jax.random.split(jax.random.PRNGKey(seed), 4)
    blocks = []
    for gk in jax.random.split(k_blocks, m["num_hidden_layers"]):
        lk = jax.random.split(jax.random.split(gk, 1)[0], 6)
        kin, kout, kconv, _ = jax.random.split(lk[0], 4)
        blocks.append({
            "ln1": jnp.zeros((d,), jnp.float32),
            "w_in": normal(kin, (d, 2 * d_in + 2 * n + h), d),
            "conv_w": normal(kconv, (w, conv_dim), w),
            "conv_b": jnp.zeros((conv_dim,), bf),
            "A_log": jnp.log(jnp.linspace(1.0, 16.0, h, dtype=jnp.float32)),
            "D": jnp.ones((h,), jnp.float32),
            "dt_bias": jnp.full((h,), np.log(np.expm1(0.01)), jnp.float32),
            "norm": jnp.zeros((d_in,), jnp.float32),
            "w_out": normal(kout, (d_in, d), d_in)})
    stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *blocks)
    return {"embed": normal(k_embed, (rows, d), d), "blocks": stacked,
            "final_norm": jnp.zeros((d,), jnp.float32)}


def _q(x, fp8: bool):
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32) if fp8 else x


def _rms(x, w, eps):
    import jax.numpy as jnp
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * (1.0 + w)


def recurrence(x, dt, A, B, C, D, every: int):
    """The SSM over (T, H, P) inputs x, (T, H) step sizes dt, (H,) A,
    (T, N) B and C, (H,) D, one token at a time from a zero state; the scan
    is checkpointed every ``every`` steps.  Returns y (T, H, P)."""
    import jax
    import jax.numpy as jnp
    t, h, p = x.shape
    n = B.shape[-1]

    def step(state, inp):
        xt, dtt, bt, ct = inp
        state = (jnp.exp(dtt * A)[:, None, None] * state
                 + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :])
        return state, jnp.einsum("hpn,n->hp", state, ct) + D[:, None] * xt

    @jax.checkpoint
    def block(state, inp):
        return jax.lax.scan(step, state, inp)

    k = t // every
    inp = jax.tree.map(lambda a: a.reshape((k, every) + a.shape[1:]),
                       (x, dt, B, C))
    _, y = jax.lax.scan(block, jnp.zeros((h, p, n), jnp.float32), inp)
    return y.reshape(t, h, p)


def forward(params, tokens, m: dict, fp8: bool = False):
    """Logits (B, S, vocab_size) of the model over (B, S) tokens."""
    import jax
    import jax.numpy as jnp
    d, d_in, h, p, n, w = _dims(m)
    eps, v = m["layer_norm_epsilon"], m["vocab_size"]
    table = _q(params["embed"], fp8)
    x = table[tokens]
    s = tokens.shape[1]

    def mixer(y, blk):
        zxbcdt = _q(y, fp8) @ _q(blk["w_in"], fp8)
        z, xbc, dt = (zxbcdt[..., :d_in], zxbcdt[..., d_in:2 * d_in + 2 * n],
                      zxbcdt[..., 2 * d_in + 2 * n:])
        pad = jnp.pad(xbc, ((0, 0), (w - 1, 0), (0, 0)))
        cw = _q(blk["conv_w"], fp8)
        conv = sum(pad[:, i:i + s] * cw[i] for i in range(w))
        xbc = jax.nn.silu(conv + blk["conv_b"].astype(jnp.float32))
        xs, bm, cm = (xbc[..., :d_in], xbc[..., d_in:d_in + n],
                      xbc[..., d_in + n:])
        dt = jax.nn.softplus(dt + blk["dt_bias"])
        A = -jnp.exp(blk["A_log"])
        run = functools.partial(recurrence, A=A, D=blk["D"],
                                every=m["chunk_size"])
        ys = jax.vmap(lambda a, b, c, e: run(a, b, B=c, C=e))(
            _q(xs, fp8).reshape(-1, s, h, p), dt, _q(bm, fp8), _q(cm, fp8))
        g = _rms(ys.reshape(-1, s, d_in) * jax.nn.silu(z), blk["norm"], eps)
        return _q(g, fp8) @ _q(blk["w_out"], fp8)

    def layer(x, blk):
        return x + mixer(_rms(x, blk["ln1"], eps), blk), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    x = _rms(x, params["final_norm"], eps)
    return jnp.einsum("bsd,vd->bsv", _q(x, fp8), table[:v])


def loss_fn(params, tokens, labels, m: dict, fp8: bool = False):
    """Mean next-token cross-entropy of the model over (B, S) tokens."""
    import jax
    import jax.numpy as jnp
    logp = jax.nn.log_softmax(forward(params, tokens, m, fp8), -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))


def _replay():
    """``ref_lm``'s replay (Eq. 4 over the pulled models, one Adam step on
    each active worker's batch), loaded from its file as a module of its
    own whose model is this one."""
    path = pathlib.Path(__file__).resolve().parent / "ref_lm.py"
    spec = importlib.util.spec_from_file_location("ref_lm_of_ssm", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.init_params, mod.loss_fn = init_params, loss_fn
    return mod


_REPLAY = _replay()
named = _REPLAY.named
model_plane = _REPLAY.model_plane
