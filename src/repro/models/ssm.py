"""Mamba-2 (SSD — state-space duality) block.

Training/prefill uses the chunked dual form: quadratic attention-like matmuls
inside chunks (MXU-friendly) + an inter-chunk ``lax.scan`` over the running
state.  Decode is the O(1)/token recurrent update.  Single B/C group
(n_groups = 1), scalar-per-head A, depthwise causal conv over [x, B, C].
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, SSMConfig
from repro.kernels import ops as K
from repro.sharding.rules import constrain

Params = Dict[str, Any]


def _dims(cfg: ModelConfig):
    s = cfg.ssm or SSMConfig()
    d_in = s.expand * cfg.d_model
    n_heads = d_in // s.head_dim
    return s, d_in, n_heads


def init_ssm(key, cfg: ModelConfig):
    s, d_in, H = _dims(cfg)
    d = cfg.d_model
    kin, kout, kconv, kdt = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    conv_dim = d_in + 2 * s.d_state
    p = {
        # fused in_proj -> [z (d_in), x (d_in), B (N), C (N), dt (H)]
        "w_in": (jax.random.normal(kin, (d, 2 * d_in + 2 * s.d_state + H), jnp.float32)
                 * d ** -0.5).astype(dt),
        "conv_w": (jax.random.normal(kconv, (s.conv_width, conv_dim), jnp.float32)
                   * s.conv_width ** -0.5).astype(dt),
        "conv_b": jnp.zeros((conv_dim,), dt),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H, dtype=jnp.float32)),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32) + jnp.log(jnp.expm1(0.01)),
        "norm": jnp.zeros((d_in,), jnp.float32),
        "w_out": (jax.random.normal(kout, (d_in, d), jnp.float32) * d_in ** -0.5).astype(dt),
    }
    ax = {
        "w_in": ("embed", "ssm_inner"),
        "conv_w": (None, "ssm_inner"),
        "conv_b": ("ssm_inner",),
        "A_log": (None,),
        "D": (None,),
        "dt_bias": (None,),
        "norm": ("ssm_inner",),
        "w_out": ("ssm_inner", "embed"),
    }
    return p, ax


def _split_in(cfg: ModelConfig, h: jnp.ndarray):
    s, d_in, H = _dims(cfg)
    z = h[..., :d_in]
    x = h[..., d_in:2 * d_in]
    B = h[..., 2 * d_in:2 * d_in + s.d_state]
    C = h[..., 2 * d_in + s.d_state:2 * d_in + 2 * s.d_state]
    dt = h[..., 2 * d_in + 2 * s.d_state:]
    return z, x, B, C, dt


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                 tail: Optional[jnp.ndarray] = None):
    """Depthwise causal conv along time.  x (B,S,C), w (W,C).

    If `tail` (B, W-1, C) is given (decode), it is prepended instead of zeros
    and the new tail is returned.
    """
    W = w.shape[0]
    if tail is None:
        pad = jnp.zeros((x.shape[0], W - 1, x.shape[2]), x.dtype)
    else:
        pad = tail.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    out = sum(xp[:, i:i + x.shape[1], :] * w[i][None, None, :] for i in range(W))
    new_tail = xp[:, -(W - 1):, :] if W > 1 else None
    return jax.nn.silu(out + b[None, None, :]), new_tail


def _gated_norm(y: jnp.ndarray, z: jnp.ndarray, scale: jnp.ndarray, eps: float):
    yf = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(jnp.square(yf), axis=-1, keepdims=True)
    return (yf * jax.lax.rsqrt(var + eps) * (1.0 + scale)).astype(y.dtype)


def ssm_forward(cfg: ModelConfig, p: Params, x_res: jnp.ndarray) -> jnp.ndarray:
    """Chunked SSD over a full sequence.  x_res: (B, S, D) -> (B, S, D)."""
    s, d_in, H = _dims(cfg)
    Bsz, S, _ = x_res.shape
    Q = min(s.chunk_size, S)
    assert S % Q == 0, f"seq {S} not divisible by chunk {Q}"
    nc = S // Q
    P_ = s.head_dim

    h = x_res @ p["w_in"]
    z, xin, Bm, Cm, dt = _split_in(cfg, h)
    conv_in = jnp.concatenate([xin, Bm, Cm], axis=-1)
    conv_out, _ = _causal_conv(conv_in, p["conv_w"], p["conv_b"])
    xin, Bm, Cm = (conv_out[..., :d_in], conv_out[..., d_in:d_in + s.d_state],
                   conv_out[..., d_in + s.d_state:])

    A = -jnp.exp(p["A_log"])                                  # (H,) negative
    dtv = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
    xh = xin.reshape(Bsz, S, H, P_).astype(jnp.float32)
    xbar = xh * dtv[..., None]
    loga = (dtv * A).reshape(Bsz, nc, Q, H)
    cum = jnp.cumsum(loga, axis=2)                            # (B,nc,Q,H)

    Bc = Bm.reshape(Bsz, nc, Q, s.d_state).astype(jnp.float32)
    Cc = Cm.reshape(Bsz, nc, Q, s.d_state).astype(jnp.float32)
    xc = xbar.reshape(Bsz, nc, Q, H, P_)

    # ---- intra-chunk (quadratic dual form) ----
    # named scopes ``ssd_intra`` / ``ssd_scan`` let a device trace charge
    # the two halves of SSD separately (chipbench/scopes.py)
    with jax.named_scope("ssd_intra"):
        if cfg.kernels.use_pallas:
            # Pallas ssd_chunk kernel (reference backward).  Kernel layout
            # is head-major (G, H, Q, ·) with G = batch * n_chunks.
            G = Bsz * nc
            y_k = K.ssd_chunk_diff(
                Bc.reshape(G, Q, s.d_state), Cc.reshape(G, Q, s.d_state),
                jnp.transpose(cum.reshape(G, Q, H), (0, 2, 1)),
                jnp.transpose(xc.reshape(G, Q, H, P_), (0, 2, 1, 3)),
                cfg.kernels)
            y_intra = jnp.transpose(y_k, (0, 2, 1, 3)).reshape(
                Bsz, nc, Q, H, P_)
        else:
            cb = jnp.einsum("bcqn,bckn->bcqk", Cc, Bc)        # (B,nc,Q,Q)
            decay = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (B,nc,Q,Q,H)
            causal = jnp.tril(jnp.ones((Q, Q), bool))
            # masked before the exp (kernels/ref.py::ssd_chunk_ref says why)
            L = jnp.exp(jnp.where(causal[None, None, :, :, None], decay,
                                  -jnp.inf))
            y_intra = jnp.einsum("bcqk,bcqkh,bckhp->bcqhp", cb, L, xc)

    # ---- chunk boundary states + inter-chunk scan ----
    with jax.named_scope("ssd_scan"):
        decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)       # (B,nc,Q,H)
        chunk_state = jnp.einsum("bckn,bckh,bckhp->bchpn", Bc, decay_to_end,
                                 xc)
        chunk_decay = jnp.exp(cum[:, :, -1, :])               # (B,nc,H)

        def scan_fn(carry, inp):
            cs, cd = inp                                      # (B,H,P,N), (B,H)
            new = carry * cd[:, :, None, None] + cs
            return new, carry                                 # emit state BEFORE this chunk

        init = jnp.zeros((Bsz, H, P_, s.d_state), jnp.float32)
        _, prev_states = jax.lax.scan(
            scan_fn, init,
            (jnp.moveaxis(chunk_state, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
        prev_states = jnp.moveaxis(prev_states, 0, 1)         # (B,nc,H,P,N)

        y_inter = jnp.einsum("bcqn,bcqh,bchpn->bcqhp", Cc, jnp.exp(cum),
                             prev_states)
    y = (y_intra + y_inter).reshape(Bsz, S, H, P_) + p["D"][None, None, :, None] * xh
    y = y.reshape(Bsz, S, d_in)
    y = _gated_norm(y, z, p["norm"], cfg.norm_eps)
    out = (y @ p["w_out"]).astype(x_res.dtype)
    return constrain(out, ("data", None, "embed_act"))


def init_ssm_cache(cfg: ModelConfig, batch: int, dtype) -> Params:
    s, d_in, H = _dims(cfg)
    return {
        "state": jnp.zeros((batch, H, s.head_dim, s.d_state), jnp.float32),
        "conv_tail": jnp.zeros((batch, s.conv_width - 1, d_in + 2 * s.d_state), dtype),
    }


def ssm_decode_step(cfg: ModelConfig, p: Params, cache: Params,
                    x_res: jnp.ndarray) -> Tuple[jnp.ndarray, Params]:
    """One recurrent step.  x_res: (B, 1, D)."""
    s, d_in, H = _dims(cfg)
    Bsz = x_res.shape[0]
    P_ = s.head_dim

    h = x_res @ p["w_in"]
    z, xin, Bm, Cm, dt = _split_in(cfg, h)
    conv_in = jnp.concatenate([xin, Bm, Cm], axis=-1)
    conv_out, new_tail = _causal_conv(conv_in, p["conv_w"], p["conv_b"],
                                      tail=cache["conv_tail"])
    xin, Bm, Cm = (conv_out[..., :d_in], conv_out[..., d_in:d_in + s.d_state],
                   conv_out[..., d_in + s.d_state:])

    A = -jnp.exp(p["A_log"])
    dtv = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + p["dt_bias"])   # (B,H)
    a = jnp.exp(dtv * A)                                                  # (B,H)
    xh = xin[:, 0].reshape(Bsz, H, P_).astype(jnp.float32)
    Bv = Bm[:, 0].astype(jnp.float32)                                     # (B,N)
    Cv = Cm[:, 0].astype(jnp.float32)
    new_state = (cache["state"] * a[:, :, None, None]
                 + jnp.einsum("bhp,bn,bh->bhpn", xh, Bv, dtv))
    y = jnp.einsum("bn,bhpn->bhp", Cv, new_state) + p["D"][None, :, None] * xh
    y = y.reshape(Bsz, 1, d_in)
    y = _gated_norm(y, z, p["norm"], cfg.norm_eps)
    out = (y @ p["w_out"]).astype(x_res.dtype)
    return out, {"state": new_state, "conv_tail": new_tail}
