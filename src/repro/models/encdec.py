"""Encoder-decoder backbone (seamless-m4t text decoder + speech encoder stub).

The modality frontend (mel-spectrogram + conv feature extractor) is a STUB per
the assignment: ``input_specs`` supplies precomputed frame embeddings
(B, F, D).  The encoder is a bidirectional transformer over those frames; the
decoder is a causal transformer with cross-attention.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import transformer as T
from repro.sharding.rules import constrain

Params = Dict[str, Any]

AUDIO_FRAME_RATIO = 4  # frames = seq_len // 4 (stub frontend downsampling)


def init_encdec(key, cfg: ModelConfig) -> Tuple[Params, Params]:
    k_embed, k_enc, k_dec, kf1, kf2 = jax.random.split(key, 5)
    p: Params = {}
    ax: Params = {}
    p["embed"], ax["embed"] = L.init_embedding(k_embed, cfg)

    def init_stack(k, n, cross):
        def one(kk):
            lp, _ = T.init_layer(kk, cfg, "attn", 0, cross=cross)
            return lp
        ks = jax.random.split(k, n)
        stacked = jax.vmap(one)(ks)
        _, la = T.init_layer(ks[0], cfg, "attn", 0, cross=cross)
        la = jax.tree.map(lambda t: ("stack",) + t, la,
                          is_leaf=lambda t: isinstance(t, tuple) and all(
                              isinstance(a, (str, type(None))) for a in t))
        return stacked, la

    p["encoder"], ax["encoder"] = init_stack(k_enc, cfg.n_enc_layers, cross=False)
    p["decoder"], ax["decoder"] = init_stack(k_dec, cfg.n_layers, cross=True)
    p["enc_norm"], ax["enc_norm"] = L.init_rmsnorm(cfg)
    p["final_norm"], ax["final_norm"] = L.init_rmsnorm(cfg)
    return p, ax


def encode(cfg: ModelConfig, params: Params, frames: jnp.ndarray,
           remat: bool = False) -> jnp.ndarray:
    """frames: (B, F, D) stub embeddings -> encoder output (B, F, D)."""
    B, F, _ = frames.shape
    positions = jnp.broadcast_to(jnp.arange(F, dtype=jnp.int32)[None, :], (B, F))
    x = constrain(frames.astype(jnp.dtype(cfg.dtype)), ("data", None, "embed_act"))
    spec = L.AttnSpec(causal=False)

    def layer_fn(xc, lp):
        h = L.rms_norm(xc, lp["ln1"], cfg.norm_eps)
        y, _ = L.multihead_attention(cfg, lp["attn"], h, spec, positions)
        xc = xc + y
        h = L.rms_norm(xc, lp["ln2"], cfg.norm_eps)
        return xc + L.mlp(cfg, lp["mlp"], h), None

    if remat:
        layer_fn = jax.checkpoint(layer_fn)
    x, _ = jax.lax.scan(layer_fn, x, params["encoder"])
    return L.rms_norm(x, params["enc_norm"], cfg.norm_eps)


def forward(cfg: ModelConfig, params: Params, tokens: jnp.ndarray,
            frames: jnp.ndarray, remat: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens (B, S) target text; frames (B, F, D) stub audio embeddings."""
    enc = encode(cfg, params, frames, remat=remat)
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    x = params["embed"]["table"][tokens].astype(jnp.dtype(cfg.dtype))
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    spec = L.AttnSpec(causal=True)

    def layer_fn(xc, lp):
        h = L.rms_norm(xc, lp["ln1"], cfg.norm_eps)
        y, _ = L.multihead_attention(cfg, lp["attn"], h, spec, positions)
        xc = xc + y
        h = L.rms_norm(xc, lp["ln_x"], cfg.norm_eps)
        y, _ = L.multihead_attention(cfg, lp["xattn"], h, L.AttnSpec(causal=False),
                                     positions, kv_x=enc)
        xc = xc + y
        h = L.rms_norm(xc, lp["ln2"], cfg.norm_eps)
        return xc + L.mlp(cfg, lp["mlp"], h), None

    if remat:
        layer_fn = jax.checkpoint(layer_fn)
    x, _ = jax.lax.scan(layer_fn, x, params["decoder"])
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return L.lm_logits(cfg, params["embed"]["table"], x), jnp.zeros((), jnp.float32)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, n_frames: int) -> Params:
    """Self-attn KV caches + cross-attn (encoder) KV caches for all dec layers."""
    dtype = jnp.dtype(cfg.dtype)
    hd = cfg.resolved_head_dim
    n = cfg.n_layers
    return {
        "pos": jnp.zeros((), jnp.int32),
        "self": {
            "k": jnp.zeros((n, batch, max_len, cfg.n_kv_heads, hd), dtype),
            "v": jnp.zeros((n, batch, max_len, cfg.n_kv_heads, hd), dtype),
            "k_pos": jnp.full((n, batch, max_len), -1, jnp.int32),
        },
        "cross": {
            "k": jnp.zeros((n, batch, n_frames, cfg.n_kv_heads, hd), dtype),
            "v": jnp.zeros((n, batch, n_frames, cfg.n_kv_heads, hd), dtype),
        },
    }


def fill_cross_cache(cfg: ModelConfig, params: Params, cache: Params,
                     frames: jnp.ndarray) -> Params:
    """Run the encoder once and cache per-decoder-layer cross-attn K/V."""
    enc = encode(cfg, params, frames)

    def per_layer(lp):
        k = jnp.einsum("bsd,dhk->bshk", enc, lp["xattn"]["wk"])
        v = jnp.einsum("bsd,dhk->bshk", enc, lp["xattn"]["wv"])
        return k.astype(jnp.dtype(cfg.dtype)), v.astype(jnp.dtype(cfg.dtype))

    k, v = jax.vmap(per_layer)(params["decoder"])
    return {**cache, "cross": {"k": k, "v": v}}


def decode_step(cfg: ModelConfig, params: Params, cache: Params,
                token: jnp.ndarray) -> Tuple[jnp.ndarray, Params]:
    """token (B, 1) -> (logits, new cache). Cross K/V must be pre-filled."""
    pos = cache["pos"]
    x = params["embed"]["table"][token].astype(jnp.dtype(cfg.dtype))
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)

    def layer_fn(x_in, scanned):
        lp, sk, sv, skp, ck, cv = scanned
        h = L.rms_norm(x_in, lp["ln1"], cfg.norm_eps)
        y, new_c = T._ring_attention_step(cfg, lp["attn"], h,
                                          {"k": sk, "v": sv, "k_pos": skp}, pos,
                                          L.AttnSpec(causal=True))
        x_in = x_in + y
        x_in, _ = _cross_step(cfg, lp, x_in, ck, cv)
        h = L.rms_norm(x_in, lp["ln2"], cfg.norm_eps)
        x_in = x_in + L.mlp(cfg, lp["mlp"], h)
        return x_in, (new_c["k"], new_c["v"], new_c["k_pos"])

    x, (nk, nv, nkp) = jax.lax.scan(
        layer_fn, x,
        (params["decoder"], cache["self"]["k"], cache["self"]["v"],
         cache["self"]["k_pos"], cache["cross"]["k"], cache["cross"]["v"]))
    new_cache = {"pos": pos + 1,
                 "self": {"k": nk, "v": nv, "k_pos": nkp},
                 "cross": cache["cross"]}
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return L.lm_logits(cfg, params["embed"]["table"], x), new_cache


def _cross_step(cfg: ModelConfig, lp: Params, x: jnp.ndarray, ck, cv):
    hd = cfg.resolved_head_dim
    B = x.shape[0]
    h = L.rms_norm(x, lp["ln_x"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, lp["xattn"]["wq"])
    qg = q.reshape(B, 1, cfg.n_kv_heads, cfg.q_per_kv, hd)
    sc = jnp.einsum("bsngk,btnk->bnsgt", qg, ck).astype(jnp.float32) * hd ** -0.5
    pr = jax.nn.softmax(sc, axis=-1).astype(cv.dtype)
    o = jnp.einsum("bnsgt,btnk->bsngk", pr, cv).reshape(B, 1, cfg.n_heads, hd)
    return x + jnp.einsum("bshk,hkd->bsd", o, lp["xattn"]["wo"]), None
