"""Unified decoder-only model covering the dense / moe / ssm / hybrid / vlm
families.  (Encoder-decoder lives in ``encdec.py`` and reuses these helpers.)

Layer-stacking: the body is organized as `prelude` (explicit leading layers,
e.g. kimi-k2's first dense layer), `blocks` (the repeating pattern period,
stacked with a leading group axis and driven by ``lax.scan`` — essential to
keep XLA compile time sane at 61-64 layers), and `coda` (remainder layers when
n_layers isn't a multiple of the pattern period, e.g. recurrentgemma's 26 = 8*3+2).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as M
from repro.models import rglru as R
from repro.models import ssm as S
from repro.sharding.rules import constrain

Params = Dict[str, Any]


# --------------------------------------------------------------------------- #
# structure
# --------------------------------------------------------------------------- #


def pattern(cfg: ModelConfig) -> Tuple[str, ...]:
    if cfg.family == "ssm":
        return ("ssm",)
    if cfg.family == "hybrid":
        return tuple(cfg.block_pattern or ("rglru", "rglru", "attn_local"))
    if cfg.attn_pattern == "local_global":
        return ("attn_local", "attn")
    if cfg.attn_pattern == "local":
        return ("attn_local",)
    return ("attn",)


def structure(cfg: ModelConfig) -> Tuple[int, int, int]:
    """(n_prelude, n_groups, n_coda) layers; prelude covers moe.first_dense."""
    per = len(pattern(cfg))
    n_pre = cfg.moe.first_dense_layers if cfg.moe else 0
    rest = cfg.n_layers - n_pre
    return n_pre, rest // per, rest % per


def _layer_kinds(cfg: ModelConfig):
    """kind of each explicit (non-block) layer, by absolute index."""
    return [cfg.layer_kind(i) for i in range(cfg.n_layers)]


# --------------------------------------------------------------------------- #
# single-layer init / apply
# --------------------------------------------------------------------------- #


def init_layer(key, cfg: ModelConfig, kind: str, layer_idx: int, cross: bool = False):
    keys = jax.random.split(key, 6)
    p: Params = {}
    ax: Params = {}
    p["ln1"], ax["ln1"] = L.init_rmsnorm(cfg)
    if kind in ("attn", "attn_local"):
        p["attn"], ax["attn"] = L.init_attention(keys[0], cfg)
    elif kind == "rglru":
        p["rglru"], ax["rglru"] = R.init_rglru(keys[0], cfg)
    elif kind == "ssm":
        p["ssm"], ax["ssm"] = S.init_ssm(keys[0], cfg)
    if cross:
        p["ln_x"], ax["ln_x"] = L.init_rmsnorm(cfg)
        p["xattn"], ax["xattn"] = L.init_attention(keys[1], cfg, cross=True)
    has_ffn = cfg.d_ff > 0
    if has_ffn:
        p["ln2"], ax["ln2"] = L.init_rmsnorm(cfg)
        if cfg.is_moe_layer(layer_idx):
            p["moe"], ax["moe"] = M.init_moe(keys[2], cfg)
        else:
            p["mlp"], ax["mlp"] = L.init_mlp(keys[3], cfg)
    if cfg.post_norm:
        p["ln1_post"], ax["ln1_post"] = L.init_rmsnorm(cfg)
        if has_ffn:
            p["ln2_post"], ax["ln2_post"] = L.init_rmsnorm(cfg)
    return p, ax


def _attn_spec(cfg: ModelConfig, kind: str, prefix_len: int) -> L.AttnSpec:
    return L.AttnSpec(
        causal=True,
        window=cfg.window_size if kind == "attn_local" else None,
        softcap=cfg.attn_logit_softcap,
        prefix_len=prefix_len,
    )


def apply_layer(cfg: ModelConfig, p: Params, kind: str, x: jnp.ndarray,
                positions: jnp.ndarray, prefix_len: int = 0,
                enc_out: Optional[jnp.ndarray] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence (train/prefill) layer.  Returns (x, moe_aux)."""
    aux = jnp.zeros((), jnp.float32)
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind in ("attn", "attn_local"):
        y, _ = L.multihead_attention(cfg, p["attn"], h, _attn_spec(cfg, kind, prefix_len),
                                     positions)
    elif kind == "rglru":
        y = R.rglru_forward(cfg, p["rglru"], h)
    else:
        y = S.ssm_forward(cfg, p["ssm"], h)
    if cfg.post_norm:
        y = L.rms_norm(y, p["ln1_post"], cfg.norm_eps)
    x = x + y
    if "xattn" in p:
        h = L.rms_norm(x, p["ln_x"], cfg.norm_eps)
        y, _ = L.multihead_attention(cfg, p["xattn"], h,
                                     L.AttnSpec(causal=False), positions, kv_x=enc_out)
        x = x + y
    if "mlp" in p or "moe" in p:
        h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
        if "moe" in p:
            y, aux = M.moe_ffn(cfg, p["moe"], h)
        else:
            y = L.mlp(cfg, p["mlp"], h)
        if cfg.post_norm:
            y = L.rms_norm(y, p["ln2_post"], cfg.norm_eps)
        x = x + y
    return x, aux


def decode_layer(cfg: ModelConfig, p: Params, kind: str, cache: Params,
                 x: jnp.ndarray, pos: jnp.ndarray,
                 enc_cache: Optional[Params] = None) -> Tuple[jnp.ndarray, Params]:
    """One-token decode.  x: (B,1,D); cache per layer kind.  Returns (x, cache)."""
    positions = jnp.broadcast_to(pos[None, None], (x.shape[0], 1)).astype(jnp.int32)
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind in ("attn", "attn_local"):
        y, new_cache = _ring_attention_step(cfg, p["attn"], h, cache, pos,
                                            _attn_spec(cfg, kind, 0))
    elif kind == "rglru":
        y, new_cache = R.rglru_decode_step(cfg, p["rglru"], cache, h)
    else:
        y, new_cache = S.ssm_decode_step(cfg, p["ssm"], cache, h)
    if cfg.post_norm:
        y = L.rms_norm(y, p["ln1_post"], cfg.norm_eps)
    x = x + y
    if "xattn" in p:
        h = L.rms_norm(x, p["ln_x"], cfg.norm_eps)
        hd = cfg.resolved_head_dim
        q = jnp.einsum("bsd,dhk->bshk", h, p["xattn"]["wq"])
        B = q.shape[0]
        qg = q.reshape(B, 1, cfg.n_kv_heads, cfg.q_per_kv, hd)
        sc = jnp.einsum("bsngk,btnk->bnsgt", qg, enc_cache["k"]).astype(jnp.float32)
        pr = jax.nn.softmax(sc * hd ** -0.5, axis=-1).astype(enc_cache["v"].dtype)
        o = jnp.einsum("bnsgt,btnk->bsngk", pr, enc_cache["v"]).reshape(B, 1, cfg.n_heads, hd)
        x = x + jnp.einsum("bshk,hkd->bsd", o, p["xattn"]["wo"])
    if "mlp" in p or "moe" in p:
        h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
        if "moe" in p:
            y, _ = M.moe_ffn(cfg, p["moe"], h)
        else:
            y = L.mlp(cfg, p["mlp"], h)
        if cfg.post_norm:
            y = L.rms_norm(y, p["ln2_post"], cfg.norm_eps)
        x = x + y
    return x, new_cache


def _ring_attention_step(cfg: ModelConfig, p: Params, x: jnp.ndarray, cache: Params,
                         pos: jnp.ndarray, spec: L.AttnSpec):
    """Decode attention against a (possibly ring-buffered) KV cache.

    cache: {k (B,W,K,hd), v, k_pos (B,W) int32 (absolute; -1 = empty)}.
    For full-attention layers W == max_len and slot == pos; for local layers
    W == window and slot == pos % W.
    """
    hd = cfg.resolved_head_dim
    B = x.shape[0]
    W = cache["k"].shape[1]
    positions = jnp.broadcast_to(pos[None, None], (B, 1)).astype(jnp.int32)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k_new = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    k_new = L.apply_rope(k_new, positions, cfg.rope_theta)
    v_new = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    slot = jax.lax.rem(pos, W)
    k = jax.lax.dynamic_update_slice(cache["k"], k_new.astype(cache["k"].dtype),
                                     (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new.astype(cache["v"].dtype),
                                     (0, slot, 0, 0))
    k_pos = jax.lax.dynamic_update_slice(
        cache["k_pos"], jnp.broadcast_to(pos[None, None], (B, 1)).astype(jnp.int32),
        (0, slot))
    mask = (k_pos >= 0) & (k_pos <= pos)
    if spec.window is not None:
        mask = mask & ((pos - k_pos) < spec.window)
    qg = q.reshape(B, 1, cfg.n_kv_heads, cfg.q_per_kv, hd)
    scores = jnp.einsum("bsngk,btnk->bnsgt", qg, k).astype(jnp.float32) * hd ** -0.5
    if spec.softcap is not None:
        scores = jnp.tanh(scores / spec.softcap) * spec.softcap
    scores = jnp.where(mask[:, None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bnsgt,btnk->bsngk", probs, v).reshape(B, 1, cfg.n_heads, hd)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, {"k": k, "v": v, "k_pos": k_pos}


def _layer_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int, dtype) -> Params:
    if kind in ("attn", "attn_local"):
        W = min(cfg.window_size, max_len) if kind == "attn_local" else max_len
        hd = cfg.resolved_head_dim
        return {
            "k": jnp.zeros((batch, W, cfg.n_kv_heads, hd), dtype),
            "v": jnp.zeros((batch, W, cfg.n_kv_heads, hd), dtype),
            "k_pos": jnp.full((batch, W), -1, jnp.int32),
        }
    if kind == "rglru":
        return R.init_rglru_cache(cfg, batch, dtype)
    return S.init_ssm_cache(cfg, batch, dtype)


# --------------------------------------------------------------------------- #
# whole-model init
# --------------------------------------------------------------------------- #


def init_decoder(key, cfg: ModelConfig) -> Tuple[Params, Params]:
    n_pre, n_grp, n_coda = structure(cfg)
    per = pattern(cfg)
    k_embed, k_pre, k_blocks, k_coda = jax.random.split(key, 4)
    p: Params = {}
    ax: Params = {}
    p["embed"], ax["embed"] = L.init_embedding(k_embed, cfg)

    pre, pre_ax = [], []
    for i, kk in enumerate(jax.random.split(k_pre, max(n_pre, 1))[:n_pre]):
        lp, la = init_layer(kk, cfg, cfg.layer_kind(i), i)
        pre.append(lp), pre_ax.append(la)
    p["prelude"], ax["prelude"] = pre, pre_ax

    # stacked pattern blocks: init one group then vmap-stack over group keys
    def init_group(k):
        ks = jax.random.split(k, len(per))
        gp = {}
        for j, kind in enumerate(per):
            lp, _ = init_layer(ks[j], cfg, kind, n_pre + j)
            gp[f"p{j}"] = lp
        return gp

    if n_grp > 0:
        gkeys = jax.random.split(k_blocks, n_grp)
        p["blocks"] = jax.vmap(init_group)(gkeys)
        one = init_group(gkeys[0])
        _, gax = jax.tree.flatten(one)
        gp_ax = {}
        for j, kind in enumerate(per):
            _, la = init_layer(gkeys[0], cfg, kind, n_pre + j)
            gp_ax[f"p{j}"] = jax.tree.map(
                lambda t: ("stack",) + t,
                la, is_leaf=lambda t: isinstance(t, tuple) and all(
                    isinstance(a, (str, type(None))) for a in t))
        ax["blocks"] = gp_ax
    else:
        p["blocks"], ax["blocks"] = None, None

    coda, coda_ax = [], []
    base = n_pre + n_grp * len(per)
    for j, kk in enumerate(jax.random.split(k_coda, max(n_coda, 1))[:n_coda]):
        li = base + j
        lp, la = init_layer(kk, cfg, cfg.layer_kind(li), li)
        coda.append(lp), coda_ax.append(la)
    p["coda"], ax["coda"] = coda, coda_ax

    p["final_norm"], ax["final_norm"] = L.init_rmsnorm(cfg)
    return p, ax


# --------------------------------------------------------------------------- #
# forward (train / prefill)
# --------------------------------------------------------------------------- #


def forward(cfg: ModelConfig, params: Params, tokens: jnp.ndarray,
            prefix_embeds: Optional[jnp.ndarray] = None,
            remat: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens (B, S_text) [+ prefix embeds (B, P, D) for vlm/audio-prefix].

    Returns (logits (B, S_total, V), moe_aux).
    """
    n_pre, n_grp, n_coda = structure(cfg)
    per = pattern(cfg)
    x = params["embed"]["table"][tokens].astype(jnp.dtype(cfg.dtype))
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    prefix_len = 0
    if prefix_embeds is not None:
        prefix_len = prefix_embeds.shape[1]
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    B, Stot = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(Stot, dtype=jnp.int32)[None, :], (B, Stot))
    x = constrain(x, ("data", None, "embed_act"))
    aux = jnp.zeros((), jnp.float32)

    for i, lp in enumerate(params["prelude"]):
        x, a = apply_layer(cfg, lp, cfg.layer_kind(i), x, positions, prefix_len)
        aux = aux + a

    if n_grp > 0:
        def block_fn(carry, gp):
            xc, auxc = carry
            for j, kind in enumerate(per):
                xc, a = apply_layer(cfg, gp[f"p{j}"], kind, xc, positions, prefix_len)
                auxc = auxc + a
            return (xc, auxc), None

        if remat:
            block_fn = jax.checkpoint(block_fn)
        (x, aux), _ = jax.lax.scan(block_fn, (x, aux), params["blocks"])

    base = n_pre + n_grp * len(per)
    for j, lp in enumerate(params["coda"]):
        x, a = apply_layer(cfg, lp, cfg.layer_kind(base + j), x, positions, prefix_len)
        aux = aux + a

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = L.lm_logits(cfg, params["embed"]["table"], x)
    return logits, aux


# --------------------------------------------------------------------------- #
# decode
# --------------------------------------------------------------------------- #


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    """Decode cache pytree (local-attention layers get ring buffers)."""
    dtype = jnp.dtype(cfg.dtype)
    n_pre, n_grp, n_coda = structure(cfg)
    per = pattern(cfg)
    cache: Params = {"pos": jnp.zeros((), jnp.int32)}
    cache["prelude"] = [
        _layer_cache(cfg, cfg.layer_kind(i), batch, max_len, dtype) for i in range(n_pre)]
    if n_grp > 0:
        one = {f"p{j}": _layer_cache(cfg, kind, batch, max_len, dtype)
               for j, kind in enumerate(per)}
        cache["blocks"] = jax.tree.map(
            lambda t: jnp.broadcast_to(t[None], (n_grp,) + t.shape).copy(), one)
    else:
        cache["blocks"] = None
    base = n_pre + n_grp * len(per)
    cache["coda"] = [
        _layer_cache(cfg, cfg.layer_kind(base + j), batch, max_len, dtype)
        for j in range(n_coda)]
    return cache


def decode_step(cfg: ModelConfig, params: Params, cache: Params,
                token: jnp.ndarray) -> Tuple[jnp.ndarray, Params]:
    """token (B, 1) int32 -> (logits (B, 1, V), new cache)."""
    n_pre, n_grp, n_coda = structure(cfg)
    per = pattern(cfg)
    pos = cache["pos"]
    x = params["embed"]["table"][token].astype(jnp.dtype(cfg.dtype))
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    new_cache: Params = {"pos": pos + 1, "prelude": [], "coda": []}

    for i, lp in enumerate(params["prelude"]):
        x, c = decode_layer(cfg, lp, cfg.layer_kind(i), cache["prelude"][i], x, pos)
        new_cache["prelude"].append(c)

    if n_grp > 0:
        def block_fn(x_in, scanned):
            gp, gc = scanned
            new_gc = {}
            for j, kind in enumerate(per):
                x_in, new_gc[f"p{j}"] = decode_layer(cfg, gp[f"p{j}"], kind,
                                                     gc[f"p{j}"], x_in, pos)
            return x_in, new_gc

        x, new_blocks = jax.lax.scan(block_fn, x, (params["blocks"], cache["blocks"]))
        new_cache["blocks"] = new_blocks
    else:
        new_cache["blocks"] = None

    base = n_pre + n_grp * len(per)
    for j, lp in enumerate(params["coda"]):
        x, c = decode_layer(cfg, lp, cfg.layer_kind(base + j), cache["coda"][j], x, pos)
        new_cache["coda"].append(c)

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = L.lm_logits(cfg, params["embed"]["table"], x)
    return logits, new_cache


# --------------------------------------------------------------------------- #
# prefill into a decode cache (used by serving examples)
# --------------------------------------------------------------------------- #


def prefill_cache(cfg: ModelConfig, params: Params, cache: Params,
                  tokens: jnp.ndarray) -> Tuple[jnp.ndarray, Params]:
    """Sequentially decode the prompt into the cache (reference path; the
    benchmark prefill uses `forward`).  tokens (B, S0)."""
    def step(c, tok):
        logits, c = decode_step(cfg, params, c, tok[:, None])
        return c, logits[:, 0]

    cache, logits = jax.lax.scan(step, cache, jnp.moveaxis(tokens, 1, 0))
    return jnp.moveaxis(logits, 0, 1), cache
