"""Plain reference of one DySTop LM federation: the control plane of
``ref_sim`` and a decoder-only LM in straightforward ``jax.numpy``.

Written from the configuration alone; it imports nothing of the program
under test.  The model is the configuration's decoder: token embedding
scaled by sqrt(hidden_size), pre-norm blocks of RMS norm (weights stored as
offsets from 1), grouped-query causal attention with rotary positions, a
SiLU-gated MLP, a final RMS norm and the tied output head; mean token
cross-entropy; Adam.  Weights are drawn from the seed by the configuration's
``init`` recipe, stored in bfloat16 (norms in float32) as the configuration
states; the reference computes every activation in float32 at ``highest``
matmul precision.

The control (``fp8=True``) rounds every weight and every matmul input to
float8 e4m3 before use, the precision below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
from typing import Dict, List

import numpy as np


def init_params(seed: int, m: dict):
    """w_0: normal(0, 1/fan_in) matrices in bfloat16, zero norm offsets.

    Key recipe: split(PRNGKey(seed), 4) -> embed, (unused), blocks, (unused);
    one key per block; per block split(., 6): [0] attention (split 4: q, k,
    v, o), [3] MLP (split 3: gate, up, down)."""
    import jax
    import jax.numpy as jnp
    d, h, kv, hd = (m["hidden_size"], m["num_attention_heads"],
                    m["num_key_value_heads"], m["head_dim"])
    f, v, n = m["intermediate_size"], m["vocab_size"], m["num_hidden_layers"]
    bf = jnp.bfloat16

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * fan_in ** -0.5).astype(bf)

    k_embed, _, k_blocks, _ = jax.random.split(jax.random.PRNGKey(seed), 4)
    blocks = []
    for gk in jax.random.split(k_blocks, n):
        lk = jax.random.split(jax.random.split(gk, 1)[0], 6)
        kq, kk, kvv, ko = jax.random.split(lk[0], 4)
        kg, ku, kd = jax.random.split(lk[3], 3)
        blocks.append({
            "ln1": jnp.zeros((d,), jnp.float32),
            "wq": dense(kq, (d, h, hd), d), "wk": dense(kk, (d, kv, hd), d),
            "wv": dense(kvv, (d, kv, hd), d), "wo": dense(ko, (h, hd, d), h * hd),
            "ln2": jnp.zeros((d,), jnp.float32),
            "w_gate": dense(kg, (d, f), d), "w_up": dense(ku, (d, f), d),
            "w_down": dense(kd, (f, d), f)})
    stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *blocks)
    return {"embed": dense(k_embed, (v, d), d), "blocks": stacked,
            "final_norm": jnp.zeros((d,), jnp.float32)}


def _q(x, fp8: bool):
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32) if fp8 else x


def _rms(x, w, eps):
    import jax.numpy as jnp
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * (1.0 + w)


def _rope(x, theta):
    import jax.numpy as jnp
    s, half = x.shape[1], x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def loss_fn(params, tokens, labels, m: dict, fp8: bool = False):
    """Mean next-token cross-entropy of the decoder over (B, S) tokens."""
    import jax
    import jax.numpy as jnp
    d, h, kv = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"]
    hd, eps = m["head_dim"], m["rms_norm_eps"]
    g = h // kv
    table = _q(params["embed"], fp8)
    x = table[tokens] * (d ** 0.5)
    s = tokens.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def block(x, p):
        y = _rms(x, p["ln1"], eps)
        q = _rope(jnp.einsum("bsd,dhk->bshk", _q(y, fp8), _q(p["wq"], fp8)),
                  m["rope_theta"])
        k = _rope(jnp.einsum("bsd,dhk->bshk", _q(y, fp8), _q(p["wk"], fp8)),
                  m["rope_theta"])
        v = jnp.einsum("bsd,dhk->bshk", _q(y, fp8), _q(p["wv"], fp8))
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
        sc = jnp.einsum("bqhk,bthk->bhqt", _q(q, fp8), _q(k, fp8)) * hd ** -0.5
        pr = jax.nn.softmax(jnp.where(causal, sc, -1e30), -1)
        o = jnp.einsum("bhqt,bthk->bqhk", _q(pr, fp8), _q(v, fp8))
        x = x + jnp.einsum("bqhk,hkd->bqd", _q(o, fp8), _q(p["wo"], fp8))
        y = _q(_rms(x, p["ln2"], eps), fp8)
        hmid = (jax.nn.silu(y @ _q(p["w_gate"], fp8)) * (y @ _q(p["w_up"], fp8)))
        return x + _q(hmid, fp8) @ _q(p["w_down"], fp8), None

    x, _ = jax.lax.scan(block, x, params["blocks"])
    x = _rms(x, params["final_norm"], eps)
    logits = jnp.einsum("bsd,vd->bsv", _q(x, fp8), table)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))


@functools.lru_cache(maxsize=None)
def _step_fn(m_items: tuple, lr: float, b1: float, b2: float, eps: float,
             fp8: bool):
    import jax
    import jax.numpy as jnp
    m = dict(m_items)

    def step(params, mu, nu, count, tokens, labels):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, labels, m,
                                                  fp8)
        count = count + 1
        c1 = 1.0 - b1 ** count.astype(jnp.float32)
        c2 = 1.0 - b2 ** count.astype(jnp.float32)

        def upd(gr, a, b, p):
            gr = gr.astype(jnp.float32)
            a = b1 * a + (1 - b1) * gr
            b = b2 * b + (1 - b2) * gr * gr
            new = p.astype(jnp.float32) - lr * (a / c1) / (jnp.sqrt(b / c2) + eps)
            return new.astype(p.dtype), a, b

        out = jax.tree.map(upd, grads, mu, nu, params)
        pick = lambda i: jax.tree.map(lambda t: t[i], out,  # noqa: E731
                                      is_leaf=lambda t: isinstance(t, tuple))
        return pick(0), pick(1), pick(2), count, loss

    return jax.jit(step)


def _mix(rows, weights):
    """Eq. 4 for one worker: the weighted sum of the pulled models, in f32,
    stored back at the models' own dtypes."""
    import jax
    import jax.numpy as jnp

    def one(*leaves):
        acc = sum(w * l.astype(jnp.float32) for w, l in zip(weights, leaves))
        return acc.astype(leaves[0].dtype)

    return jax.tree.map(one, *rows)


def named(tree) -> Dict[str, object]:
    """The model's leaves by name: ``embed``, ``final_norm`` and
    ``blocks.<weight>`` (each stacked over the layers)."""
    out = {"embed": tree["embed"], "final_norm": tree["final_norm"]}
    out.update({f"blocks.{k}": v for k, v in tree["blocks"].items()})
    return out


def fleet_norms(trees, base=None) -> Dict[str, float]:
    """Each leaf's norm over the whole fleet: sqrt of the sum over workers
    of ||tree_i - base||^2 (``base`` None: of ||tree_i||^2), in f32."""
    import jax.numpy as jnp
    b = named(base) if base is not None else None
    acc: Dict[str, float] = {}
    for t in trees:
        for k, v in named(t).items():
            d = v.astype(jnp.float32)
            if b is not None:
                d = d - b[k].astype(jnp.float32)
            acc[k] = acc.get(k, 0.0) + float(jnp.sum(d * d))
    return {k: float(np.sqrt(v)) for k, v in acc.items()}


def model_plane(ctrl: Dict[str, list], n_rounds: int, m: dict, opt: dict,
                seed: int, batches: List[dict], fp8: bool = False,
                fleet: bool = False) -> Dict[str, object]:
    """Replay ``n_rounds`` rounds: mix the active workers' models over their
    pulled neighbours (Eq. 4), then one Adam step on each active worker's
    batch (Eq. 5).  Returns the mean loss of each round's active workers
    (``round_loss``); with ``fleet`` also each leaf's change from w_0 over
    the fleet (``change``) and each leaf's Adam first moment over the fleet
    (``moment``, the gradient as the optimizer holds it)."""
    import jax
    import jax.numpy as jnp
    n = len(ctrl["active"][0])
    m_items = tuple(sorted(m.items()))
    out: Dict[str, object] = {"round_loss": []}
    with jax.default_matmul_precision("highest"):
        p0 = init_params(seed, m)
        zeros = jax.tree.map(lambda l: jnp.zeros(l.shape, jnp.float32), p0)
        params = [p0] * n
        mu, nu = [zeros] * n, [zeros] * n
        count = [jnp.zeros((), jnp.int32)] * n
        step = _step_fn(m_items, opt["lr"], opt["b1"], opt["b2"], opt["eps"],
                        fp8)
        for t in range(n_rounds):
            W, active = ctrl["W"][t], ctrl["active"][t]
            rows = [i for i in range(n) if not (W[i] == np.eye(n)[i]).all()]
            mixed = {i: _mix([params[j] for j in range(n) if W[i, j] != 0],
                             [float(W[i, j]) for j in range(n) if W[i, j] != 0])
                     for i in rows}
            params = [mixed.get(i, params[i]) for i in range(n)]
            losses = []
            for i in np.flatnonzero(active):
                tok = jnp.asarray(batches[t]["tokens"][i])
                lab = jnp.asarray(batches[t]["labels"][i])
                params[i], mu[i], nu[i], count[i], loss = step(
                    params[i], mu[i], nu[i], count[i], tok, lab)
                losses.append(float(loss))
            out["round_loss"].append(float(np.mean(losses)) if losses else 0.0)
        if fleet:
            out["change"] = fleet_norms(params, p0)
            out["moment"] = fleet_norms(mu)
    return out
