"""Plain reference of one DySTop simulation: the control plane in numpy, the
model plane in straightforward ``jax.numpy``.

Written from the paper's equations and the configuration alone; it imports
nothing of the program under test.  What it shares with the program is the
configuration, the seed and the inputs that ``gen`` makes.

Control plane (Alg. 1-3): the edge network and worker speeds drawn from the
seed, WAA (Alg. 2, the Eq. 34 drift-plus-penalty over cost-sorted prefixes),
PTCA (Alg. 3, Eq. 45-47 priorities and the greedy bandwidth-bounded pull
construction), the Eq. 7-10 round durations and transfer counts, and the
Eq. 6/33 staleness and queue updates.  One numpy generator is drawn in the
order of Alg. 1: placement, transmit powers and speeds once, then per round
the channel gains, their fluctuation and the link blink-outs.

Model plane: Eq. 4 as a dense (N, N) @ (N, P) mix of every worker's model,
Eq. 5 as ``local_steps`` plain-autodiff SGD steps of every worker masked by
the activation, with minibatches drawn from the seed, and the Eq. 11 global
model evaluated on the held-out set.

The reference runs the control plane in float64 and the model plane in
float32 at ``highest`` matmul precision.  The control run, which has to fail
the comparison, takes the precision below: a float32 control plane and
``high`` (three bfloat16 passes) matmuls.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional

import numpy as np

import gen


def _network(n: int, net: dict, rng: np.random.Generator, ft):
    pos = rng.uniform(0, net["region_m"], size=(n, 2))
    diff = pos[:, None, :] - pos[None, :, :]
    dist = np.sqrt((diff ** 2).sum(-1)) + 1e-9
    np.fill_diagonal(dist, 0.0)
    p_dbm = rng.uniform(net["tx_power_dbm_lo"], net["tx_power_dbm_hi"], size=n)
    tx_w = (10 ** ((p_dbm - 30) / 10)).astype(ft)
    g0 = 10 ** (net["g0_db"] / 10)
    with np.errstate(divide="ignore"):
        mean_gain = g0 * np.where(dist > 0, dist, np.inf) ** -4
    in_range = dist <= net["comm_range_m"]
    np.fill_diagonal(in_range, False)
    return dist, tx_w, mean_gain, in_range


def _rate(net: dict, gain, tx_w):
    """Shannon rate in bytes/s."""
    return net["bandwidth_hz"] * np.log2(1.0 + tx_w * gain / net["noise_w"]) / 8.0


def _waa(tau, queue, tau_bound: int, cost, V: float, cap: Optional[int]):
    """Alg. 2: the cost-sorted prefix that minimises Eq. 34."""
    n = len(cost)
    order = np.argsort(cost, kind="stable")
    limit = n if cap is None else min(cap, n)
    best_k, best = 1, None
    stay = queue * (tau + 1.0)
    for k in range(1, limit + 1):
        inactive = stay[order[k:]].sum()
        score = inactive - tau_bound * queue.sum() + V * cost[order[k - 1]]
        if best is None or score < best:
            best, best_k = score, k
    active = np.zeros(n, bool)
    active[order[:best_k]] = True
    return active


def _ptca(active, in_range, prio, budget, max_nb: Optional[int]):
    """Alg. 3's greedy construction: each active worker pulls from its
    highest-priority reachable neighbour whose budget allows, one link per
    sweep, until a sweep adds nothing."""
    n = len(active)
    links = np.zeros((n, n), bool)
    used = np.zeros(n)
    cands = {}
    for i in np.flatnonzero(active):
        reach = in_range[i].copy()
        reach[i] = False
        c = np.flatnonzero(reach)
        cands[i] = c[np.argsort(-prio[i, c], kind="stable")]
    ptr = {i: 0 for i in cands}
    chosen = {i: 0 for i in cands}
    while True:
        added = False
        for i, c in cands.items():
            if used[i] + 1 > budget[i]:
                continue
            if max_nb is not None and chosen[i] >= max_nb:
                continue
            while ptr[i] < len(c):
                j = c[ptr[i]]
                ptr[i] += 1
                if used[j] + 1 > budget[j]:
                    continue
                links[i, j] = True
                used[i] += 1
                used[j] += 1
                chosen[i] += 1
                added = True
                break
        if not added:
            return links


def control_plane(n: int, net: dict, proto: dict, run: dict,
                  class_counts: np.ndarray, data_sizes: np.ndarray,
                  model_bytes: float, seed: int, n_rounds: int,
                  dtype=np.float64) -> Dict[str, list]:
    """Replay ``n_rounds`` rounds of Alg. 1's control half.

    Returns per-round ``active`` masks, Eq. 4 matrices ``W``, ``duration``
    and ``n_active``, and the running ``sim_time``, ``comm_gb`` and
    staleness at every round."""
    ft = dtype
    rng = np.random.default_rng(seed)
    dist, tx_w, mean_gain, in_range = _network(n, net, rng, ft)
    h_i = (run["base_compute_s"]
           * rng.lognormal(0.0, run["compute_sigma"], size=n)).astype(ft)
    snr = tx_w[None, :] * mean_gain / net["noise_w"]
    with np.errstate(divide="ignore"):
        exp_link = (model_bytes / (net["bandwidth_hz"] * np.log2(1.0 + snr)
                                   / 8.0)).astype(ft)
    np.fill_diagonal(exp_link, 0.0)
    gain_floor = np.maximum(mean_gain, 1e-30)
    frac = class_counts / np.maximum(class_counts.sum(1, keepdims=True), 1)
    emd = np.abs(frac[:, None, :] - frac[None, :, :]).sum(-1)
    prio1 = (emd / max(emd.max(), 1e-12)
             + (1.0 - dist / max(dist.max(), 1e-12))).astype(ft)
    sizes = np.asarray(data_sizes, ft)

    tau = np.zeros(n, np.int64)
    queue = np.zeros(n, ft)
    pulls = np.zeros((n, n), ft)
    since = np.zeros(n, ft)
    budget = np.full(n, run["bandwidth_budget"], ft)
    clock, comm = ft(0.0), ft(0.0)
    out: Dict[str, list] = {k: [] for k in (
        "active", "W", "duration", "n_active", "sim_time", "comm_gb",
        "staleness_avg", "staleness_max")}
    est_com = np.where(in_range, exp_link, 0.0).max(axis=1).astype(ft)
    for t in range(1, n_rounds + 1):
        h_cmp = np.maximum(h_i - since, 0.0).astype(ft)
        active = _waa(tau, queue, run["tau_bound"], (h_cmp + est_com).astype(ft),
                      proto["V"], proto.get("max_workers"))
        if t <= proto["t_thre"]:
            prio = prio1
        else:
            gap = np.abs(tau[:, None] - tau[None, :]).astype(ft)
            prio = ((1.0 - pulls / ft(t)) / (1.0 + gap)).astype(ft)
        links = _ptca(active, in_range, prio, budget, proto.get("max_neighbors"))
        gain = rng.exponential(gain_floor)
        gain = gain * rng.lognormal(0.0, net["gain_fluctuation"], gain.shape)
        drop = rng.random(gain.shape) < net["dynamics_drop_prob"]
        rate = _rate(net, gain.astype(ft), tx_w[None, :]).astype(ft)
        rate = np.where(drop, rate * ft(0.02), rate)
        with np.errstate(divide="ignore"):
            xfer = np.where(links, model_bytes / rate, 0.0).max(axis=1)
        xfer = xfer.astype(ft)
        per = h_cmp + np.minimum(xfer, ft(run["link_timeout_s"]))
        duration = ft(per[active].max()) if active.any() else ft(0.0)

        W = np.eye(n, dtype=np.float32)
        for i in np.flatnonzero(active | links.any(1)):
            members = links[i].copy()
            members[i] = True
            w = np.where(members, sizes, 0.0)
            W[i] = (w / w.sum()).astype(np.float32)

        clock = ft(clock + duration)
        comm = ft(comm + ft(links.sum()) * ft(model_bytes))
        pulls = pulls + links
        since = (since + duration).astype(ft)
        since[active] = 0.0
        queue = np.maximum(queue + tau - run["tau_bound"], 0.0).astype(ft)
        tau = (tau + 1) * (~active)
        out["active"].append(active)
        out["W"].append(W)
        out["duration"].append(float(duration))
        out["n_active"].append(int(active.sum()))
        out["sim_time"].append(float(clock))
        out["comm_gb"].append(float(comm) / 1e9)
        out["staleness_avg"].append(float(tau.mean()))
        out["staleness_max"].append(int(tau.max()))
    return out


# --------------------------------------------------------------------------- #
# model plane
# --------------------------------------------------------------------------- #


def _mlp_logits(p, x):
    import jax
    h = jax.nn.relu(x @ p["w1"] + p["b1"])
    h = jax.nn.relu(h @ p["w2"] + p["b2"])
    return h @ p["w3"] + p["b3"]


def _ce(p, x, y):
    import jax
    import jax.numpy as jnp
    logp = jax.nn.log_softmax(_mlp_logits(p, x).astype(jnp.float32), -1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], -1))


def init_mlp(seed: int, dim: int, hidden: int, n_classes: int):
    """w_0 from the seed: He-style normal weights, zero biases."""
    import jax
    import jax.numpy as jnp
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {"w1": jax.random.normal(k1, (dim, hidden)) * dim ** -0.5,
            "b1": jnp.zeros((hidden,)),
            "w2": jax.random.normal(k2, (hidden, hidden)) * hidden ** -0.5,
            "b2": jnp.zeros((hidden,)),
            "w3": jax.random.normal(k3, (hidden, n_classes)) * hidden ** -0.5,
            "b3": jnp.zeros((n_classes,))}


@functools.lru_cache(maxsize=None)
def _round_fn(local_steps: int, batch: int, lr: float, dtype: str):
    import jax
    import jax.numpy as jnp

    def rnd(params, W, active, key, t, data_x, data_y, part_idx, part_sizes):
        dt = jnp.dtype(dtype)
        mixed = jax.tree.map(
            lambda l: jnp.tensordot(W.astype(dt), l, axes=1).astype(dt),
            params)
        n = W.shape[0]
        kt = jax.random.fold_in(key, t)

        def draw(i, row, size):
            r = jax.random.randint(jax.random.fold_in(kt, i),
                                   (local_steps, batch), 0, size)
            ids = row[r]
            return data_x[ids].astype(dt), data_y[ids]

        xb, yb = jax.vmap(draw)(jnp.arange(n, dtype=jnp.int32), part_idx,
                                part_sizes)

        def train(p, xs, ys, a):
            for s in range(local_steps):
                g = jax.grad(_ce)(p, xs[s], ys[s])
                p = jax.tree.map(lambda w, gw: (w - (lr * a) * gw).astype(dt),
                                 p, g)
            return p

        return jax.vmap(train)(mixed, xb, yb, active.astype(dt))

    return jax.jit(rnd)


@functools.lru_cache(maxsize=None)
def _eval_fn():
    import jax
    import jax.numpy as jnp

    def ev(params, alpha, x, y):
        gm = jax.tree.map(lambda l: jnp.tensordot(alpha.astype(l.dtype), l,
                                                  axes=1), params)
        logits = _mlp_logits(gm, x.astype(gm["w1"].dtype)).astype(jnp.float32)
        acc = jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))
        logp = jax.nn.log_softmax(logits, -1)
        return acc, -jnp.mean(jnp.take_along_axis(logp, y[:, None], -1))

    return jax.jit(ev)


def model_plane(ctrl: Dict[str, list], n_rounds: int, eval_every: int,
                model: dict, run: dict, seed: int, train: gen.Classification,
                test: gen.Classification, parts: List[np.ndarray],
                data_sizes: np.ndarray, dtype: str = "float32",
                precision: str = "highest") -> Dict[str, list]:
    """Replay ``n_rounds`` rounds of Eq. 4 + Eq. 5 on the control plane's
    decisions; evaluate the Eq. 11 global model every ``eval_every``; return
    the losses and every leaf's change from w_0."""
    import jax
    import jax.numpy as jnp
    n = len(parts)
    with jax.default_matmul_precision(precision):
        p0 = init_mlp(seed, model["dim"], model["hidden"], train.n_classes)
        params = jax.tree.map(
            lambda l: jnp.broadcast_to(l.astype(dtype), (n,) + l.shape), p0)
        max_part = max(len(p) for p in parts)
        part_idx = np.zeros((n, max_part), np.int32)
        for i, p in enumerate(parts):
            part_idx[i, :len(p)] = p
        part_idx = jnp.asarray(part_idx)
        part_sizes = jnp.asarray(np.asarray(data_sizes, np.int32))
        data_x, data_y = jnp.asarray(train.x), jnp.asarray(train.y)
        x_te, y_te = jnp.asarray(test.x), jnp.asarray(test.y)
        alpha = jnp.asarray(data_sizes / data_sizes.sum(), jnp.float32)
        key = jax.random.PRNGKey(seed + 0x5EED)
        step = _round_fn(run["local_steps"], run["batch_size"], run["lr"],
                         dtype)
        ev = _eval_fn()
        out: Dict[str, list] = {"rounds": [], "loss_global": [],
                                "acc_global": []}
        for t in range(1, n_rounds + 1):
            params = step(params, jnp.asarray(ctrl["W"][t - 1]),
                          jnp.asarray(ctrl["active"][t - 1]), key,
                          jnp.int32(t), data_x, data_y, part_idx, part_sizes)
            if t % eval_every == 0:
                acc, loss = ev(params, alpha, x_te, y_te)
                out["rounds"].append(t)
                out["acc_global"].append(float(acc))
                out["loss_global"].append(float(loss))
        # each leaf's change from w_0 over the whole fleet, (N, leaf size)
        out["change"] = {k: np.asarray(jnp.asarray(params[k], jnp.float32)
                                       - jnp.asarray(p0[k], jnp.float32)
                                       ).reshape(n, -1)
                         for k in sorted(params)}
    return out
