"""Unit + property tests for the paper's control plane: staleness (Eq. 6/33),
WAA (Alg. 2), PTCA (Alg. 3), aggregation (Eq. 4), convergence bound (Thm. 1
corollaries)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import convergence as CV
from repro.core import ptca as PT
from repro.core import waa as WA
from repro.core.aggregation import apply_mixing, mixing_matrix
from repro.core.staleness import StalenessState, drift_plus_penalty
from repro.kernels.config import KernelConfig


# --------------------------------------------------------------------------- #
# staleness / queues
# --------------------------------------------------------------------------- #


@settings(max_examples=30, deadline=None)
@given(st.lists(st.lists(st.booleans(), min_size=6, max_size=6),
                min_size=1, max_size=20))
def test_staleness_eq6_semantics(mask_rounds):
    st_ = StalenessState.create(6, tau_bound=3)
    tau_ref = np.zeros(6, np.int64)
    q_ref = np.zeros(6)
    for mask in mask_rounds:
        m = np.array(mask, bool)
        q_ref = np.maximum(q_ref + tau_ref - 3, 0.0)       # Eq. 33
        tau_ref = (tau_ref + 1) * (~m)                     # Eq. 6
        st_.advance(m)
        np.testing.assert_array_equal(st_.tau, tau_ref)
        np.testing.assert_allclose(st_.queue, q_ref)


def test_activated_worker_resets_to_zero():
    st_ = StalenessState.create(3, tau_bound=2)
    st_.advance(np.array([False, False, True]))
    assert st_.tau.tolist() == [1, 1, 0]
    st_.advance(np.array([True, False, False]))
    assert st_.tau.tolist() == [0, 2, 1]


# --------------------------------------------------------------------------- #
# WAA
# --------------------------------------------------------------------------- #


@settings(max_examples=30, deadline=None)
@given(n=st.integers(2, 10), seed=st.integers(0, 1000))
def test_waa_is_optimal_over_prefixes(n, seed):
    rng = np.random.default_rng(seed)
    st_ = StalenessState.create(n, tau_bound=2)
    st_.tau = rng.integers(0, 6, n)
    st_.queue = rng.uniform(0, 5, n)
    cost = rng.uniform(0.1, 4.0, n)
    active, best = WA.worker_activation(st_, cost, V=3.0)

    # brute-force all prefixes of the sorted order
    order = np.argsort(cost, kind="stable")
    scores = []
    for k in range(1, n + 1):
        mask = np.zeros(n, bool)
        mask[order[:k]] = True
        h = float(cost[order[:k]].max())
        scores.append(drift_plus_penalty(st_.queue, st_.previewed_tau(mask),
                                         st_.tau_bound, h, 3.0))
    assert best == pytest.approx(min(scores))
    assert active.sum() >= 1


def test_waa_large_V_prefers_fast_single_worker():
    """V huge -> round-duration term dominates -> activate only the fastest."""
    st_ = StalenessState.create(5, tau_bound=3)
    cost = np.array([1.0, 2.0, 3.0, 4.0, 5.0])
    active, _ = WA.worker_activation(st_, cost, V=1e9)
    assert active.tolist() == [True, False, False, False, False]


def test_waa_starved_queue_forces_activation():
    """A worker with a huge Lyapunov queue gets activated even if slow."""
    st_ = StalenessState.create(3, tau_bound=1)
    st_.queue = np.array([0.0, 0.0, 1e6])
    st_.tau = np.array([0, 0, 50])
    cost = np.array([1.0, 1.1, 10.0])
    active, _ = WA.worker_activation(st_, cost, V=1.0)
    assert active[2]


# --------------------------------------------------------------------------- #
# PTCA
# --------------------------------------------------------------------------- #


def test_emd_properties():
    counts = np.array([[10, 0, 0], [0, 10, 0], [5, 5, 0], [10, 0, 0]])
    emd = PT.emd_matrix(counts)
    assert np.allclose(emd, emd.T)
    assert np.allclose(np.diag(emd), 0)
    assert emd[0, 1] == pytest.approx(2.0)      # disjoint classes: max EMD
    assert emd[0, 3] == pytest.approx(0.0)      # identical distributions
    assert emd[0, 2] == pytest.approx(1.0)


@settings(max_examples=25, deadline=None)
@given(n=st.integers(3, 12), seed=st.integers(0, 500),
       budget=st.integers(1, 6))
def test_ptca_respects_bandwidth_budgets(n, seed, budget):
    rng = np.random.default_rng(seed)
    active = rng.random(n) < 0.5
    if not active.any():
        active[0] = True
    in_range = rng.random((n, n)) < 0.7
    np.fill_diagonal(in_range, False)
    prio = rng.random((n, n))
    budgets = np.full(n, float(budget))
    res = PT.construct_topology(active, in_range, prio, budgets)
    # Eq. 10: in-links + out-links per worker, each consuming one unit of b
    usage = res.links.sum(axis=1) + res.links.sum(axis=0)
    assert (usage <= budget).all()
    # only activated workers pull
    assert not res.links[~active].any()
    # links only within range
    assert not res.links[~in_range & res.links].any() if res.links.any() else True


def test_ptca_phase1_prefers_dissimilar_neighbors():
    # worker 0 active; worker 1 has identical data, worker 2 disjoint data
    active = np.array([True, False, False])
    in_range = np.ones((3, 3), bool)
    np.fill_diagonal(in_range, False)
    counts = np.array([[10, 0], [10, 0], [0, 10]])
    dist = np.ones((3, 3))
    res = PT.ptca(t=1, t_thre=10, active=active, in_range=in_range,
                  class_counts=counts, phys_dist=dist,
                  pull_counts=np.zeros((3, 3)), tau=np.zeros(3),
                  bandwidth_budget=np.array([1.0, 9.0, 9.0]))
    assert res.links[0, 2] and not res.links[0, 1]


def test_ptca_phase2_prefers_fresh_and_similar_staleness():
    active = np.array([True, False, False])
    in_range = np.ones((3, 3), bool)
    np.fill_diagonal(in_range, False)
    pulls = np.zeros((3, 3))
    pulls[0, 1] = 50.0                     # worker 1 pulled many times already
    tau = np.array([0, 0, 0])
    res = PT.ptca(t=100, t_thre=10, active=active, in_range=in_range,
                  class_counts=np.ones((3, 2)), phys_dist=np.ones((3, 3)),
                  pull_counts=pulls, tau=tau,
                  bandwidth_budget=np.array([1.0, 9.0, 9.0]))
    assert res.links[0, 2] and not res.links[0, 1]


def test_ptca_max_neighbors():
    n = 10
    active = np.zeros(n, bool)
    active[0] = True
    in_range = np.ones((n, n), bool)
    np.fill_diagonal(in_range, False)
    res = PT.construct_topology(active, in_range, np.random.default_rng(0).random((n, n)),
                                np.full(n, 100.0), max_neighbors=3)
    assert res.links[0].sum() == 3


# --------------------------------------------------------------------------- #
# aggregation
# --------------------------------------------------------------------------- #


@settings(max_examples=20, deadline=None)
@given(n=st.integers(2, 12), seed=st.integers(0, 300))
def test_mixing_matrix_row_stochastic(n, seed):
    rng = np.random.default_rng(seed)
    active = rng.random(n) < 0.5
    links = (rng.random((n, n)) < 0.3)
    np.fill_diagonal(links, False)
    links[~active] = False
    d = rng.integers(1, 100, n).astype(float)
    W = mixing_matrix(active, links, d)
    np.testing.assert_allclose(W.sum(axis=1), 1.0, rtol=1e-6)
    for i in range(n):
        if not active[i]:
            assert W[i, i] == 1.0
    # Eq. 4 weights: sigma_{i,j} proportional to D_j
    for i in np.flatnonzero(active):
        members = np.flatnonzero(W[i] > 0)
        np.testing.assert_allclose(W[i, members], d[members] / d[members].sum(),
                                   rtol=1e-5)


def test_apply_mixing_kernel_equals_matmul():
    n = 9
    rng = np.random.default_rng(0)
    W = jnp.asarray(mixing_matrix(np.ones(n, bool),
                                  rng.random((n, n)) < 0.4, rng.integers(1, 9, n)))
    tree = {"a": jnp.asarray(rng.normal(size=(n, 13, 7)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(n, 40)), jnp.float32)}
    out_k = apply_mixing(W, tree, kernels=KernelConfig(backend="pallas"))
    out_j = apply_mixing(W, tree)
    for k in tree:
        np.testing.assert_allclose(out_k[k], out_j[k], rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------- #
# convergence bound (Thm. 1 / Corollaries 1-3)
# --------------------------------------------------------------------------- #


def _toy_history(n=4, T=30, freq=0.5, seed=0):
    rng = np.random.default_rng(seed)
    active_hist, mix_hist = [], []
    for _ in range(T):
        a = rng.random(n) < freq
        if not a.any():
            a[rng.integers(n)] = True
        links = np.zeros((n, n), bool)
        for i in np.flatnonzero(a):
            links[i] = rng.random(n) < 0.5
            links[i, i] = False
        mix_hist.append(mixing_matrix(a, links, np.ones(n)))
        active_hist.append(a)
    return active_hist, mix_hist


def test_corollary1_bound_decreases_with_tau_max():
    vals = CV.bound_vs_tau_max([1, 3, 5, 10], psi=0.5, T=100, rho=0.95, f0_gap=1.0)
    assert all(vals[i] < vals[i + 1] for i in range(len(vals) - 1))


def test_corollary2_bound_decreases_with_psi():
    vals = CV.bound_vs_psi([0.1, 0.3, 0.6, 0.9], tau_max=3, T=100, rho=0.95,
                           f0_gap=1.0)
    assert all(vals[i] > vals[i + 1] for i in range(len(vals) - 1))


def test_corollary3_bound_increases_with_non_iid():
    active_hist, mix_hist = _toy_history()
    alpha = np.full(4, 0.25)
    kw = dict(alpha=alpha, f0_gap=1.0, eta=0.01, mu=0.5, L=1.0,
              g_star=np.ones(4))
    b_iid = CV.convergence_bound(active_hist, mix_hist, xi=np.zeros(4), **kw)
    b_noniid = CV.convergence_bound(active_hist, mix_hist, xi=np.full(4, 2.0), **kw)
    assert b_noniid > b_iid


def test_bound_finite_and_positive():
    active_hist, mix_hist = _toy_history(T=50)
    b = CV.convergence_bound(active_hist, mix_hist, alpha=np.full(4, 0.25),
                             f0_gap=2.0, eta=0.01, mu=0.5, L=1.0,
                             xi=np.full(4, 0.5), g_star=np.ones(4))
    assert np.isfinite(b) and b > 0
