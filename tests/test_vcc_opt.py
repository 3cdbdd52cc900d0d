"""VCC optimizer: constraints, optimality vs exact reference, campus duals,
and the Pallas kernel path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.vcc import (VCCProblem, delta_bounds,
                            greedy_linear_reference, solve_vcc)
from repro.kernels.vcc_pgd.kernel import pgd_epoch_pallas
from repro.kernels.vcc_pgd.ref import pgd_epoch_ref


def make_problem(n=6, lambda_p=0.0, seed=0, campus_limit=1e9):
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 6)
    H = 24
    eta = 0.3 + 0.25 * jnp.sin(jnp.linspace(0, 2 * jnp.pi, H))[None] \
        + 0.05 * jax.random.normal(ks[0], (n, H))
    u_if = 0.4 + 0.05 * jax.random.normal(ks[1], (n, H))
    tau = 2.0 + 3.0 * jax.random.uniform(ks[2], (n,))
    pow_nom = 500.0 + 20.0 * jax.random.normal(ks[3], (n, H))
    pi = jnp.full((n, H), 300.0)
    return VCCProblem(
        eta=jnp.abs(eta), u_if=u_if, u_if_q=u_if * 1.1, tau=tau,
        pow_nom=pow_nom, pi=pi, u_pow_cap=jnp.full((n,), 0.95),
        capacity=jnp.full((n,), 1.3), ratio=jnp.full((n, H), 1.3),
        campus=jnp.asarray(np.arange(n) % 2, jnp.int32),
        campus_limit=jnp.full((2,), campus_limit),
        lambda_e=0.1, lambda_p=lambda_p, drop_limit=1.0)


def test_conservation_and_bounds():
    p = make_problem()
    sol = solve_vcc(p, inner_iters=120, outer_iters=3)
    lo, ub, feas = delta_bounds(p)
    assert bool(feas.all())
    assert float(jnp.abs(sol.delta.sum(1)).max()) < 1e-4
    assert bool(jnp.all(sol.delta >= lo - 1e-4))
    assert bool(jnp.all(sol.delta <= ub + 1e-4))
    assert bool(jnp.all(sol.vcc <= p.capacity[:, None] + 1e-4))


def test_matches_exact_greedy_when_linear():
    p = make_problem(lambda_p=0.0)
    sol = solve_vcc(p, inner_iters=250, outer_iters=2)
    lo, ub, _ = delta_bounds(p)
    for c in range(p.eta.shape[0]):
        cost = np.asarray(p.eta[c] * p.pi[c])
        dref = greedy_linear_reference(cost, np.asarray(lo[c]),
                                       np.asarray(ub[c]))
        jp = float((cost * np.asarray(sol.delta[c])).sum())
        jr = float((cost * dref).sum())
        assert jp <= jr + 0.005 * abs(jr), (c, jp, jr)


def test_peak_term_flattens_power():
    p0 = make_problem(lambda_p=0.0, seed=3)
    p1 = make_problem(lambda_p=5.0, seed=3)
    s0 = solve_vcc(p0, inner_iters=150, outer_iters=2)
    s1 = solve_vcc(p1, inner_iters=150, outer_iters=2)
    assert float(s1.y.mean()) <= float(s0.y.mean()) + 1e-3


def test_campus_duals_enforce_contract():
    p = make_problem(lambda_p=0.1, seed=4)
    unconstrained = solve_vcc(p, inner_iters=100, outer_iters=2)
    camp_peak = np.asarray(jax.ops.segment_sum(unconstrained.y, p.campus,
                                               num_segments=2))
    tight = make_problem(lambda_p=0.1, seed=4,
                         campus_limit=float(camp_peak.max()) * 0.97)
    sol = solve_vcc(tight, inner_iters=100, outer_iters=25)
    new_peak = np.asarray(jax.ops.segment_sum(sol.y, tight.campus,
                                              num_segments=2))
    viol = (new_peak - np.asarray(tight.campus_limit)) \
        / np.asarray(tight.campus_limit)
    assert viol.max() < 0.02, viol          # within 2% of the contract
    assert float(sol.mu.max()) > 0.0        # duals actually engaged


@pytest.mark.parametrize("n,tile", [(12, 12), (256, 256), (300, 128)],
                         ids=["whole-array", "cell-tile", "padded-lanes"])
def test_pallas_epoch_matches_ref(n, tile):
    """The clusters-on-lanes kernel (interpret mode) against the oracle: a
    block narrower than one vreg's lanes, the cell's 256-lane tile, and
    128-lane tiles with dead lanes padded past the last cluster."""
    H = 24
    key = jax.random.PRNGKey(5)
    ks = jax.random.split(key, 6)
    delta = jnp.zeros((n, H))
    eta = 0.2 + 0.2 * jax.random.uniform(ks[0], (n, H))
    pi = 200 + 100 * jax.random.uniform(ks[1], (n, H))
    pow_nom = 400 + 100 * jax.random.uniform(ks[2], (n, H))
    tau24 = 0.05 + 0.2 * jax.random.uniform(ks[3], (n, 1))
    price = 0.05 * jnp.ones((n, 1))
    lo = jnp.full((n, H), -0.8)
    ub = 0.5 + jax.random.uniform(ks[4], (n, H))
    lr = 0.01 * jnp.ones((n, 1))
    kw = dict(temp=10.0, lambda_e=0.3, iters=30)
    d1 = pgd_epoch_ref(delta, eta, pi, pow_nom, tau24, price, lo, ub, lr,
                       **kw)
    d2 = pgd_epoch_pallas(delta, eta, pi, pow_nom, tau24, price, lo, ub, lr,
                          tile=tile, interpret=True, **kw)
    assert float(jnp.abs(d1 - d2).max()) < 1e-5


def test_infeasible_clusters_get_capacity_vcc():
    p = make_problem(seed=6)
    # make cluster 0 hopeless: inflexible above the power cap all day
    u_if = p.u_if.at[0].set(2.0)
    p = VCCProblem(**{**p.__dict__, "u_if": u_if, "u_if_q": u_if * 1.1})
    sol = solve_vcc(p, inner_iters=50, outer_iters=2)
    assert not bool(sol.shaped[0])
    np.testing.assert_allclose(np.asarray(sol.vcc[0]),
                               float(p.capacity[0]), rtol=1e-5)
