"""VCC optimizer: constraints, optimality vs exact reference, campus duals,
and the Pallas kernel path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.experimental import pallas as pl

from repro.core.vcc import (VCCProblem, delta_bounds,
                            greedy_linear_reference, solve_vcc,
                            suffix_bounds)
from repro.kernels.vcc_pgd.kernel import _project_rows, pgd_epoch_pallas
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


@pytest.mark.parametrize("n,tile,hour", [(12, 12, None), (256, 256, None),
                                         (300, 128, None), (256, 256, 12)],
                         ids=["whole-array", "cell-tile", "padded-lanes",
                              "suffix-hour12"])
def test_pallas_epoch_matches_ref(n, tile, hour):
    """The clusters-on-lanes kernel (interpret mode) against the oracle: a
    block narrower than one vreg's lanes, the cell's 256-lane tile,
    128-lane tiles with dead lanes padded past the last cluster, and the
    hourly re-solve's suffix polytope at hour 12 (``vcc.suffix_bounds``:
    elapsed hours pinned at the realized deviations, clusters that can no
    longer conserve pinned everywhere)."""
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
    if hour is not None:
        p = make_problem(n=n, seed=7)
        lo0, ub0, _ = delta_bounds(p)
        delta = lo0 + jax.random.uniform(ks[5], (n, H)) * (ub0 - lo0)
        lo, ub, feasible = suffix_bounds(p, delta, hour)
        assert 0 < int(feasible.sum()) < n        # both kinds of cluster
    kw = dict(temp=10.0, lambda_e=0.3, iters=30)
    d1 = pgd_epoch_ref(delta, eta, pi, pow_nom, tau24, price, lo, ub, lr,
                       **kw)
    d2 = pgd_epoch_pallas(delta, eta, pi, pow_nom, tau24, price, lo, ub, lr,
                          tile=tile, interpret=True, **kw)
    assert float(jnp.abs(d1 - d2).max()) < 1e-5


def _exact_projection(z, lo, ub):
    """Sort-based exact projection of each column of (H, n) ``z`` onto
    {sum = 0} n [lo, ub], in float64: f(nu) = sum clip(z - nu, lo, ub)
    is linear between neighbouring sorted breakpoints z - ub, z - lo.
    Where no breakpoint has f <= 0 (sum lo > 0) every hour goes to lo;
    where none has f >= 0 (sum ub < 0), to ub."""
    z, lo, ub = (np.asarray(a, np.float64).T for a in (z, lo, ub))
    out = np.empty_like(z)
    for c in range(z.shape[0]):
        bp = np.sort(np.concatenate([z[c] - ub[c], z[c] - lo[c]]))
        f = np.array([np.clip(z[c] - b, lo[c], ub[c]).sum() for b in bp])
        if f[-1] > 0:
            nu = np.inf
        elif f[0] < 0:
            nu = -np.inf
        else:
            j = max(int(np.argmax(f <= 0)), 1)
            b0, b1, f0, f1 = bp[j - 1], bp[j], f[j - 1], f[j]
            nu = b0 + (f0 * (b1 - b0) / (f0 - f1) if f0 > f1 else 0.0)
        out[c] = np.clip(z[c] - nu, lo[c], ub[c])
    return out.T


def _projection_case(case, n, rng):
    """(z, lo, ub), each (24, n) float32, clusters on the last axis."""
    H = 24
    z = 2.0 * rng.standard_normal((H, n))
    lo = -rng.uniform(0.2, 1.0, (H, n))
    ub = rng.uniform(0.2, 1.5, (H, n))
    if case == "duplicate-breakpoints":
        z = rng.choice([-0.5, 0.0, 0.5], (H, n))
        lo, ub = np.full((H, n), -0.5), np.full((H, n), 0.5)
    elif case == "flat-zero":        # half the hours at ub, half at lo
        w = rng.uniform(0.2, 1.0, (1, n))
        lo, ub = np.full((H, n), -w), np.full((H, n), w)
        z = np.where(np.arange(H)[:, None] % 2 == 0, 1.0, -1.0) \
            * (w + rng.uniform(1.0, 3.0, (H, n)))
    elif case == "zero-box":
        lo, ub = np.zeros((H, n)), np.zeros((H, n))
    elif case == "pinned-hours":     # elapsed hours at the committed value
        for h in (1, 12, 23):
            lo[h] = ub[h] = rng.uniform(-0.2, 0.2, n)
    elif case == "sum-ub-negative":
        ub = -rng.uniform(0.01, 0.3, (H, n))
        lo = ub - rng.uniform(0.0, 1.0, (H, n))
    elif case == "sum-lo-positive":
        lo = rng.uniform(0.01, 0.3, (H, n))
        ub = lo + rng.uniform(0.0, 1.0, (H, n))
    return tuple(np.asarray(a, np.float32) for a in (z, lo, ub))


@pytest.mark.parametrize("n", [8, 256])
@pytest.mark.parametrize("case", ["random", "duplicate-breakpoints",
                                  "flat-zero", "zero-box", "pinned-hours",
                                  "sum-ub-negative", "sum-lo-positive"])
def test_kernel_projection_is_exact(case, n):
    """The kernels' shared projection (interpret mode, (24, n) blocks with
    clusters on lanes) against a sort-based exact oracle: within 1e-6 of
    each column's scale, conserving to 1e-5 of it, inside the box
    exactly. Rows that cannot conserve go wholly to the bound they
    violate, exactly, as the jnp oracle's bisection sends them."""
    z, lo, ub = _projection_case(case, n,
                                 np.random.default_rng(len(case) + n))

    def kernel(z_ref, lo_ref, ub_ref, out_ref):
        out_ref[...] = _project_rows(z_ref[...], lo_ref[...], ub_ref[...])

    x = np.asarray(pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct(z.shape, jnp.float32),
        interpret=True)(z, lo, ub))
    scale = np.abs(z).max(axis=0)
    assert np.all(np.abs(x - _exact_projection(z, lo, ub)).max(axis=0)
                  <= 1e-6 * scale)
    assert np.all((lo <= x) & (x <= ub))
    if case == "sum-ub-negative":
        np.testing.assert_array_equal(x, ub)
    elif case == "sum-lo-positive":
        np.testing.assert_array_equal(x, lo)
    else:
        assert np.all(np.abs(x.sum(axis=0)) <= 1e-5 * scale)


def test_infeasible_clusters_get_capacity_vcc():
    p = make_problem(seed=6)
    # make cluster 0 hopeless: inflexible above the power cap all day
    u_if = p.u_if.at[0].set(2.0)
    p = VCCProblem(**{**p.__dict__, "u_if": u_if, "u_if_q": u_if * 1.1})
    sol = solve_vcc(p, inner_iters=50, outer_iters=2)
    assert not bool(sol.shaped[0])
    np.testing.assert_allclose(np.asarray(sol.vcc[0]),
                               float(p.capacity[0]), rtol=1e-5)
