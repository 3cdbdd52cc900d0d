"""CVaR ensemble optimizer (repro.core.risk): objective properties, the
degenerate-ensemble bitwise contracts, and the kernel dispatch parity.

Conventions under test (see risk.py): ``risk_beta`` is the averaged
worst-tail FRACTION — beta=1 is the risk-neutral mean (today's
point-forecast path), smaller beta is more risk-averse. Bitwise notes:

* K=1 ensembles are statically collapsed inside ``solve_vcc`` to the
  point-forecast problem, so the degenerate risk path runs the EXACT
  legacy graph (hard bitwise contract, kernel path included).
* K identical members collapse bitwise at the STEP level (the member
  reduction is anchored on member 0, so every deviation is exactly 0.0).
  The full solve compiles ensemble and plain epochs as different XLA
  programs, which may legally differ in fusion/FMA choices (the same
  caveat sim.engine documents for standalone-vs-scan compilation), so the
  solve-level check asserts a few-ulp ceiling rather than equality.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import risk, vcc
from repro.kernels.vcc_pgd import ref as kref

f32 = jnp.float32


# the one synthetic problem recipe shared with the parity tests and the
# solve-cost benchmark probe
_vcc_problem = vcc.synthetic_problem


def _identical_ensemble(p, K):
    eta_ens = jnp.broadcast_to(p.eta[None], (K,) + p.eta.shape)
    uif_ens = jnp.broadcast_to(p.u_if[None], (K,) + p.u_if.shape)
    return eta_ens, uif_ens


def _perturbed_ensemble(p, K, seed=0, vol=0.5):
    """Correlated whole-day intensity perturbations (member 0 = point
    forecast, like risk.sample_eta_ensemble's resampled-day structure)."""
    prof = 1.0 + vol * jax.random.normal(jax.random.PRNGKey(seed),
                                         (K, 1, 24))
    eta_ens = jnp.clip(
        jnp.broadcast_to(p.eta[None], (K,) + p.eta.shape)
        * prof.at[0].set(1.0), 1e-4, None)
    _, uif_ens = _identical_ensemble(p, K)
    return eta_ens, uif_ens


# ------------------------------------------------------- CVaR properties

def test_cvar_beta_one_is_mean():
    """``cvar`` at beta=1 sums the members in sorted order, ``mean`` in
    index order, so the two round differently. Where the members cancel
    (a mean near 0) the gap is relative to the terms, not the result:
    ``atol`` is half an ulp of a unit-scale term, 2**-24 (measured on
    jax 0.9 CPU: 2.6e-8 at a mean of -0.0103)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 5))
    np.testing.assert_allclose(np.asarray(risk.cvar(x, 1.0, axis=0)),
                               np.asarray(x.mean(axis=0)), rtol=1e-6,
                               atol=2.0 ** -24)
    np.testing.assert_allclose(np.asarray(risk.soft_cvar(x, 1.0, axis=0)),
                               np.asarray(x.mean(axis=0)), rtol=1e-5,
                               atol=1e-6)


def test_cvar_beta_to_zero_is_max():
    x = jax.random.normal(jax.random.PRNGKey(1), (32,))
    np.testing.assert_allclose(np.asarray(risk.cvar(x, 1e-9)),
                               np.asarray(x.max()), rtol=1e-6)


def test_cvar_monotone_in_beta():
    """Smaller beta = averaging fewer, worse outcomes = larger value:
    CVaR is monotone non-increasing in beta (equivalently non-decreasing
    in the risk aversion 1-beta). Holds for the hard and soft forms."""
    x = jax.random.normal(jax.random.PRNGKey(2), (64,)) * 3.0
    betas = [0.05, 0.2, 0.5, 0.9, 1.0]
    hard = [float(risk.cvar(x, b)) for b in betas]
    soft = [float(risk.soft_cvar(x, b)) for b in betas]
    assert all(a >= b - 1e-5 for a, b in zip(hard, hard[1:])), hard
    assert all(a >= b - 1e-5 for a, b in zip(soft, soft[1:])), soft


def test_soft_cvar_between_mean_and_max():
    x = jax.random.normal(jax.random.PRNGKey(3), (24,)) * 2.0
    for b in (0.1, 0.5, 0.9):
        v = float(risk.soft_cvar(x, b))
        assert float(x.mean()) - 1e-5 <= v <= float(x.max()) + 1e-5


def test_cvar_sharpness_endpoints():
    assert float(kref.cvar_sharpness(1.0)) == 0.0
    assert float(kref.cvar_sharpness(0.5)) > 0.0
    # traced beta works (the day cycle carries beta as a data leaf)
    assert float(jax.jit(kref.cvar_sharpness)(jnp.asarray(0.9))) > 0.0


# ------------------------------------------- degenerate-ensemble parity

def test_k1_ensemble_bitwise_identical_to_plain_solve():
    """Acceptance contract: the K=1 / beta->1 ensemble path IS today's
    solve_vcc, bitwise — jnp oracle and interpret-mode kernel both."""
    p = _vcc_problem()
    eta_ens, uif_ens = _identical_ensemble(p, 1)
    for kw in (dict(use_pallas=False), dict(interpret=True)):
        plain = vcc.solve_vcc(p, inner_iters=40, outer_iters=4, **kw)
        # beta->1 (risk-neutral) and a risk-averse beta: K=1 must collapse
        # identically for ANY beta
        for beta in (1.0, 0.5):
            pe = risk.attach_ensemble(p, eta_ens, uif_ens, beta)
            ens = vcc.solve_vcc(pe, inner_iters=40, outer_iters=4, **kw)
            for name in ("delta", "y", "vcc", "shaped", "mu", "objective"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(ens, name)),
                    np.asarray(getattr(plain, name)),
                    err_msg=f"{name} (beta={beta}, {kw})")


def test_identical_members_step_bitwise():
    """The anchored member reduction: K identical members produce the
    EXACT single-member PGD step (every deviation is exactly 0.0)."""
    p = _vcc_problem(n=6)
    K = 8
    tau24 = p.tau[:, None] / 24.0
    price = jnp.full((6, 1), 0.05, f32)
    lo = jnp.full((6, 24), -0.8, f32)
    ub = jnp.full((6, 24), 2.0, f32)
    lr = jnp.full((6, 1), 0.01, f32)
    d = 0.1 * jax.random.normal(jax.random.PRNGKey(5), (6, 24))
    eta_e = jnp.broadcast_to(p.eta[None], (K, 6, 24))
    pow_e = jnp.broadcast_to(p.pow_nom[None], (K, 6, 24))
    plain = kref.pgd_step_arrays(d, p.eta, p.pi, p.pow_nom, tau24, price,
                                 lo, ub, lr, 10.0, 0.1)
    for beta in (1.0, 0.5, 0.1):
        ens = kref.pgd_step_ens_arrays(d, eta_e, p.pi, pow_e, tau24, price,
                                       lo, ub, lr, 10.0, 0.1,
                                       kref.cvar_sharpness(beta))
        np.testing.assert_array_equal(np.asarray(ens), np.asarray(plain),
                                      err_msg=f"beta={beta}")


def test_identical_members_solve_collapses_to_plain():
    """K=8 identical members == K=1 == plain solve. Bitwise at the step
    level (above); at the solve level ensemble and plain epochs are
    different XLA programs whose fusion/FMA choices may legally differ,
    so assert a few-ulp ceiling on the compounded drift: 1.5e-6 on
    |delta| <= 2, about a dozen float32 ulps (measured on jax 0.9 CPU:
    1.25e-6 after 160 steps)."""
    p = _vcc_problem()
    eta_ens, uif_ens = _identical_ensemble(p, 8)
    pe = risk.attach_ensemble(p, eta_ens, uif_ens, 0.5)
    plain = vcc.solve_vcc(p, inner_iters=40, outer_iters=4,
                          use_pallas=False)
    ens = vcc.solve_vcc(pe, inner_iters=40, outer_iters=4,
                        use_pallas=False)
    np.testing.assert_allclose(np.asarray(ens.delta),
                               np.asarray(plain.delta),
                               rtol=0.0, atol=1.5e-6)
    np.testing.assert_allclose(np.asarray(ens.vcc), np.asarray(plain.vcc),
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(ens.shaped),
                                  np.asarray(plain.shaped))


# ------------------------------------------------------- kernel dispatch

def test_ens_interpret_kernel_matches_ref():
    """The ensemble Pallas kernel (interpret mode on CPU) must match the
    jnp ensemble oracle inside solve_vcc — same member-reduction math,
    two dispatch targets (mirrors the plain-kernel parity test)."""
    p = _vcc_problem()
    eta_ens, uif_ens = _perturbed_ensemble(p, 8)
    pe = risk.attach_ensemble(p, eta_ens, uif_ens, 0.5)
    ref = vcc.solve_vcc(pe, inner_iters=40, outer_iters=4,
                        use_pallas=False)
    ker = vcc.solve_vcc(pe, inner_iters=40, outer_iters=4, interpret=True)
    np.testing.assert_allclose(np.asarray(ker.delta), np.asarray(ref.delta),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ker.vcc), np.asarray(ref.vcc),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(ker.shaped),
                                  np.asarray(ref.shaped))


def test_ens_epoch_kernel_tiling_covers_remainder():
    """Cluster counts that do not divide the ensemble tile must pad
    cleanly (dead rows projected to zero, then sliced off)."""
    p = _vcc_problem(n=7)
    eta_ens, uif_ens = _perturbed_ensemble(p, 3)
    pe = risk.attach_ensemble(p, eta_ens, uif_ens, 0.7)
    ref = vcc.solve_vcc(pe, inner_iters=10, outer_iters=2,
                        use_pallas=False)
    ker = vcc.solve_vcc(pe, inner_iters=10, outer_iters=2, interpret=True)
    np.testing.assert_allclose(np.asarray(ker.delta), np.asarray(ref.delta),
                               rtol=1e-4, atol=1e-5)


def test_ens_kernel_k32_sweep_size():
    """The largest sweep size (K=32, sim.RISK_MEMBERS) goes through the
    ensemble kernel's (K, tile, 24) member slabs."""
    from repro.sim import RISK_MEMBERS
    K = max(RISK_MEMBERS)
    p = _vcc_problem(n=4)
    eta_ens, uif_ens = _perturbed_ensemble(p, K)
    pe = risk.attach_ensemble(p, eta_ens, uif_ens, 0.9)
    ref = vcc.solve_vcc(pe, inner_iters=5, outer_iters=1,
                        use_pallas=False)
    ker = vcc.solve_vcc(pe, inner_iters=5, outer_iters=1, interpret=True)
    np.testing.assert_allclose(np.asarray(ker.delta), np.asarray(ref.delta),
                               rtol=1e-4, atol=1e-5)


# ------------------------------------------------- risk-averse behavior

def test_risk_averse_solve_improves_soft_cvar():
    """Descending the soft-CVaR tilt must (weakly) beat the risk-neutral
    delta ON that objective, for every sweep beta."""
    p = _vcc_problem()
    eta_ens, uif_ens = _perturbed_ensemble(p, 8)
    neutral = vcc.solve_vcc(p, use_pallas=False)
    for beta in (0.5, 0.9, 0.99):
        pr = risk.attach_ensemble(p, eta_ens, uif_ens, beta)
        sr = vcc.solve_vcc(pr, use_pallas=False)
        got = float(risk.soft_cvar_objective(pr, sr.delta, sr.mu))
        ref = float(risk.soft_cvar_objective(pr, neutral.delta, neutral.mu))
        assert got <= ref + 1e-3 * abs(ref), \
            f"beta={beta}: soft CVaR {got} > neutral {ref}"


def test_member_objectives_member0_is_nominal():
    """Member 0 is the point forecast: its cost must equal the nominal
    eq. 4 objective (same hard-peak form) to float tolerance."""
    p = _vcc_problem()
    eta_ens, uif_ens = _identical_ensemble(p, 4)
    pe = risk.attach_ensemble(p, eta_ens, uif_ens, 0.9)
    sol = vcc.solve_vcc(p, inner_iters=10, outer_iters=2, use_pallas=False)
    objs = risk.member_objectives(pe, sol.delta, sol.mu)
    assert objs.shape == (4,)
    np.testing.assert_allclose(
        float(objs[0]),
        float(vcc.objective(p, sol.delta, sol.mu)), rtol=1e-5)


def test_ensemble_solve_jit_and_vmap():
    """Ensemble problems ride jit and vmap (batched risk sweeps)."""
    p = _vcc_problem(n=6)
    eta_ens, uif_ens = _perturbed_ensemble(p, 4)
    pe = risk.attach_ensemble(p, eta_ens, uif_ens, 0.5)
    eager = vcc.solve_vcc(pe, inner_iters=10, outer_iters=2,
                          use_pallas=False)
    jitted = jax.jit(lambda q: vcc.solve_vcc(q, inner_iters=10,
                                             outer_iters=2,
                                             use_pallas=False))(pe)
    np.testing.assert_allclose(np.asarray(jitted.delta),
                               np.asarray(eager.delta), rtol=1e-5,
                               atol=1e-6)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), pe, pe)
    solb = vcc.solve_vcc_batched(stacked, inner_iters=10, outer_iters=2,
                                 use_pallas=False)
    assert solb.delta.shape == (2, 6, 24)


def test_sampled_ensembles_member0_is_point_forecast():
    """risk.sample_* pin member 0 to the point forecast bitwise, and all
    members stay in sane ranges."""
    key = jax.random.PRNGKey(9)
    n, D = 5, 10
    uif_pred = jnp.abs(1.0 + 0.2 * jax.random.normal(key, (n, 24)))
    hist_act = jnp.abs(1.0 + 0.3 * jax.random.normal(key, (n, D, 24)))
    hist_pred = jnp.abs(1.0 + 0.3 * jax.random.normal(
        jax.random.fold_in(key, 1), (n, D, 24)))
    ens = risk.sample_uif_ensemble(key, uif_pred, hist_pred, hist_act, 6)
    assert ens.shape == (6, n, 24)
    np.testing.assert_array_equal(np.asarray(ens[0]), np.asarray(uif_pred))
    assert np.all(np.asarray(ens) >= 0.0)

    fc_z = jnp.abs(0.4 + 0.1 * jax.random.normal(key, (3, 24)))
    chist = jnp.abs(0.4 + 0.1 * jax.random.normal(key, (3, D, 24)))
    zmap = jnp.asarray([0, 1, 2, 0, 1], jnp.int32)
    eta = risk.sample_eta_ensemble(key, fc_z, chist, zmap, 6)
    assert eta.shape == (6, n, 24)
    np.testing.assert_array_equal(np.asarray(eta[0]),
                                  np.asarray(fc_z[zmap]))
    assert np.all(np.asarray(eta) > 0.0)
