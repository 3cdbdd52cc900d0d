"""Joint spatio-temporal optimization (spatial.solve_joint) + the solver
layer it is assembled from.

Contracts under test:

* mobility=0 (static Python scalar) collapses to the EXACT legacy
  temporal graph — bitwise, kernel path included (the spatial analogue of
  the K=1 risk-ensemble contract).
* joint (weakly) dominates the sequential greedy-pre-shift + temporal
  solve on BOTH the nominal objective and its carbon term, for every
  mobility in the sweep (structural: best-of safeguard).
* the fused joint kernel step (Pallas interpreter on CPU) matches the jnp
  oracle, remainder tiles included.
* the spatial pre-shift's import cap is headroom- AND size-aware.
* solver.minimize_linear matches the independent numpy greedy oracle.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import solver, spatial, vcc
from repro.kernels.vcc_pgd import kernel as kker
from repro.kernels.vcc_pgd import ref as kref
from repro.sim import MOBILITY_SWEEP

f32 = jnp.float32


_zonal_problem = vcc.synthetic_zonal_problem


# ------------------------------------------------- mobility=0 collapse

def test_mobility_zero_bitwise_identical_to_legacy_solve():
    """Acceptance contract: solve_joint(p, 0.0) IS solve_vcc(p), bitwise
    — jnp oracle and interpret-mode kernel both."""
    p = _zonal_problem()
    for kw in (dict(use_pallas=False), dict(interpret=True)):
        plain = vcc.solve_vcc(p, **kw)
        sol, tau_j, s = spatial.solve_joint(p, 0.0, **kw)
        for name in ("delta", "y", "vcc", "shaped", "mu", "objective"):
            np.testing.assert_array_equal(
                np.asarray(getattr(sol, name)),
                np.asarray(getattr(plain, name)),
                err_msg=f"{name} ({kw})")
        np.testing.assert_array_equal(np.asarray(tau_j), np.asarray(p.tau))
        assert float(jnp.abs(s).max()) == 0.0


def test_traced_mobility_zero_pins_shift_to_zero():
    """Batched (traced) mobility=0 cannot statically collapse, but the
    bounds pin s to exactly zero through the joint graph."""
    p = _zonal_problem(n=6)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), p, p)
    sol, tau_j, s = spatial.solve_joint_batched(
        stacked, jnp.asarray([0.0, 0.4]), use_pallas=False)
    assert float(jnp.abs(s[0]).max()) == 0.0
    np.testing.assert_array_equal(np.asarray(tau_j[0]), np.asarray(p.tau))
    assert float(jnp.abs(s[1]).sum()) > 0.0


# ------------------------------------------------- dominance (best-of)

def test_joint_dominates_sequential_on_mobility_sweep():
    """For every mobility in the sweep, the joint solution's carbon AND
    nominal objective are <= the sequential two-phase answer's, evaluated
    on the same model-consistent functions (structural via the best-of
    safeguard in solve_joint)."""
    p = _zonal_problem()
    for mob in MOBILITY_SWEEP:
        sol, tau_j, s = spatial.solve_joint(p, float(mob),
                                            use_pallas=False)
        tau_sh, _ = spatial.spatial_shift(p, mobility=float(mob))
        sol_seq = vcc.solve_vcc(dataclasses.replace(p, tau=tau_sh),
                                use_pallas=False)
        s0 = tau_sh - p.tau
        c_j = float(spatial.joint_carbon(p, sol.delta, s))
        c_q = float(spatial.joint_carbon(p, sol_seq.delta, s0))
        o_j = float(spatial.joint_objective(p, sol.delta, s))
        o_q = float(spatial.joint_objective(p, sol_seq.delta, s0))
        tol = 1e-5
        assert c_j <= c_q * (1 + tol) + tol, (mob, c_j, c_q)
        assert o_j <= o_q * (1 + tol) + tol, (mob, o_j, o_q)


def test_joint_strictly_improves_when_saturated():
    """On the saturated zonal fleet at high mobility the joint refinement
    must find strictly less carbon than the greedy pre-shift."""
    p = _zonal_problem(n=16, seed=7)
    sol, _, s = spatial.solve_joint(p, 0.6, use_pallas=False)
    tau_sh, _ = spatial.spatial_shift(p, mobility=0.6)
    sol_seq = vcc.solve_vcc(dataclasses.replace(p, tau=tau_sh),
                            use_pallas=False)
    c_j = float(spatial.joint_carbon(p, sol.delta, s))
    c_q = float(spatial.joint_carbon(p, sol_seq.delta, tau_sh - p.tau))
    assert c_j < c_q, (c_j, c_q)


def test_joint_solution_respects_constraints():
    """Joint delta conserves each cluster's day and respects the bounds
    recomputed at the SHIFTED budgets; s conserves the fleet."""
    p = _zonal_problem()
    sol, tau_j, s = spatial.solve_joint(p, 0.4, use_pallas=False)
    assert float(jnp.abs(s.sum())) < 1e-3 * float(p.tau.sum())
    lo_s, ub_s = spatial.shift_bounds(p, 0.4)
    assert bool(jnp.all(s >= lo_s - 1e-4))
    assert bool(jnp.all(s <= ub_s + 1e-4))
    lo, ub, feas = vcc.delta_bounds(dataclasses.replace(p, tau=tau_j))
    d = np.asarray(sol.delta)
    assert np.abs(d.sum(axis=1)).max() < 1e-3
    feas_np = np.asarray(feas)
    assert (d[feas_np] >= np.asarray(lo)[feas_np] - 1e-3).all()
    assert (d[feas_np] <= np.asarray(ub)[feas_np] + 1e-3).all()
    assert (d[~feas_np] == 0.0).all()


# ------------------------------------------------- kernel parity

def test_joint_step_interpret_kernel_matches_ref():
    """The fused joint step through the Pallas interpreter must match the
    jnp oracle, including remainder tiles (n not divisible by the tile)."""
    for n in (12, 7):
        p = _zonal_problem(n=n, seed=5)
        key = jax.random.PRNGKey(n)
        d = 0.1 * jax.random.normal(key, (n, 24))
        s = 0.2 * jax.random.normal(jax.random.fold_in(key, 1), (n, 1))
        tau = p.tau[:, None]
        price = jnp.full((n, 1), 0.05, f32)
        lr = jnp.full((n, 1), 0.01, f32)
        kw = dict(temp=10.0, lambda_e=0.3, drop_limit=float(p.drop_limit))
        d_r, g_r = kref.joint_step_arrays(
            d, s, p.eta, p.pi, p.pow_nom, tau, p.u_if, p.u_if_q, p.ratio,
            p.u_pow_cap[:, None], p.capacity[:, None], price, lr, **kw)
        d_k, g_k = kker.joint_step_pallas(
            d, s, p.eta, p.pi, p.pow_nom, tau, p.u_if, p.u_if_q, p.ratio,
            p.u_pow_cap[:, None], p.capacity[:, None], price, lr,
            tile=8, interpret=True, **kw)
        np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_r),
                                   rtol=1e-5, atol=1e-6, err_msg=f"n={n}")
        np.testing.assert_allclose(np.asarray(g_k), np.asarray(g_r),
                                   rtol=1e-5, atol=1e-6, err_msg=f"n={n}")


def test_solve_joint_interpret_matches_ref():
    p = _zonal_problem(n=10, seed=4)
    ref, tau_r, s_r = spatial.solve_joint(p, 0.4, use_pallas=False)
    ker, tau_k, s_k = spatial.solve_joint(p, 0.4, interpret=True)
    np.testing.assert_allclose(np.asarray(ker.delta), np.asarray(ref.delta),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ker.vcc), np.asarray(ref.vcc),
                               rtol=1e-5, atol=1e-4)


def test_solve_joint_jit_and_vmap():
    """jit and eager may legally pick different best-of branches when the
    joint and sequential candidates tie to float precision (different
    XLA fusion/FMA choices), so assert equal solution QUALITY, not
    bitwise equality."""
    p = _zonal_problem(n=6)
    sol_e, _, s_e = spatial.solve_joint(p, 0.3, use_pallas=False)
    sol_j, _, s_j = jax.jit(lambda q: spatial.solve_joint(
        q, 0.3, use_pallas=False))(p)
    np.testing.assert_allclose(
        float(spatial.joint_carbon(p, sol_j.delta, s_j)),
        float(spatial.joint_carbon(p, sol_e.delta, s_e)), rtol=1e-4)
    np.testing.assert_allclose(
        float(spatial.joint_objective(p, sol_j.delta, s_j)),
        float(spatial.joint_objective(p, sol_e.delta, s_e)), rtol=1e-4)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), p, p)
    solb, taub, sb = spatial.solve_joint_batched(stacked, 0.3,
                                                 use_pallas=False)
    assert solb.delta.shape == (2, 6, 24)
    assert sb.shape == (2, 6)


# ------------------------------------------------- engine integration

def test_joint_rollout_through_engine():
    """SimConfig(joint_spatial=True) runs the mobility sweep end to end:
    finite ledgers, and the mobility=0 row stays close to the
    sequential-path rollout of the same scenario. Both graphs pin the
    shift to zero, but the joint graph still runs its delta refinement
    from the sequential warm start and keeps it wherever it weakly
    improves the plan (``spatial.solve_joint`` step 4). On jax 0.9 CPU
    it keeps it on both days (telemetry ``joint_winner`` = 1), which
    moves realized carbon by 5.1e-4 relative: a different plan, not
    rounding, hence rtol 1e-3."""
    from repro.sim import (SimConfig, build_batch, mobility_sweep_library,
                           rollout_batch)
    days, seeds = 2, [0]
    scens = mobility_sweep_library(days, mobilities=(0.0, 0.3))
    led = {}
    for joint in (True, False):
        cfg = SimConfig(n_clusters=4, n_campuses=2, n_zones=2,
                        pds_per_cluster=2, hist_days=10,
                        joint_spatial=joint)
        batch = build_batch(cfg, scens, seeds, days)
        _, led[joint], _ = rollout_batch(cfg, days)(batch)
    for b in (True, False):
        assert np.isfinite(np.asarray(led[b].carbon_kg)).all()
    # mobility=0 (batch row 0): s pinned to 0, delta refined (docstring)
    np.testing.assert_allclose(np.asarray(led[True].carbon_kg[0]),
                               np.asarray(led[False].carbon_kg[0]),
                               rtol=1e-3)


def test_joint_rollouts_within_half_percent_of_sequential_carbon():
    """Rollout-level tripwire on the mobility sweep: joint rollouts emit
    no more than 0.5% more carbon than the sequential pre-shift
    rollouts of the same batch. Plan-level dominance is exact
    (``test_joint_dominates_sequential_on_mobility_sweep``); realized
    carbon after sampled load and admission can move either way, so
    this only catches joint plans that systematically realize worse."""
    from repro.sim import (SimConfig, build_batch, mobility_sweep_library,
                           mobility_sweep_rows, rollout_batch)
    days, seeds = 3, [0]
    scens = mobility_sweep_library(days, mobilities=(0.0, 0.3))
    led = {}
    for joint in (True, False):
        cfg = SimConfig(n_clusters=4, n_campuses=2, n_zones=2,
                        pds_per_cluster=2, hist_days=14,
                        joint_spatial=joint)
        batch = build_batch(cfg, scens, seeds, days)
        _, led[joint], _ = rollout_batch(cfg, days)(batch)
    rows = mobility_sweep_rows(led[True], led[False],
                               [s.name for s in scens], len(seeds))
    for r in rows:
        assert r["carbon_vs_sequential_pct"] >= -0.5, r


def test_joint_with_ensemble_stage():
    """joint_spatial + n_members > 1 composes: the joint solve places
    budgets on the point forecast, the CVaR solve shapes at them."""
    from repro.sim import (SimConfig, build_batch, mobility_sweep_library,
                           rollout_batch)
    cfg = SimConfig(n_clusters=4, n_campuses=2, n_zones=2,
                    pds_per_cluster=2, hist_days=10, joint_spatial=True,
                    n_members=2)
    scens = mobility_sweep_library(1, mobilities=(0.3,))
    batch = build_batch(cfg, scens, [0], 1)
    _, led, _ = rollout_batch(cfg, 1)(batch)
    assert np.isfinite(np.asarray(led.carbon_kg)).all()


# ------------------------------------------------- spatial import cap

def test_import_cap_is_size_and_headroom_aware():
    """No cluster imports more than min(mobility * its own budget, its
    headroom) — the uniform fleet-average cap is gone."""
    n = 8
    rng = np.random.RandomState(0)
    H = 24
    capacity = jnp.asarray(8.0 + 4.0 * rng.rand(n), f32)
    u_if = jnp.asarray(2.0 + rng.rand(n, H), f32)
    # one tiny cluster (index 0): under the old uniform cap it could
    # import the fleet-average share; now its import is bounded by its
    # own mobility budget
    tau = jnp.asarray([0.5] + [20.0] * (n - 1), f32)
    eta = jnp.asarray(np.concatenate([[0.1], 2.0 + rng.rand(n - 1)])[:, None]
                      * np.ones((1, H)), f32)
    p = vcc.VCCProblem(
        eta=eta, u_if=u_if, u_if_q=u_if * 1.1, tau=tau,
        pow_nom=jnp.ones((n, H)) * 500.0, pi=jnp.ones((n, H)) * 300.0,
        u_pow_cap=capacity * 0.95, capacity=capacity,
        ratio=jnp.ones((n, H)) * 1.3,
        campus=jnp.zeros((n,), jnp.int32),
        campus_limit=jnp.asarray([1e9], f32))
    mob = 0.5
    tau2, _ = spatial.spatial_shift(p, mobility=mob)
    imported = np.asarray(tau2 - p.tau)
    lo, ub = spatial.shift_bounds(p, mob)
    assert (imported <= np.asarray(ub) + 1e-4).all()
    # the cheap tiny cluster is import-capped by its own size, not the
    # fleet average (old cap: mob * tau.sum()/n = 8.8 >> 0.25)
    assert imported[0] <= mob * float(tau[0]) + 1e-4
    # exports still bounded by the cluster's own mobility budget
    assert (-imported <= mob * np.asarray(tau) + 1e-4).all()


# ------------------------------------------------- solver layer oracle

def test_minimize_linear_matches_greedy_oracle():
    rng = np.random.RandomState(3)
    for _ in range(5):
        c = rng.randn(24)
        lo = -rng.rand(24)
        ub = rng.rand(24)
        got = np.asarray(solver.minimize_linear(
            jnp.asarray(c, f32)[None], jnp.asarray(lo, f32)[None],
            jnp.asarray(ub, f32)[None])[0])
        want = vcc.greedy_linear_reference(c, lo, ub)
        # same optimal value (the argmin may differ on ties)
        assert float((c * got).sum()) <= float((c * want).sum()) + 1e-4
        np.testing.assert_allclose(got.sum(), 0.0, atol=1e-5)
        assert (got >= lo - 1e-6).all() and (got <= ub + 1e-6).all()


def test_dual_ascent_carries_pytree_state():
    """solver.dual_ascent accepts an arbitrary pytree for x (the joint
    solve carries (delta, s))."""
    def inner(x, mu):
        a, b = x
        return (a + mu, b - 1.0)

    def dual_update(x, mu):
        return mu + 1.0

    (a, b), mu = solver.dual_ascent(inner, dual_update,
                                    (jnp.zeros(()), jnp.zeros(())),
                                    jnp.zeros(()), 3)
    assert float(mu) == 3.0 and float(a) == 3.0 and float(b) == -3.0
