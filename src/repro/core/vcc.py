"""Risk-aware day-ahead VCC optimization (paper §III-C, eq. 4).

Per cluster c and hour h, choose flexible-usage deviations delta(c,h) from
the hourly average tau/24, minimizing

    lambda_e * sum_{c,h} eta(c,h) * [Pow(U_nom) + pi(U_nom) * delta * tau/24]
  + lambda_p * sum_c  y_c ,                    y_c >= Pow_c(h)  for all h

subject to
  * daily conservation        sum_h delta(c,h) = 0
  * power-capping (chance)    (1+delta) tau/24 <= U_pow - (U_IF)_{1-gamma}(h)
  * machine capacity          VCC(c,h) = (U_IF + (1+delta) tau/24) R(h) <= C
  * campus contracts          sum_{c in dc} y_c <= L_cont(dc)
  * delta >= -1               (flexible usage cannot go negative)

Solver: projected gradient on delta (the objective is linear + a smooth-max
peak term), with an EXACT O(iter x n x 24) bisection projection onto
{sum_h delta = 0} ∩ [lo, ub], and dual ascent on the campus coupling — all
assembled from the generic PGD pieces in ``repro.core.solver`` (this module
keeps NO private solver machinery). The fused PGD step is the CICS
fleet-scale hotspot and has a Pallas kernel (repro.kernels.vcc_pgd).

Clusters whose bounds make shaping infeasible (too full / tau ~ 0) are
excluded and get VCC = machine capacity (paper: ~10% of clusters per day).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import solver
from repro.core.admission import hour_sum
from repro.kernels.vcc_pgd import ref as _pgd_ref



@dataclass(frozen=True)
class VCCProblem:
    """Stacked fleetwide problem. n = clusters, H = 24.

    The optional ensemble axes carry K day-ahead forecast *realizations*
    (member 0 is the point forecast by convention; ``repro.core.risk``
    samples them from the empirical relative-error history) and turn the
    optimizer's objective into a soft CVaR over members — ``risk_beta`` is
    the averaged worst-tail fraction (1.0 = risk-neutral mean = the
    eq. 4 point-forecast path).
    """
    eta: jnp.ndarray          # (n, H) carbon intensity forecast kg/kWh
    u_if: jnp.ndarray         # (n, H) predicted inflexible CPU
    u_if_q: jnp.ndarray       # (n, H) (1-gamma) quantile of inflexible CPU
    tau: jnp.ndarray          # (n,)  risk-aware daily flexible CPU (alpha*T)
    pow_nom: jnp.ndarray      # (n, H) power at nominal usage (kW)
    pi: jnp.ndarray           # (n, H) power slope at nominal usage (kW/CPU)
    u_pow_cap: jnp.ndarray    # (n,)  power-capping CPU threshold
    capacity: jnp.ndarray     # (n,)  machine capacity (CPU)
    ratio: jnp.ndarray        # (n, H) reservations-to-usage ratio R(h)
    campus: jnp.ndarray       # (n,) int campus id
    campus_limit: jnp.ndarray  # (n_dc,) power limits (kW)
    lambda_e: float = 0.05    # $ / kg CO2e
    lambda_p: float = 0.1     # $ / kW / day
    # forecast-ensemble axes (None = point-forecast problem, eq. 4)
    eta_ens: Optional[jnp.ndarray] = None      # (K, n, H) intensity members
    pow_nom_ens: Optional[jnp.ndarray] = None  # (K, n, H) nominal power
    risk_beta: float = 1.0    # CVaR tail fraction (1.0 = risk-neutral)
    # paper §III-C "other constraints": bound the allowed intraday drop in
    # flexible usage (1.0 = flexible may drop to zero)
    drop_limit: float = 0.8


# Pytree registration: every field except the static drop_limit is data, so
# stacked problems can cross vmap/scan boundaries (sim engine, sweeps).
# lambda_e / lambda_p / risk_beta are data leaves — scenario sweeps batch
# them; the None ensemble fields flatten to empty subtrees until attached.
jax.tree_util.register_dataclass(
    VCCProblem,
    data_fields=["eta", "u_if", "u_if_q", "tau", "pow_nom", "pi",
                 "u_pow_cap", "capacity", "ratio", "campus", "campus_limit",
                 "lambda_e", "lambda_p", "eta_ens", "pow_nom_ens",
                 "risk_beta"],
    meta_fields=["drop_limit"])


@dataclass
class VCCSolution:
    delta: jnp.ndarray        # (n, H)
    y: jnp.ndarray            # (n,) peak power bound
    vcc: jnp.ndarray          # (n, H) hourly reservation capacity
    shaped: jnp.ndarray       # (n,) bool: cluster actively shaped
    mu: jnp.ndarray           # (n_dc,) campus duals
    objective: jnp.ndarray    # scalar


jax.tree_util.register_dataclass(
    VCCSolution,
    data_fields=["delta", "y", "vcc", "shaped", "mu", "objective"],
    meta_fields=[])


def delta_bounds(p: VCCProblem):
    """Per (c,h) bounds on delta + feasibility mask."""
    tau24 = jnp.clip(p.tau[:, None] / 24.0, 1e-9, None)
    ub_pow = (p.u_pow_cap[:, None] - p.u_if_q) / tau24 - 1.0
    ub_cap = (p.capacity[:, None] / p.ratio - p.u_if) / tau24 - 1.0
    ub = jnp.minimum(ub_pow, ub_cap)
    lo = jnp.full_like(ub, -p.drop_limit)
    ub = jnp.clip(ub, -p.drop_limit, 24.0)
    # feasible to conserve the day iff sum_h ub >= 0 and tau > 0
    feasible = (ub.sum(axis=1) >= 0.0) & (p.tau > 1e-6) \
        & jnp.all(ub > -p.drop_limit + 1e-9, axis=1)
    return lo, ub, feasible


# the core-layer projection entry point (re-exported for the tests and
# legacy import sites; repro.core.solver owns the machinery)
project_conservation = solver.project_conservation


def cluster_power(p: VCCProblem, delta):
    """Hourly power under delta (local linearization around nominal)."""
    return p.pow_nom + p.pi * delta * p.tau[:, None] / 24.0


def objective(p: VCCProblem, delta, mu, *, risk: bool = True):
    """Day cost of ``delta``. Point-forecast problems get eq. 4 exactly;
    problems carrying ensemble axes get the soft-CVaR ensemble objective
    (``risk.soft_cvar_objective``) unless ``risk=False`` forces the
    nominal (member-0/point-forecast) evaluation — which is what
    ``solve_vcc`` records in ``VCCSolution.objective`` so the field stays
    comparable (and bitwise-stable) across risk settings."""
    if risk and p.eta_ens is not None:
        from repro.core import risk as _risk
        return _risk.soft_cvar_objective(p, delta, mu)
    pow_h = cluster_power(p, delta)
    y = pow_h.max(axis=1)
    carbon = p.lambda_e * jnp.sum(p.eta * pow_h)
    peak_price = p.lambda_p + mu[p.campus]
    return carbon + jnp.sum(peak_price * y)


def cluster_objective(p: VCCProblem, delta):
    """Per-cluster nominal (eq. 4, mu-free primal) day cost of ``delta``:
    lambda_e * sum_h eta * pow + lambda_p * max_h pow, as an (n,) vector.
    Ordered reductions only (``hour_sum``; max is order-exact), so the
    telemetry channels built from it stay bitwise batch-invariant."""
    pow_h = cluster_power(p, delta)
    return p.lambda_e * hour_sum(p.eta * pow_h) \
        + p.lambda_p * pow_h.max(axis=1)


def solution_diagnostics(p: VCCProblem, delta, mu, *,
                         temp_frac: float = 0.02, proj_iters: int = 50):
    """Post-solve convergence residuals of ``(delta, mu)`` — the in-graph
    solver telemetry channels. Elementwise + ordered reductions only
    (bitwise batch-invariant; the cluster axis is NOT reduced — host-side
    consumers reduce it).

    Returns a dict of arrays:
      * ``conservation_resid`` (n,) — |sum_h delta| per cluster, the
        residual the bisection projection drives to ~0.
      * ``proj_nu_tol`` (n,) — certified tolerance of the conservation
        projection's nu bisection at the solution: the initial bracket
        width (``kernels.vcc_pgd.ref.project_row``'s [a, b]) halved
        ``proj_iters`` times. It certifies the jnp oracle's bisection;
        the Pallas kernels project exactly.
      * ``dual_resid`` (n_dc,) — relative campus-contract overshoot
        max(0, (sum_c y - L) / L) at the final point (0 = the campus
        dual ascent converged feasibly).
      * ``cvar_tail_mass`` (n,) — max soft-CVaR member weight per cluster
        at the final delta (K > 1 problems; 1/K = risk-neutral-uniform,
        -> 1 = the tilt concentrates on one worst member). Point-forecast
        problems report the degenerate 1.0.
    """
    conservation = jnp.abs(hour_sum(delta))
    lo, ub, feasible = delta_bounds(p)
    lo = jnp.where(feasible[:, None], lo, 0.0)
    ub = jnp.where(feasible[:, None], ub, 0.0)
    width0 = jnp.clip((delta.max(axis=1) - lo.min(axis=1))
                      - (delta.min(axis=1) - ub.max(axis=1)), 0.0, None)
    proj_tol = width0 * (2.0 ** -proj_iters)
    y = cluster_power(p, delta).max(axis=1)
    campus_pow = jax.ops.segment_sum(y, p.campus,
                                     num_segments=p.campus_limit.shape[0])
    dual_resid = jnp.clip((campus_pow - p.campus_limit)
                          / jnp.clip(p.campus_limit, 1e-9, None), 0.0, None)
    if p.eta_ens is not None and p.eta_ens.shape[0] > 1:
        tau24 = jnp.clip(p.tau[:, None] / 24.0, 1e-9, None)
        price = (p.lambda_p + mu[p.campus])[:, None]
        temp = solver.peak_temperature(p.pow_nom, temp_frac)
        cost, _, _ = _pgd_ref.member_costs(
            delta, p.eta_ens, p.pi, p.pow_nom_ens, tau24, price, temp,
            p.lambda_e)
        tail = _pgd_ref.cvar_member_weights(
            cost, _pgd_ref.cvar_sharpness(p.risk_beta)).max(axis=0)
    else:
        tail = jnp.ones_like(p.tau)
    return {"conservation_resid": conservation, "proj_nu_tol": proj_tol,
            "dual_resid": dual_resid, "cvar_tail_mass": tail}


def solve_vcc(p: VCCProblem, *, inner_iters: int = 80, outer_iters: int = 20,
              lr: float = 0.5, temp_frac: float = 0.02, rho: float = 0.2,
              use_pallas: Optional[bool] = None,
              interpret: bool = False, telemetry: bool = False):
    """Solve the fleetwide VCC problem (eq. 4).

    Assembly over ``repro.core.solver``: scaled-lr PGD epochs
    (``solver.pgd_epochs`` — the fleet-wide kernel dispatch convention:
    ``use_pallas=None`` auto-selects the Pallas kernel on TPU and the jnp
    oracle elsewhere; ``interpret=True`` exercises the kernel through the
    Pallas interpreter on CPU) inside ``solver.dual_ascent`` on the
    campus power couplings.

    Ensemble problems (K members attached via ``risk.attach_ensemble``)
    descend the soft-CVaR member tilt in the same epoch; a K=1 ensemble is
    statically collapsed to the point-forecast problem, so the degenerate
    risk path traces the EXACT legacy graph (bitwise contract, tested).
    ``VCCSolution.objective`` is always the nominal eq. 4 cost of the
    chosen delta (comparable across risk settings; the risk value is
    ``risk.cvar_objective``).

    ``telemetry=True`` returns ``(solution, diag)`` where ``diag`` adds
    the solver convergence channels: per-outer-round per-cluster nominal
    objective (``obj_cluster_traj`` (outer_iters, n)) and max step
    (``step_max_traj`` (outer_iters, n)) from the dual-ascent scan, plus
    ``solution_diagnostics`` at the final point. The default
    ``telemetry=False`` path traces the EXACT legacy graph (byte-identical
    compiled HLO — the repo's collapse contract, tested).
    """
    if p.eta_ens is not None and p.eta_ens.shape[0] == 1:
        p = dataclasses.replace(p, eta_ens=None, pow_nom_ens=None)
    lo, ub, feasible = delta_bounds(p)
    # neutralize infeasible clusters: bounds collapse to {0}
    lo = jnp.where(feasible[:, None], lo, 0.0)
    ub = jnp.where(feasible[:, None], ub, 0.0)
    temp = solver.peak_temperature(p.pow_nom, temp_frac)
    lr_eff = solver.scaled_lr(lr, p.pi, p.tau, p.eta, p.lambda_e,
                              p.lambda_p)

    def inner(delta, mu):
        return solver.pgd_epochs(p, delta, mu, lo, ub, lr_eff, temp,
                                 inner_iters, use_pallas=use_pallas,
                                 interpret=interpret)

    def dual_update(delta, mu):
        y = cluster_power(p, delta).max(axis=1)
        return solver.campus_dual_update(mu, y, p.campus, p.campus_limit,
                                         rho)

    if telemetry:
        def diag_fn(d_prev, d_new, _mu):
            return {"obj_cluster": cluster_objective(p, d_new),
                    "step_max": jnp.abs(d_new - d_prev).max(axis=1)}

        delta, mu, traj = solver.dual_ascent(inner, dual_update,
                                             jnp.zeros_like(p.eta),
                                             jnp.zeros_like(p.campus_limit),
                                             outer_iters, diag_fn=diag_fn)
    else:
        delta, mu = solver.dual_ascent(inner, dual_update,
                                       jnp.zeros_like(p.eta),
                                       jnp.zeros_like(p.campus_limit),
                                       outer_iters)
    pow_h = cluster_power(p, delta)
    y = pow_h.max(axis=1)
    vcc_shaped = (p.u_if + (1.0 + delta) * p.tau[:, None] / 24.0) * p.ratio
    vcc = jnp.where(feasible[:, None],
                    jnp.minimum(vcc_shaped, p.capacity[:, None]),
                    p.capacity[:, None])
    sol = VCCSolution(delta=delta, y=y, vcc=vcc, shaped=feasible, mu=mu,
                      objective=objective(p, delta, mu, risk=False))
    if not telemetry:
        return sol
    with jax.named_scope("solver.diagnostics"):
        diag = {"obj_cluster_traj": traj["obj_cluster"],
                "step_max_traj": traj["step_max"],
                **solution_diagnostics(p, delta, mu, temp_frac=temp_frac)}
    return sol, diag


def suffix_bounds(p: VCCProblem, delta_committed, hour):
    """Bounds of the masked suffix polytope at intra-day ``hour`` (0-23,
    may be traced): elapsed hours (h < hour) are pinned at the REALIZED
    deviations ``delta_committed``, remaining hours keep the day-ahead
    box. The exact bisection projection onto {sum_h delta = 0} ∩ [lo, ub]
    then enforces the TIGHTENED suffix conservation
    ``sum_{h >= hour} delta = -sum_{h < hour} delta_committed`` for free
    — no new solver math.

    Feasibility needs both box sums to bracket zero (the day-ahead check
    only needs ``sum ub >= 0`` because its lo is the constant
    -drop_limit); clusters whose realized prefix cannot be conserved any
    more are pinned to ``delta_committed`` everywhere — the projection
    returns a lo==ub row exactly, so infeasible clusters simply keep
    their current plan. Returns (lo, ub, feasible)."""
    mask = jnp.arange(24) >= hour                       # True = remaining
    lo, ub, feasible = delta_bounds(p)
    lo = jnp.where(mask[None, :], lo, delta_committed)
    ub = jnp.where(mask[None, :], ub, delta_committed)
    feasible = feasible & (hour_sum(lo) <= 1e-6) \
        & (hour_sum(ub) >= -1e-6)
    lo = jnp.where(feasible[:, None], lo, delta_committed)
    ub = jnp.where(feasible[:, None], ub, delta_committed)
    return lo, ub, feasible


def solve_vcc_suffix(p: VCCProblem, delta0, mu0, hour, *,
                     inner_iters: int = 8, outer_iters: int = 2,
                     lr: float = 0.5, temp_frac: float = 0.02,
                     rho: float = 0.2, use_pallas: Optional[bool] = None,
                     interpret: bool = False) -> VCCSolution:
    """Warm-started intra-day re-solve of the REMAINING hours' VCC.

    ``delta0`` (n, 24): the current plan with elapsed columns (h < hour)
    replaced by the realized deviations; ``mu0``: campus duals carried
    from the day-ahead solve (the warm start is what makes the short
    schedule converge). Machinery is exactly ``solve_vcc``'s —
    ``solver.pgd_epochs`` inside ``solver.dual_ascent`` with the
    projection acting on the masked suffix polytope (``suffix_bounds``)
    — but the default schedule is outer 2 x inner 8 = 16 PGD steps vs
    the full solve's 20 x 80 = 1600."""
    n, H = p.eta.shape
    lo, ub, feasible = suffix_bounds(p, delta0, hour)
    temp = solver.peak_temperature(p.pow_nom, temp_frac)
    n_dc = p.campus_limit.shape[0]
    lr_eff = solver.scaled_lr(lr, p.pi, p.tau, p.eta, p.lambda_e,
                              p.lambda_p)

    def inner(delta, mu):
        return solver.pgd_epochs(p, delta, mu, lo, ub, lr_eff, temp,
                                 inner_iters, use_pallas=use_pallas,
                                 interpret=interpret)

    def dual_update(delta, mu):
        y = cluster_power(p, delta).max(axis=1)
        return solver.campus_dual_update(mu, y, p.campus, p.campus_limit,
                                         rho)

    delta, mu = solver.dual_ascent(inner, dual_update, delta0, mu0,
                                   outer_iters)
    pow_h = cluster_power(p, delta)
    y = pow_h.max(axis=1)
    vcc_shaped = (p.u_if + (1.0 + delta) * p.tau[:, None] / 24.0) * p.ratio
    vcc = jnp.where(feasible[:, None],
                    jnp.minimum(vcc_shaped, p.capacity[:, None]),
                    p.capacity[:, None])
    return VCCSolution(delta=delta, y=y, vcc=vcc, shaped=feasible, mu=mu,
                       objective=objective(p, delta, mu, risk=False))


def solve_vcc_batched(p: VCCProblem, **kw) -> VCCSolution:
    """vmap solve_vcc over a leading (scenario x seed) axis of a stacked
    VCCProblem (requires the pytree registration above)."""
    return jax.vmap(lambda q: solve_vcc(q, **kw))(p)


def synthetic_problem(n: int = 12, seed: int = 7, n_campuses: int = 2
                      ) -> VCCProblem:
    """The canonical synthetic fleetwide problem shared by the solver
    tests (tests/test_stages_parity.py, tests/test_risk.py,
    tests/test_mpc.py, ...) and ``chip_smoke.py``: a diurnal intensity
    curve + noisy inflexible load with uncontended campus limits and
    drop_limit=1.0."""
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 4)
    H = 24
    eta = jnp.abs(0.3 + 0.25 * jnp.sin(jnp.linspace(0, 2 * jnp.pi, H))[None]
                  + 0.05 * jax.random.normal(ks[0], (n, H)))
    u_if = 0.4 + 0.05 * jax.random.normal(ks[1], (n, H))
    tau = 2.0 + 3.0 * jax.random.uniform(ks[2], (n,))
    pow_nom = 500.0 + 20.0 * jax.random.normal(ks[3], (n, H))
    import numpy as np
    return VCCProblem(
        eta=eta, u_if=u_if, u_if_q=u_if * 1.1, tau=tau,
        pow_nom=pow_nom, pi=jnp.full((n, H), 300.0),
        u_pow_cap=jnp.full((n,), 0.95), capacity=jnp.full((n,), 1.3),
        ratio=jnp.full((n, H), 1.3),
        campus=jnp.asarray(np.arange(n) % n_campuses, jnp.int32),
        campus_limit=jnp.full((n_campuses,), 1e9),
        lambda_e=0.1, lambda_p=0.05, drop_limit=1.0)


def synthetic_zonal_problem(n: int = 12, seed: int = 3,
                            n_campuses: int = 2) -> VCCProblem:
    """``synthetic_problem`` with a strong spatial carbon gradient
    (alternating dirty/clean clusters) and tightened machine capacity, so
    temporal shaping saturates in the dirty clusters and exporting budget
    is what a spatial/joint optimizer can exploit (tests/test_joint.py)."""
    p = synthetic_problem(n, seed=seed, n_campuses=n_campuses)
    scale = jnp.where(jnp.arange(n) % 2 == 0, 2.2, 0.5)[:, None]
    return dataclasses.replace(p, eta=p.eta * scale,
                               capacity=p.capacity * 0.85)


# ------------------------------------------------- exact greedy reference

def greedy_linear_reference(eta_pi, lo, ub):
    """Exact minimizer of sum_h c_h * delta_h with sum delta = 0, box
    bounds, for ONE cluster (numpy-style; the independent oracle the tests
    hold PGD and ``solver.minimize_linear`` against).

    Classic exchange argument: push delta to ub at the cheapest hours and lo
    at the most expensive, with one marginal hour balancing the budget.
    """
    import numpy as np
    c = np.asarray(eta_pi, dtype=np.float64)
    lo = np.asarray(lo, np.float64).copy()
    ub = np.asarray(ub, np.float64).copy()
    order = np.argsort(c)
    delta = lo.copy()                 # start everything at lower bound
    budget = -delta.sum()             # must add this much
    for h in order:                   # fill cheapest hours first
        room = ub[h] - delta[h]
        add = min(room, budget)
        delta[h] += add
        budget -= add
        if budget <= 1e-12:
            break
    return delta
