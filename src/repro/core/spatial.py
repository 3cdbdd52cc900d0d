"""Spatial flexibility: day-ahead shifting of flexible compute across
clusters (paper §V names this as the planned next step).

Two layers, both assemblies over ``repro.core.solver``:

* ``spatial_shift`` — the decoupled GREEDY pre-shift: move daily flexible
  budgets tau toward carbon-cheap clusters (exact linear minimizer over
  the fleet-conservation polytope), then run the paper's temporal VCC
  optimization on the shifted budgets. Fast, but blind to the temporal
  solve: a cluster whose green hours are capacity-saturated still imports
  work it cannot shape into them.

* ``solve_joint`` — JOINT spatio-temporal optimization: the temporal
  deviations delta (n, H) and the daily shift s (n,) are descended
  TOGETHER, with the temporal bounds recomputed from the shifted budgets
  tau + s inside every fused step (``kernels.vcc_pgd.joint_step``). The
  sequential two-phase answer seeds the joint descent and a best-of
  safeguard keeps the result from ever being worse than it (on both the
  nominal objective and its carbon term). A static ``mobility == 0``
  collapses to the EXACT legacy temporal graph, bitwise — the same
  contract the K=1 risk ensemble keeps.

Shift bounds: a cluster may export at most ``mobility * tau_c`` (the
location-flexible fraction of its own budget) and import at most
``min(mobility * tau_c, headroom_c)`` — size-aware (proportional to the
cluster's own flexible budget) and headroom-aware (it must have the spare
daily machine capacity to actually run the work).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import solver, vcc
from repro.core.vcc import VCCProblem, VCCSolution

f32 = jnp.float32


def carbon_price(p: VCCProblem) -> jnp.ndarray:
    """(n,) marginal kgCO2e of placing one CPU-day at each cluster
    (before temporal shaping): mean_h eta(c,h) * pi(c,h)."""
    return (p.eta * p.pi).mean(axis=1)


def shift_bounds(p: VCCProblem, mobility) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-cluster (lo, ub) for the daily shift s (negative = export).

    Export is capped at ``mobility * tau_c``; import at
    ``min(mobility * tau_c, headroom_c)`` where headroom is the spare
    daily machine capacity beyond the cluster's own flexible budget. Both
    caps scale with the cluster's own size (a uniform fleet-average
    import cap would let small clusters import work they cannot hold)."""
    room_h = jnp.clip(p.capacity[:, None] / p.ratio - p.u_if, 0.0, None)
    headroom = jnp.clip(room_h.sum(axis=1) - p.tau, 0.0, None)
    lo = -mobility * p.tau
    ub = jnp.minimum(mobility * p.tau, headroom)
    return lo, ub


def spatial_shift(p: VCCProblem, *, mobility: float = 0.3
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Greedy pre-shift: returns (tau_shifted (n,), carbon_price (n,)).

    The objective is linear in s (constant gradient), so the minimizer
    over {sum_c s = 0} ∩ [lo, ub] is exact (``solver.minimize_linear`` —
    the closed form of the constant-gradient PGD loop this used to
    iterate). ``mobility`` may be a float or a traced scalar; mobility=0
    collapses the bounds to {0} and returns tau bitwise."""
    price = carbon_price(p)
    lo, ub = shift_bounds(p, mobility)
    shift = solver.minimize_linear(price[None, :], lo[None, :],
                                   ub[None, :])[0]
    return jnp.clip(p.tau + shift, 0.0, None), price


def spatial_shift_batched(p: VCCProblem, *, mobility=0.3):
    """vmap spatial_shift over a leading batch axis of a stacked VCCProblem.
    ``mobility`` may be a scalar or a (batch,) array (scenario sweeps)."""
    mob = jnp.asarray(mobility, f32)
    if mob.ndim == 0:
        mob = jnp.broadcast_to(mob,
                               (jax.tree_util.tree_leaves(p)[0].shape[0],))
    return jax.vmap(lambda q, m: spatial_shift(q, mobility=m))(p, mob)


# ------------------------------------------------- joint spatio-temporal

def joint_power(p: VCCProblem, delta, s):
    """Hourly power under (delta, s): the local linearization around the
    ORIGINAL nominal point, including the baseline term pi * s / 24 from
    moving the flat daily budget itself — the term the sequential
    pre-shift path ignores (its pow_nom is linearized at the unshifted
    nominal)."""
    return p.pow_nom + p.pi * (delta * (p.tau + s)[:, None]
                               + s[:, None]) / 24.0


def joint_carbon(p: VCCProblem, delta, s):
    """Model-consistent expected carbon (kg) of the joint point."""
    return jnp.sum(p.eta * joint_power(p, delta, s))


def joint_objective(p: VCCProblem, delta, s, mu=None):
    """Nominal day cost of (delta, s): carbon price + hard hourly peak
    (eq. 4 shape). ``mu=None`` evaluates the primal objective (lambda_p
    only) — the scale both best-of candidates are compared on."""
    pow_h = joint_power(p, delta, s)
    y = pow_h.max(axis=1)
    price = p.lambda_p if mu is None else p.lambda_p + mu[p.campus]
    return p.lambda_e * joint_carbon(p, delta, s) + jnp.sum(price * y)


def solve_joint(p: VCCProblem, mobility, *, inner_iters: int = 80,
                outer_iters: int = 20, joint_inner: int = 25,
                joint_outer: int = 8, lr: float = 0.5, lr_s: float = 0.15,
                temp_frac: float = 0.02, rho: float = 0.2,
                use_pallas: Optional[bool] = None, interpret: bool = False,
                telemetry: bool = False):
    """Joint spatio-temporal VCC optimization.

    Returns (solution, tau_joint (n,), s (n,)): the temporal deviations
    and VCC curves of ``solution`` are consistent with the SHIFTED daily
    budgets ``tau_joint = clip(tau + s, 0)``.

    Pipeline:
      1. static collapse — a Python-scalar ``mobility == 0`` returns the
         EXACT legacy temporal solve (bitwise; the spatial variable never
         enters the graph — the K=1 risk-ensemble contract, spatially);
      2. sequential warm start — greedy ``spatial_shift`` + temporal
         ``solve_vcc`` at the shifted budgets (the pre-shift baseline);
      3. joint refinement — ``solver.dual_ascent`` over
         ``solver.joint_epochs``: fused steps recompute the temporal
         bounds from tau + s and descend (delta, s) together, so budget
         flows out of clusters whose green hours are saturated;
      4. best-of safeguard — the joint point is kept only if it (weakly)
         improves BOTH the nominal objective and its carbon term over the
         warm start, evaluated model-consistently (``joint_objective`` /
         ``joint_carbon``, which include the pi*s/24 baseline term the
         sequential pass ignores). Joint is therefore never worse than
         sequential by construction. The switch is fleet-wide and
         all-or-nothing — conservative by design: in slack fleets where
         the greedy pre-shift is already optimal (bounds not binding)
         the joint path simply reduces to the sequential answer; it pays
         off in supply-tight regimes (see
         ``vcc.synthetic_zonal_problem`` / the capacity-squeezed
         mobility sweep), which is where the gates measure it.

    ``telemetry=True`` appends a solver-diagnostics dict to the return
    (``(sol, tau_j, s, diag)``): the warm-start temporal solve's
    convergence trajectories, ``vcc.solution_diagnostics`` at the FINAL
    joint-consistent point, and ``joint_winner`` — 1.0 when the best-of
    safeguard kept the joint refinement, 0.0 when it fell back to the
    sequential warm start (the static mobility==0 collapse reports 0.0:
    the joint path never ran). ``telemetry=False`` (default) traces the
    exact legacy graph.
    """
    if not isinstance(mobility, jnp.ndarray) and float(mobility) == 0.0:
        sol = vcc.solve_vcc(p, inner_iters=inner_iters,
                            outer_iters=outer_iters, lr=lr,
                            temp_frac=temp_frac, rho=rho,
                            use_pallas=use_pallas, interpret=interpret,
                            telemetry=telemetry)
        if telemetry:
            sol, diag = sol
            diag["joint_winner"] = jnp.zeros((), f32)
            return sol, p.tau, jnp.zeros_like(p.tau), diag
        return sol, p.tau, jnp.zeros_like(p.tau)

    mob = jnp.asarray(mobility, f32)
    # 2. sequential two-phase warm start
    tau_sh, _ = spatial_shift(p, mobility=mob)
    p_seq = dataclasses.replace(p, tau=tau_sh)
    sol_seq = vcc.solve_vcc(p_seq, inner_iters=inner_iters,
                            outer_iters=outer_iters, lr=lr,
                            temp_frac=temp_frac, rho=rho,
                            use_pallas=use_pallas, interpret=interpret,
                            telemetry=telemetry)
    diag_seq = None
    if telemetry:
        sol_seq, diag_seq = sol_seq
    lo_s, ub_s = shift_bounds(p, mob)
    s0 = jnp.clip(tau_sh - p.tau, lo_s, ub_s)

    # 3. joint refinement from (delta_seq, s0)
    temp = solver.peak_temperature(p.pow_nom, temp_frac)
    lr_d = solver.scaled_lr(lr, p.pi, p.tau, p.eta, p.lambda_e, p.lambda_p)
    # shift-gradient scale: g_s ~ lambda_e * mean_h(eta pi) + price pi / 24
    g_norm = jnp.clip((p.lambda_e * (p.eta * p.pi).mean(axis=1)
                       + p.lambda_p * p.pi.mean(axis=1) / 24.0).max(),
                      1e-9, None)
    lr_s_eff = lr_s * jnp.clip(p.tau.mean(), 1e-6, None) / g_norm

    def inner(x, mu):
        d, s = x
        return solver.joint_epochs(p, d, s, mu, lo_s, ub_s, lr_d, lr_s_eff,
                                   temp, joint_inner, use_pallas=use_pallas,
                                   interpret=interpret)

    def dual_update(x, mu):
        d, s = x
        y = joint_power(p, d, s).max(axis=1)
        return solver.campus_dual_update(mu, y, p.campus, p.campus_limit,
                                         rho)

    (d_j, s_j), mu_j = solver.dual_ascent(inner, dual_update,
                                          (sol_seq.delta, s0), sol_seq.mu,
                                          joint_outer)

    # 4. best-of safeguard: joint must (weakly) dominate the warm start
    take = (joint_objective(p, d_j, s_j) <= joint_objective(p, sol_seq.delta,
                                                            s0)) \
        & (joint_carbon(p, d_j, s_j) <= joint_carbon(p, sol_seq.delta, s0))
    delta = jnp.where(take, d_j, sol_seq.delta)
    s = jnp.where(take, s_j, s0)
    mu = jnp.where(take, mu_j, sol_seq.mu)

    tau_j = jnp.clip(p.tau + s, 0.0, None)
    pf = dataclasses.replace(p, tau=tau_j)
    lo, ub, feasible = vcc.delta_bounds(pf)
    delta = jnp.where(feasible[:, None], delta, 0.0)
    pow_h = joint_power(p, delta, s)
    y = pow_h.max(axis=1)
    vcc_shaped = (pf.u_if + (1.0 + delta) * tau_j[:, None] / 24.0) * pf.ratio
    vcc_curve = jnp.where(feasible[:, None],
                          jnp.minimum(vcc_shaped, pf.capacity[:, None]),
                          pf.capacity[:, None])
    sol = VCCSolution(delta=delta, y=y, vcc=vcc_curve, shaped=feasible,
                      mu=mu, objective=joint_objective(p, delta, s, mu))
    if telemetry:
        with jax.named_scope("solver.diagnostics"):
            diag = {"obj_cluster_traj": diag_seq["obj_cluster_traj"],
                    "step_max_traj": diag_seq["step_max_traj"],
                    **vcc.solution_diagnostics(pf, delta, mu,
                                               temp_frac=temp_frac),
                    "joint_winner": take.astype(f32)}
        return sol, tau_j, s, diag
    return sol, tau_j, s


def solve_joint_batched(p: VCCProblem, mobility, **kw):
    """vmap solve_joint over a leading batch axis of a stacked VCCProblem.
    ``mobility`` may be a scalar or a (batch,) array (mobility sweeps);
    batched mobility is always traced, so the joint graph runs for every
    row (mobility=0 rows pin s to 0 through the bounds)."""
    mob = jnp.asarray(mobility, f32)
    if mob.ndim == 0:
        mob = jnp.broadcast_to(mob,
                               (jax.tree_util.tree_leaves(p)[0].shape[0],))
    return jax.vmap(lambda q, m: solve_joint(q, m, **kw))(p, mob)
