"""Generic projected-gradient solver layer — THE core-side PGD machinery.

Every optimizer in this repo is a thin assembly over the same pieces:

  * ``project_conservation`` — exact bisection projection of each row onto
    the conservation polytope {sum = 0} ∩ [lo, ub] (the jnp oracle lives
    in ``kernels.vcc_pgd.ref`` so the Pallas kernels can mirror it op for
    op in VMEM; this module is the single core-layer entry point).
  * ``smooth_peak`` / ``peak_temperature`` — the differentiable softmax
    relaxation of the hard hourly peak and its problem-scaled temperature.
  * ``scaled_lr`` — per-cluster learning-rate normalization for the
    linearized carbon + peak gradient.
  * ``pgd_epochs`` / ``joint_epochs`` — the fused-epoch dispatch
    convention shared fleet-wide: ``use_pallas=None`` auto-selects the
    Pallas kernel on TPU and the jnp oracle elsewhere; ``interpret=True``
    drives the kernel through the Pallas interpreter (CPU parity tests).
  * ``dual_ascent`` / ``campus_dual_update`` — the outer loop: scan of
    [inner PGD epoch → multiplier update] with clipped ascent on the
    campus power couplings.
  * ``minimize_linear`` — the EXACT minimizer of a linear objective over
    the conservation polytope (the closed form of constant-gradient PGD,
    which the spatial pre-shift used to iterate).

``core.vcc`` (temporal, eq. 4), ``core.spatial`` (spatial pre-shift and
the joint spatio-temporal solve), and ``core.risk`` (CVaR ensembles) hold
NO private copies of this machinery — they parameterize it.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.vcc_pgd import ref as _pgd_ref

f32 = jnp.float32


# ------------------------------------------------------------- projections

def project_conservation(z, lo, ub, iters: int = 50):
    """Euclidean projection of each row of ``z`` onto {sum=0} ∩ [lo, ub]
    via bisection on the shift nu: sum(clip(z - nu, lo, ub)) = 0. Exact to
    bisection tolerance; elementwise + ordered ops only, so it is bitwise
    batch-invariant (the sim engine's parity contract rides on this).
    Delegates to the kernel package's jnp oracle — the Pallas kernels
    mirror the same loop in VMEM."""
    return _pgd_ref.project_row(z, lo, ub, iters)


def minimize_linear(cost, lo, ub):
    """Exact row-wise minimizer of <cost, x> over {sum x = 0} ∩ [lo, ub]
    (requires lo <= 0 <= ub so x = 0 is feasible).

    This is the closed form that constant-gradient projected descent
    converges to: start every coordinate at its lower bound and spend the
    budget ``-sum(lo)`` on coordinates in increasing-cost order (classic
    exchange argument; ``vcc.greedy_linear_reference`` is the independent
    numpy oracle). Vectorized with sort + cumsum: jit/vmap-safe, and with
    lo = ub = 0 the result is exactly 0 in every coordinate (the
    mobility=0 identity the golden trace depends on)."""
    order = jnp.argsort(cost, axis=1)
    room = jnp.take_along_axis(ub - lo, order, axis=1)
    budget = -jnp.sum(lo, axis=1, keepdims=True)
    cum = jnp.cumsum(room, axis=1)
    add = jnp.clip(budget - (cum - room), 0.0, room)
    inv = jnp.argsort(order, axis=1)
    return lo + jnp.take_along_axis(add, inv, axis=1)


# ---------------------------------------------------------- peak relaxation

def smooth_peak(pow_h, temp):
    """Differentiable softmax-peak and its weights. pow_h: (n, H)."""
    w = jax.nn.softmax(pow_h / temp, axis=1)
    return jnp.sum(w * pow_h, axis=1), w


def peak_temperature(pow_nom, temp_frac):
    """Problem-scaled softmax-peak temperature (fraction of mean power)."""
    return temp_frac * jnp.clip(pow_nom.mean(), 1e-6, None)


# --------------------------------------------------------------- lr scaling

def scaled_lr(lr, pi, tau, eta, lambda_e, lambda_p):
    """Per-cluster (n, 1) learning rate for the linearized carbon + peak
    objective: the raw gradient scales like pi * tau/24 * (lambda_e * eta
    + lambda_p), so divide it out to make ``lr`` dimensionless."""
    g_scale = jnp.clip((pi * tau[:, None] / 24.0).max(axis=1,
                                                      keepdims=True),
                       1e-9, None)
    return lr / (g_scale * jnp.clip(
        lambda_e * eta.max(axis=1, keepdims=True) + lambda_p, 1e-9,
        None))


# ------------------------------------------------------------- dual ascent

def campus_dual_update(mu, y, campus, campus_limit, rho):
    """Clipped dual ascent on the campus power couplings: mu grows where
    the summed cluster peaks ``y`` exceed the campus contract."""
    campus_pow = jax.ops.segment_sum(y, campus,
                                     num_segments=campus_limit.shape[0])
    return jnp.clip(mu + rho * (campus_pow - campus_limit)
                    / jnp.clip(campus_limit, 1e-9, None), 0.0, None)


def dual_ascent(inner, dual_update, x0, mu0, outer_iters: int,
                diag_fn=None):
    """Generic outer loop: ``outer_iters`` rounds of [x = inner(x, mu);
    mu = dual_update(x, mu)] under lax.scan. ``x`` may be any pytree
    (the joint solve carries a (delta, s) tuple).

    ``diag_fn(x_prev, x_new, mu_new) -> pytree`` (optional) emits one
    per-round diagnostic record through the scan's ys; the return becomes
    ``(x, mu, ys)`` with each ys leaf stacked (outer_iters, ...). With
    ``diag_fn=None`` the traced graph is EXACTLY the legacy two-value
    scan (the telemetry=off collapse contract rides on this)."""
    def outer(carry, _):
        x, mu = carry
        x_new = inner(x, mu)
        with jax.named_scope("solver.dual_update"):
            mu = dual_update(x_new, mu)
        y = None
        if diag_fn is not None:
            with jax.named_scope("solver.diagnostics"):
                y = diag_fn(x, x_new, mu)
        return (x_new, mu), y

    (x, mu), ys = jax.lax.scan(outer, (x0, mu0), None, length=outer_iters)
    if diag_fn is None:
        return x, mu
    return x, mu, ys


# ---------------------------------------------------------- epoch dispatch

def pgd_epochs(prob, delta, mu, lo, ub, lr_eff, temp, iters: int, *,
               use_pallas: Optional[bool] = None, interpret: bool = False):
    """``iters`` fused temporal PGD steps (gradient + exact conservation
    projection) for a VCCProblem — the fleet-wide dispatch convention:
    ``use_pallas=None`` auto-selects the Pallas kernel on TPU and the jnp
    oracle elsewhere; ``interpret=True`` runs the kernel through the
    Pallas interpreter (CPU tests). Problems carrying ensemble axes route
    to the CVaR member-reduction epoch."""
    from repro.kernels.vcc_pgd import ops as _k
    with jax.named_scope("solver.pgd_epoch"):
        return _k.pgd_epoch(prob, delta, mu, lo, ub, lr_eff, temp, iters,
                            use_pallas=use_pallas, interpret=interpret)


def joint_epochs(prob, delta, s, mu, lo_s, ub_s, lr_d, lr_s, temp,
                 iters: int, *, use_pallas: Optional[bool] = None,
                 interpret: bool = False):
    """``iters`` joint spatio-temporal steps. Each step runs the fused
    per-cluster kernel (temporal bounds recomputed from the shifted tau,
    delta gradient + projection, per-cluster shift gradient — see
    ``kernels.vcc_pgd.ref.joint_step_arrays``) and then descends +
    projects the fleet-coupled shift ``s`` onto {sum_c s = 0} ∩
    [lo_s, ub_s] OUTSIDE the cluster-tiled kernel (the conservation over
    clusters cannot be tiled)."""
    from repro.kernels.vcc_pgd import ops as _k

    def body(i, carry):
        d, sv = carry
        with jax.named_scope("solver.pgd_epoch"):
            d, g_s = _k.joint_step(prob, d, sv, mu, lr_d, temp,
                                   use_pallas=use_pallas,
                                   interpret=interpret)
        z = sv - lr_s * g_s[:, 0]
        sv = project_conservation(z[None, :], lo_s[None, :],
                                  ub_s[None, :])[0]
        return (d, sv)

    return jax.lax.fori_loop(0, iters, body, (delta, s))
