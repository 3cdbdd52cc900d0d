"""jnp oracle for the fused VCC projected-gradient epoch (paper §III-C).

One epoch = ``iters`` iterations of [linearized-objective gradient →
exact bisection projection onto {sum_h delta = 0} ∩ [lo, ub]] for a tile of
clusters. This module is the SINGLE implementation of that math:
``core.vcc`` delegates its ``project_conservation`` / ``pgd_step`` to
``project_row`` / ``pgd_step_arrays``, and the Pallas kernels compute the
same math in VMEM, though not in this layout: they hold clusters on lanes
and hours on sublanes, so their sums over the 24 hours run in another
order, and they project exactly at the breakpoints where this bisects,
so the two agree to rounding. ``temp`` / ``lambda_e`` may be Python floats
or traced scalars (the day-cycle computes ``temp`` from the problem inside
jit).

Ensemble (CVaR) variant: ``pgd_step_ens_arrays`` / ``pgd_epoch_ens_ref``
take K member realizations of (eta, pow_nom) and descend a per-cluster
soft-CVaR tilt of the member costs (see ``repro.core.risk`` for the risk
model). The member reduction is *anchored on member 0*:

    x_w = x[0] + sum_k w_k * (x[k] - x[0])        (== sum_k w_k x[k])

so K identical members collapse BITWISE to the single-member gradient
(every deviation is exactly 0.0), which is the degenerate-ensemble parity
contract tested in tests/test_risk.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

f32 = jnp.float32

# softmax sharpness at risk_beta=0.5 (costs are normalized to unit mean
# absolute deviation before the tilt, so this is dimensionless)
CVAR_SHARPNESS = 4.0


def cvar_sharpness(beta):
    """Map the CVaR tail fraction ``beta`` to the soft-tilt sharpness.

    Convention (repro.core.risk): the risk objective averages the worst
    ``beta`` fraction of member outcomes — ``beta -> 1`` is the risk-
    neutral mean (sharpness 0, today's point-forecast path), smaller beta
    is more risk-averse (sharpness -> inf concentrates on the worst
    member). ``beta`` may be a Python float or a traced scalar.
    """
    b = jnp.clip(jnp.asarray(beta, f32), 0.05, 1.0)
    return CVAR_SHARPNESS * (1.0 - b) / b


def project_row(z, lo, ub, iters: int = 50):
    """Bisection projection onto {sum_h = 0} ∩ [lo, ub], rows independent.
    z/lo/ub: (n, H). Elementwise + ordered ops only: bitwise batch-invariant
    (the sim engine's batched==sequential parity contract rides on this)."""
    a = jnp.min(z, 1) - jnp.max(ub, 1)
    b = jnp.max(z, 1) - jnp.min(lo, 1)

    def body(i, ab):
        a, b = ab
        m = 0.5 * (a + b)
        f = jnp.sum(jnp.clip(z - m[:, None], lo, ub), axis=1)
        a = jnp.where(f > 0, m, a)
        b = jnp.where(f > 0, b, m)
        return a, b

    a, b = jax.lax.fori_loop(0, iters, body, (a, b))
    nu = 0.5 * (a + b)
    return jnp.clip(z - nu[:, None], lo, ub)


def pgd_step_arrays(d, eta, pi, pow_nom, tau24, price, lo, ub, lr,
                    temp, lambda_e, proj_iters: int = 50):
    """One projected-gradient step in the kernel wrappers' array layout.

    d/eta/pi/pow_nom/lo/ub: (n, H); tau24/price/lr: (n, 1); temp/lambda_e:
    scalars (possibly traced). The linearized carbon + softmax-peak gradient
    followed by the exact conservation projection.
    """
    pow_h = pow_nom + pi * d * tau24
    w = jax.nn.softmax(pow_h / temp, axis=1)
    grad = (lambda_e * eta + price * w) * pi * tau24
    return project_row(d - lr * grad, lo, ub, proj_iters)


def pgd_epoch_ref(delta, eta, pi, pow_nom, tau24, price, lo, ub, lr,
                  *, temp, lambda_e, iters: int, proj_iters: int = 50):
    """delta/eta/pi/pow_nom/lo/ub: (n, H); tau24/price/lr: (n, 1)."""

    def body(i, d):
        return pgd_step_arrays(d, eta, pi, pow_nom, tau24, price, lo, ub,
                               lr, temp, lambda_e, proj_iters)

    return jax.lax.fori_loop(0, iters, body, delta)


# ------------------------------------------------- ensemble (CVaR) variant

def member_costs(d, eta_e, pi, pow_nom_e, tau24, price, temp, lambda_e):
    """Per-(member, cluster) day cost under delta ``d``.

    eta_e/pow_nom_e: (K, n, H) member realizations; d/pi: (n, H);
    tau24/price: (n, 1). Returns (cost (K, n), pow_e (K, n, H),
    w_peak (K, n, H)) — the softmax-peak weights are reused by the
    gradient so the step computes each member's forward pass once.
    """
    pow_e = pow_nom_e + (pi * d * tau24)[None]
    w_peak = jax.nn.softmax(pow_e / temp, axis=-1)
    cost = lambda_e * jnp.sum(eta_e * pow_e, axis=-1) \
        + price[..., 0] * jnp.sum(w_peak * pow_e, axis=-1)
    return cost, pow_e, w_peak


def cvar_member_weights(cost, risk_s):
    """Soft-CVaR member weights per cluster. cost: (K, n); risk_s: scalar
    (possibly traced; 0 = uniform/risk-neutral). Logits are anchored on
    member 0 — identical members give EXACTLY zero logits (and uniform
    weights) under any reduction order, which mean-centering cannot
    guarantee — while the normalizing scale is the mean absolute
    deviation from the member mean, the SAME scale ``risk.soft_cvar``
    uses, so the step's tilt sharpness matches the reported objective
    (softmax is shift-invariant, so anchor vs mean only moves logits by a
    constant)."""
    z = cost - cost[:1]
    dev = cost - jnp.mean(cost, axis=0, keepdims=True)
    scale = jnp.mean(jnp.abs(dev), axis=0, keepdims=True) + 1e-9
    return jax.nn.softmax(risk_s * z / scale, axis=0)


def pgd_step_ens_arrays(d, eta_e, pi, pow_nom_e, tau24, price, lo, ub, lr,
                        temp, lambda_e, risk_s, proj_iters: int = 50):
    """One CVaR-aware projected-gradient step over a K-member ensemble.

    Danskin-style: member weights are treated as locally constant, so the
    descent direction is the weight-tilted member gradient. The member
    reduction is anchored on member 0 (see module docstring) so identical
    members reproduce ``pgd_step_arrays`` bitwise.
    """
    cost, pow_e, w_peak = member_costs(d, eta_e, pi, pow_nom_e, tau24,
                                       price, temp, lambda_e)
    wm = cvar_member_weights(cost, risk_s)[..., None]        # (K, n, 1)
    eta_w = eta_e[0] + jnp.sum(wm * (eta_e - eta_e[:1]), axis=0)
    w_w = w_peak[0] + jnp.sum(wm * (w_peak - w_peak[:1]), axis=0)
    grad = (lambda_e * eta_w + price * w_w) * pi * tau24
    return project_row(d - lr * grad, lo, ub, proj_iters)


def pgd_epoch_ens_ref(delta, eta_e, pi, pow_nom_e, tau24, price, lo, ub,
                      lr, *, temp, lambda_e, risk_s, iters: int,
                      proj_iters: int = 50):
    """eta_e/pow_nom_e: (K, n, H); delta/pi/lo/ub: (n, H);
    tau24/price/lr: (n, 1); temp/lambda_e/risk_s scalars (maybe traced)."""

    def body(i, d):
        return pgd_step_ens_arrays(d, eta_e, pi, pow_nom_e, tau24, price,
                                   lo, ub, lr, temp, lambda_e, risk_s,
                                   proj_iters)

    return jax.lax.fori_loop(0, iters, body, delta)


# ------------------------------------------- joint spatio-temporal variant

def joint_step_arrays(d, s, eta, pi, pow_nom, tau, u_if, u_if_q, ratio,
                      u_pow_cap, capacity, price, lr_d, temp, lambda_e,
                      drop_limit: float, proj_iters: int = 50):
    """One fused JOINT spatio-temporal step in the kernel wrappers' layout.

    d/eta/pi/pow_nom/u_if/u_if_q/ratio: (n, H); s/tau/u_pow_cap/capacity/
    price/lr_d: (n, 1); temp/lambda_e: scalars (possibly traced);
    drop_limit: static float. Everything per-cluster is fused: the
    temporal bounds lo/ub are RECOMPUTED from the shifted budget
    tau + s (the same formulas as ``core.vcc.delta_bounds``, including
    the feasibility mask that collapses hopeless clusters to {0}), the
    linearized carbon + softmax-peak gradient is taken at the shifted
    point — power = pow_nom + pi * (d * (tau+s) + s) / 24, which keeps
    the baseline pi*s/24 term of moving the flat budget itself — and
    delta is projected exactly onto its conservation slab.

    Returns (d', g_s): the updated delta tile and the per-cluster shift
    gradient (n, 1). The s update itself conserves over ALL clusters
    (sum_c s = 0), so it cannot be tiled and happens outside
    (``core.solver.joint_epochs``).
    """
    tau_s = tau + s
    t24 = jnp.clip(tau_s / 24.0, 1e-9, None)
    ub = jnp.minimum((u_pow_cap - u_if_q) / t24 - 1.0,
                     (capacity / ratio - u_if) / t24 - 1.0)
    ub = jnp.clip(ub, -drop_limit, 24.0)
    feas = (jnp.sum(ub, axis=1, keepdims=True) >= 0.0) \
        & (tau_s > 1e-6) \
        & jnp.all(ub > -drop_limit + 1e-9, axis=1, keepdims=True)
    lo = jnp.where(feas, jnp.full_like(ub, -drop_limit), 0.0)
    ub = jnp.where(feas, ub, 0.0)

    pow_h = pow_nom + pi * (d * tau_s + s) / 24.0
    w = jax.nn.softmax(pow_h / temp, axis=1)
    gcoef = (lambda_e * eta + price * w) * pi
    g_d = gcoef * (tau_s / 24.0)
    g_s = jnp.sum(gcoef * (1.0 + d), axis=1, keepdims=True) / 24.0
    d2 = project_row(d - lr_d * g_d, lo, ub, proj_iters)
    return d2, g_s
