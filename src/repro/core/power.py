"""Power-domain power models (paper §III-A, following ref [20]).

A PD's power is a piecewise-linear function of its CPU usage; the paper
reports daily MAPE < 5% for >95% of PDs, and uses the local slope
``pi^(PD)(u)`` to map CPU deltas to power deltas. Cluster-level slope is the
lambda-weighted sum over its PDs (PD usage fractions are near-constant).

Models are refit daily, vmapped across every PD in the fleet.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

f32 = jnp.float32
N_BREAKS = 3            # interior breakpoints -> 4 linear segments


@dataclass(frozen=True)
class PDTruth:
    """Ground-truth (simulator) PD power curve parameters."""
    idle_kw: jnp.ndarray        # (pds,)
    slope_kw: jnp.ndarray       # (pds,) average dynamic slope
    curve: jnp.ndarray          # (pds,) curvature in [0.7, 1.3] (u^curve)


def simulate_pd_power(key, truth: PDTruth, cpu: jnp.ndarray,
                      noise: float = 0.01) -> jnp.ndarray:
    """True PD power for CPU usage series. cpu: (pds, t) in [0,1]."""
    base = truth.idle_kw[:, None] + truth.slope_kw[:, None] * \
        jnp.power(jnp.clip(cpu, 0.0, 1.0), truth.curve[:, None])
    eps = 1.0 + noise * jax.random.normal(key, cpu.shape)
    return base * eps


def _basis(u: jnp.ndarray, breaks: jnp.ndarray) -> jnp.ndarray:
    """[1, u, relu(u - b_k)...] hinge basis. u: (t,); breaks: (K,)."""
    cols = [jnp.ones_like(u), u]
    for k in range(breaks.shape[0]):
        cols.append(jnp.maximum(u - breaks[k], 0.0))
    return jnp.stack(cols, axis=-1)          # (t, K+2)


def _solve_spd(A: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Unrolled Cholesky solve for a small SPD system (K+2 = 5 here).

    Scalar elementwise ops in a fixed order — unlike LAPACK ``solve`` (and
    matmul normal equations), the result is bitwise identical under vmap,
    which the sim engine's batched-vs-sequential parity guarantee needs.
    """
    n = A.shape[-1]
    L = [[None] * n for _ in range(n)]
    for i in range(n):
        for j in range(i + 1):
            s = A[..., i, j]
            for k in range(j):
                s = s - L[i][k] * L[j][k]
            if i == j:
                L[i][j] = jnp.sqrt(jnp.clip(s, 1e-12, None))
            else:
                L[i][j] = s / L[j][j]
    y = []
    for i in range(n):
        s = b[..., i]
        for k in range(i):
            s = s - L[i][k] * y[k]
        y.append(s / L[i][i])
    x = [None] * n
    for i in reversed(range(n)):
        s = y[i]
        for k in range(i + 1, n):
            s = s - L[k][i] * x[k]
        x[i] = s / L[i][i]
    return jnp.stack(x, axis=-1)


def _pairwise_sum(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Sum over ``axis`` by a fixed tree of elementwise adds (halves,
    zero-padded when odd). Each add is elementwise, so the result is the
    same bits at any batch extent, layout or device count."""
    x = jnp.moveaxis(x, axis, 0)
    while x.shape[0] > 1:
        if x.shape[0] % 2:
            x = jnp.concatenate([x, jnp.zeros_like(x[:1])])
        half = x.shape[0] // 2
        x = x[:half] + x[half:]
    return x[0]


def fit_pd_model(cpu: jnp.ndarray, power: jnp.ndarray
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Least-squares piecewise-linear fit for ONE pd.
    cpu, power: (t,). Returns (coef (K+2,), breaks (K,))."""
    qs = jnp.linspace(0.0, 1.0, N_BREAKS + 2)[1:-1]
    breaks = jnp.quantile(cpu, qs)
    # Fit in window coordinates x = (u - lo) / span in [0, 1]. In raw
    # usage a PD that idles in a narrow band makes the constant and
    # linear columns nearly collinear, and float32 normal equations then
    # return coefficients of 1e10 whose predictions are off by orders of
    # magnitude, and differently on every backend. The ridge is relative
    # to the window length, which bounds cond(XtX) near 1e4 when
    # breakpoints coincide.
    lo = jnp.min(cpu)
    span = jnp.clip(jnp.max(cpu) - lo, 1e-6, None)
    X = _basis((cpu - lo) / span, (breaks - lo) / span)
    # normal equations summed by _pairwise_sum, not dots or reduces: XLA
    # picks their accumulation order from the layout of the batch around
    # them (on a TPU v5e a batch of 11 rollouts rounded differently from
    # the same rollouts in a batch of 44)
    XtX = _pairwise_sum(X[..., :, None] * X[..., None, :], axis=-3) \
        + 1e-4 * cpu.shape[-1] * jnp.eye(X.shape[-1])
    Xty = _pairwise_sum(X * power[..., None], axis=-2)
    cx = _solve_spd(XtX, Xty)
    # back to usage coordinates: relu(x - q_k) = relu(u - b_k) / span
    coef = jnp.concatenate([cx[:1] - cx[1:2] * lo / span, cx[1:] / span])
    return coef, breaks


fit_pd_models = jax.jit(jax.vmap(fit_pd_model))      # (pds, t) -> batched


def pd_power(coef, breaks, u):
    """Predicted power at usage u (broadcasts over u).

    Evaluated as an ordered elementwise chain, not `basis @ coef`: a dot's
    accumulation order varies with surrounding batch dims, and the sim
    engine requires bitwise batched-vs-sequential parity."""
    p = coef[0] + coef[1] * u
    for k in range(breaks.shape[0]):
        p = p + coef[2 + k] * jnp.maximum(u - breaks[k], 0.0)
    return p


def pd_slope(coef, breaks, u):
    """Local slope pi(u) = d power / d usage."""
    shp = u.shape
    uu = u.reshape(-1)
    s = jnp.broadcast_to(coef[1], uu.shape)
    for k in range(breaks.shape[0]):
        s = s + jnp.where(uu > breaks[k], coef[2 + k], 0.0)
    return s.reshape(shp)


pd_power_b = jax.vmap(pd_power)          # batched over pds
pd_slope_b = jax.vmap(pd_slope)


def daily_mape(coef, breaks, cpu, power) -> jnp.ndarray:
    pred = pd_power(coef, breaks, cpu)
    return jnp.mean(jnp.abs(pred - power) / jnp.clip(power, 1e-6, None))


daily_mape_b = jax.jit(jax.vmap(daily_mape))


# ------------------------------------------------------- cluster aggregation

def usage_fractions(cpu_by_pd: jnp.ndarray) -> jnp.ndarray:
    """lambda^(PD): time-average usage fraction of each PD within a cluster.
    cpu_by_pd: (pds, t) -> (pds,). Paper: median variation ~1%."""
    tot = jnp.clip(cpu_by_pd.sum(axis=0, keepdims=True), 1e-9, None)
    return (cpu_by_pd / tot).mean(axis=1)


def cluster_power(coef, breaks, lam, u_cluster):
    """Cluster power at cluster CPU u (sum over PDs at u*lambda)."""
    u_pd = lam[:, None] * jnp.atleast_1d(u_cluster)[None, :]
    p = jax.vmap(pd_power, in_axes=(0, 0, 0))(coef, breaks, u_pd)
    return p.sum(axis=0).reshape(jnp.shape(u_cluster))


def cluster_slope(coef, breaks, lam, u_cluster):
    """pi^(c)(u) = sum_PD pi^(PD)(lambda*u) * lambda  (paper eq. 1)."""
    u_pd = lam[:, None] * jnp.atleast_1d(u_cluster)[None, :]
    s = jax.vmap(pd_slope, in_axes=(0, 0, 0))(coef, breaks, u_pd)
    s = (s * lam[:, None]).sum(axis=0)
    return s.reshape(jnp.shape(u_cluster))
