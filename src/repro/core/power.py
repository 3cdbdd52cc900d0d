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
import numpy as np

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


BREAK_QS = np.linspace(0.0, 1.0, N_BREAKS + 2, dtype=np.float32)[1:-1]


def quantiles_sorted(s: jnp.ndarray, qs, f=lambda v: v) -> jnp.ndarray:
    """``jnp.quantile(f(x), qs, axis=-1, method="linear")``, quantiles
    last, from ``s``, ``x`` sorted along its last axis.

    ``f`` is an elementwise non-decreasing map, which float32 rounding
    keeps non-decreasing, so ``sort(f(x)) == f(sort(x))``: only the two
    order statistics each quantile reads are mapped. The indices and
    weights are ``jnp.quantile``'s own and so is the combination
    ``low * (1 - w) + high * w``, so the result is the same bits for
    NaN-free ``x``. ``f`` may add leading axes ((..., m) -> (..., p, m)).
    Only values are read, so ``s`` may come from an unstable sort (on a
    TPU a stable one sorts an index operand too); the two differ only in
    where +0.0 and -0.0 land, which compare equal.
    """
    n = s.shape[-1]
    q = np.asarray(qs, np.float32) * np.float32(n - 1)
    low, high = np.floor(q), np.ceil(q)
    high_w = q - low
    low_w = np.float32(1.0) - high_w
    low, high = (np.clip(i, 0, n - 1).astype(np.int32) for i in (low, high))
    return f(s[..., low]) * low_w + f(s[..., high]) * high_w


def _columns(x: jnp.ndarray, q: jnp.ndarray, y: jnp.ndarray,
             c: np.ndarray) -> jnp.ndarray:
    """Columns ``c`` (static) of ``[X, y]``, (t, len(c)), where ``X`` is
    the hinge basis ``[1, x, relu(x - q_k)...]`` (t, K+2) and column K+2
    is ``y``. One broadcast expression, so a product of two of them is one
    multiply; ``x`` is ``max(x - 0, -inf)``, the same bits."""
    m = q.shape[-1] + 2
    zero = jnp.zeros_like(q[..., :1])
    shift = jnp.concatenate([zero, zero, q], axis=-1)[
        ..., np.minimum(c, m - 1)]
    floor = np.where(c >= 2, 0.0, -np.inf).astype(np.float32)
    v = jnp.maximum(x[..., :, None] - shift[..., None, :], floor)
    v = jnp.where(c == 0, np.float32(1.0), v)
    return jnp.where(c == m, y[..., :, None], v)


def normal_equations(x: jnp.ndarray, q: jnp.ndarray, y: jnp.ndarray
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(X^T X, X^T y)`` of the hinge basis ``X = [1, x, relu(x -
    q_k)...]``; x, y (t,), q (K,). Neither ``X`` nor its outer products
    are formed.

    The same bits as ``_pairwise_sum`` of the full outer products
    ``X[:, :, None] * X[:, None, :]`` and ``X * y[:, None]``: products
    commute, so only the upper triangle of ``X^T X`` is summed, and its
    (0, 0) entry, a sum of t ones, is t exactly. That is m(m+1)/2 - 1 + m
    summed columns (19 at m = 5, not 30). They are one multiply of two
    broadcast operands, halved by the same tree: on a CPU, XLA contracts
    a multiply fused into an add into an FMA by the shape of the fusion,
    so the products keep the form of the outer product's, not only its
    values."""
    t, m = x.shape[-1], q.shape[-1] + 2
    iu, ju = np.triu_indices(m)         # i <= j; (0, 0) first, left out
    a = np.concatenate([iu[1:], np.arange(m)])      # pairs (a, b) of the
    b = np.concatenate([ju[1:], np.full(m, m)])     # columns [X, y]
    sums = _pairwise_sum(_columns(x, q, y, a) * _columns(x, q, y, b),
                         axis=-2)
    # [t, sum of each upper-triangle product], mirrored into m x m
    ent = jnp.concatenate(
        [jnp.full(sums.shape[:-1] + (1,), t, sums.dtype),
         sums[..., :iu.size - 1]], axis=-1)
    where = np.zeros((m, m), np.int32)
    where[iu, ju] = where[ju, iu] = np.arange(iu.size)
    return ent[..., where], sums[..., iu.size - 1:]


def fit_pd_window(cpu: jnp.ndarray, power: jnp.ndarray, lo, hi,
                  breaks: jnp.ndarray) -> jnp.ndarray:
    """Least-squares piecewise-linear fit for ONE pd, given its window's
    order statistics: ``lo``/``hi`` its min and max, ``breaks`` (K,) its
    ``BREAK_QS`` quantiles. cpu, power: (t,). Returns coef (K+2,)."""
    # Fit in window coordinates x = (u - lo) / span in [0, 1]. In raw
    # usage a PD that idles in a narrow band makes the constant and
    # linear columns nearly collinear, and float32 normal equations then
    # return coefficients of 1e10 whose predictions are off by orders of
    # magnitude, and differently on every backend. The ridge is relative
    # to the window length, which bounds cond(XtX) near 1e4 when
    # breakpoints coincide.
    span = jnp.clip(hi - lo, 1e-6, None)
    # normal equations summed by _pairwise_sum, not dots or reduces: XLA
    # picks their accumulation order from the layout of the batch around
    # them (on a TPU v5e a batch of 11 rollouts rounded differently from
    # the same rollouts in a batch of 44)
    XtX, Xty = normal_equations((cpu - lo) / span, (breaks - lo) / span,
                                power)
    XtX = XtX + 1e-4 * cpu.shape[-1] * jnp.eye(breaks.shape[-1] + 2)
    cx = _solve_spd(XtX, Xty)
    # back to usage coordinates: relu(x - q_k) = relu(u - b_k) / span
    return jnp.concatenate([cx[:1] - cx[1:2] * lo / span, cx[1:] / span])


def fit_pd_model(cpu: jnp.ndarray, power: jnp.ndarray
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Least-squares piecewise-linear fit for ONE pd.
    cpu, power: (t,). Returns (coef (K+2,), breaks (K,))."""
    s = jnp.sort(cpu, stable=False)
    breaks = quantiles_sorted(s, BREAK_QS)
    return fit_pd_window(cpu, power, s[0], s[-1], breaks), breaks


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
