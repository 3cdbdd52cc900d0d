"""Day-ahead load forecasting (paper §III-B1).

Per cluster, the pipeline forecasts:
  (i)   hourly inflexible CPU usage  U_IF(h)      [ (days,24) history ]
  (ii)  daily flexible compute usage T_UF(d)      [ (days,) ]
  (iii) daily total reservations     T_R(d)       [ (days,) ]
  (iv)  reservations-to-usage ratio  R(h) >= 1    [ linear in log usage ]

Method (paper): two-step. First a weekly forecast = EWMA weekly mean
(half-life ~0.5 weeks) x EWMA intra-week hourly/daily factors (half-life ~4
weeks); then a linear previous-day deviation corrector. EWMA half-lives are
tunable (the paper selects them by out-of-sample MAPE exploration —
``calibrate_half_lives``). Risk terms: trailing relative-error quantiles give
the 97%-ile capacity requirement Theta (eq. 2) and the (1-gamma) inflexible
quantile for power capping; eq. (3) yields the alpha inflation factor.

Everything is vmap-friendly: functions take one cluster's history;
``stages.forecast_stage`` vmaps them across clusters.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.admission import hour_sum

f32 = jnp.float32


def ewma_alpha(half_life) -> jnp.ndarray:
    """One-step EWMA weight for a given half-life, measured in UPDATE
    STEPS (weeks for the weekly rescan, days for the streaming carry).
    Shared by the batch ``ewma`` scan below and the O(1) incremental
    ``core.stats`` estimators — one expression, so the two paths apply
    bitwise-identical recursions."""
    return 1.0 - jnp.exp(jnp.log(0.5) / jnp.maximum(half_life, 1e-3))


def ewma_update(level: jnp.ndarray, x: jnp.ndarray, alpha) -> jnp.ndarray:
    """One EWMA step: the exact expression the ``ewma`` scan body
    applies. ``core.stats`` carries this across days; a COMPILED
    single-step chain (the streaming day step always runs jitted)
    reproduces the batch scan bitwise — XLA contracts the mul+add into
    the same fma in both compiled forms (property-tested; fully-eager
    per-op dispatch rounds the products separately and may differ in the
    last ulp, which is the repo-wide eager-vs-compiled caveat)."""
    return alpha * x + (1 - alpha) * level


def ewma(x: jnp.ndarray, half_life: float) -> jnp.ndarray:
    """EWMA over the leading axis (oldest first); returns the final level."""
    alpha = ewma_alpha(half_life)

    def step(level, xi):
        return ewma_update(level, xi, alpha), None

    level0 = x[0]
    level, _ = jax.lax.scan(step, level0, x[1:])
    return level


def weekly_mean_forecast(daily: jnp.ndarray, half_life_weeks: float = 0.5
                         ) -> jnp.ndarray:
    """daily: (days,) -> forecast of next week's mean (scalar).
    Trailing full weeks only."""
    d = daily.shape[0]
    nw = d // 7
    weekly = daily[d - nw * 7:].reshape(nw, 7).mean(axis=1)
    return ewma(weekly, half_life_weeks)


def hourly_factor_forecast(hourly: jnp.ndarray, half_life_weeks: float = 4.0
                           ) -> jnp.ndarray:
    """hourly: (days, 24) -> per hour-of-week factors folded to (7,24)."""
    d = hourly.shape[0]
    nw = d // 7
    h = hourly[d - nw * 7:].reshape(nw, 7, 24)
    wmean = jnp.clip(h.mean(axis=(1, 2), keepdims=True), 1e-9, None)
    factors = h / wmean                      # (nw, 7, 24)
    return ewma(factors, half_life_weeks)    # (7, 24)


def daily_factor_forecast(daily: jnp.ndarray, half_life_weeks: float = 4.0
                          ) -> jnp.ndarray:
    """daily: (days,) -> day-of-week factors (7,)."""
    d = daily.shape[0]
    nw = d // 7
    dd = daily[d - nw * 7:].reshape(nw, 7)
    wmean = jnp.clip(dd.mean(axis=1, keepdims=True), 1e-9, None)
    return ewma(dd / wmean, half_life_weeks)


def deviation_coef(actual: jnp.ndarray, weekly_pred: jnp.ndarray
                   ) -> jnp.ndarray:
    """Linear model: next-day deviation ~ coef * previous-day deviation."""
    dev = actual - weekly_pred
    x, y = dev[:-1], dev[1:]
    num = jnp.sum(x * y)
    den = jnp.clip(jnp.sum(x * x), 1e-9, None)
    return jnp.clip(num / den, -1.0, 1.0)


# fold columns of the trailing 8 days (k = 8..1 days before the forecast
# day): column (-k) % 7 of the week fold — see POS_NEXT/POS_PREV below
POS8 = tuple(int((7 - k) % 7) for k in range(8, 0, -1))
POS_NEXT, POS_PREV = 0, 6


def forecast_inflexible(hourly: jnp.ndarray, dow_next: jnp.ndarray,
                        hl_mean: float = 0.5, hl_factor: float = 4.0
                        ) -> jnp.ndarray:
    """Next-day hourly inflexible usage forecast. hourly: (days,24);
    returns (24,).

    The week fold is indexed POSITIONALLY: the trailing whole-week
    window ends yesterday, so fold column 0 always holds the forecast
    day's day-of-week and column 6 yesterday's — for EVERY forecast day,
    not just when the window phase happens to align. (The old
    ``factors[dow_next]`` indexing silently rotated through the week as
    the window slid: 6 days out of 7 it applied a neighboring dow's
    pattern.) ``dow_next`` is kept for API compatibility; the phase is
    fully encoded by the window itself."""
    del dow_next
    daily = hourly.mean(axis=1)
    wmean = weekly_mean_forecast(daily, hl_mean)
    factors = hourly_factor_forecast(hourly, hl_factor)      # (7,24)
    weekly_fc_next = wmean * factors[POS_NEXT]
    # previous-day deviation correction (same-hour deviations). The coef
    # is fit on deviations from the dow-FACTORED weekly predictions — a
    # constant level here would fold the intra-week pattern into the
    # "deviations" and bias the correction (regression-tested).
    prev_pred = wmean * factors[POS_PREV]
    dev_prev = hourly[-1] - prev_pred
    coef = deviation_coef(hourly[-8:].mean(axis=1),
                          wmean * factors[jnp.asarray(POS8)].mean(axis=-1))
    return jnp.clip(weekly_fc_next + coef * dev_prev, 0.0, None)


def forecast_daily_total(daily: jnp.ndarray, dow_next: jnp.ndarray,
                         hl_mean: float = 0.5, hl_factor: float = 4.0
                         ) -> jnp.ndarray:
    """Next-day total (flexible usage or reservations). daily: (days,).
    Positional fold indexing, same as ``forecast_inflexible``."""
    del dow_next
    wmean = weekly_mean_forecast(daily, hl_mean)         # daily level
    factors = daily_factor_forecast(daily, hl_factor)    # (7,) dow factors
    pred_next = wmean * factors[POS_NEXT]
    prev_pred = wmean * factors[POS_PREV]
    # corrector fit against the dow-factored weekly predictions (a
    # constant level here leaks the weekly pattern into the deviations)
    coef = deviation_coef(daily[-8:], wmean * factors[jnp.asarray(POS8)])
    return jnp.clip(pred_next + coef * (daily[-1] - prev_pred), 0.0, None)


def fit_ratio_model(usage: jnp.ndarray, reservations: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """R = a + b * log(usage), fit by least squares on hourly samples.
    usage, reservations: (t,) flattened hourly totals."""
    r = reservations / jnp.clip(usage, 1e-9, None)
    x = jnp.log(jnp.clip(usage, 1e-9, None))
    xm, rm = x.mean(), r.mean()
    b = jnp.sum((x - xm) * (r - rm)) / jnp.clip(jnp.sum((x - xm) ** 2),
                                                1e-9, None)
    a = rm - b * xm
    return a, b


def ratio_at(a, b, usage):
    return jnp.clip(a + b * jnp.log(jnp.clip(usage, 1e-9, None)), 1.0, 10.0)


def relative_error_quantile(pred_hist: jnp.ndarray, actual_hist: jnp.ndarray,
                            q: float) -> jnp.ndarray:
    """q-quantile of trailing relative errors (eq. 2's epsilon term)."""
    eps = (actual_hist - pred_hist) / jnp.clip(jnp.abs(pred_hist), 1e-9, None)
    return jnp.quantile(eps, q)


def theta_requirement(tr_pred_next: jnp.ndarray, eps_q97: jnp.ndarray
                      ) -> jnp.ndarray:
    """Theta^(c)(d) = T_R-hat * (1 + eps_.97)  (paper eq. 2)."""
    return tr_pred_next * (1.0 + jnp.clip(eps_q97, 0.0, 2.0))


def alpha_inflation(theta: jnp.ndarray, uif_pred: jnp.ndarray,
                    tuf_pred: jnp.ndarray, ratio_a, ratio_b) -> jnp.ndarray:
    """Solve eq. (3) for alpha: sum_h (U_IF(h) + a*T_UF/24) * R(h) = Theta,
    with R evaluated at the nominal usage. The hour sums are ordered
    (``admission.hour_sum``), like the day's other totals: a plain sum
    rounded differently in a batch of one on XLA's CPU backend."""
    u_nom = uif_pred + tuf_pred / 24.0
    r = ratio_at(ratio_a, ratio_b, u_nom)
    denom = jnp.clip(hour_sum(tuf_pred / 24.0 * r), 1e-9, None)
    alpha = (theta - hour_sum(uif_pred * r)) / denom
    return jnp.clip(alpha, 0.5, 4.0)


def _walk_forward_mape(hourly: jnp.ndarray, hm, hf) -> jnp.ndarray:
    """Mean walk-forward MAPE of ``forecast_inflexible`` at half-lives
    (hm, hf) on the trailing 14 days (two 7-day-apart holdouts). hm/hf
    may be traced — the half-life only enters through ``ewma_alpha``."""
    errs = []
    for back in range(14, 0, -7):
        hist = hourly[:-back]
        dow = jnp.asarray((hourly.shape[0] - back) % 7)
        pred = forecast_inflexible(hist, dow, hm, hf)
        act = hourly[-back]
        errs.append(jnp.mean(jnp.abs(pred - act)
                             / jnp.clip(act, 1e-6, None)))
    return jnp.stack(errs).mean()


def calibrate_half_lives(hourly: jnp.ndarray,
                         grid=(0.25, 0.5, 1.0, 2.0, 4.0, 8.0)
                         ) -> Tuple[float, float]:
    """Paper: 'EWMA parameters are selected by exploration over a given
    range, so that out-of-sample MAPE is minimized.' Walk-forward eval on
    the trailing 14 days.

    The whole grid x grid exploration is ONE vmapped+jitted evaluation
    (half-lives are data, not Python constants — no re-trace per combo);
    ``argmin`` over the row-major error surface selects the same
    (first-best) pair as the legacy Python loop
    (``calibrate_half_lives_loop``, kept as the parity reference)."""
    g = len(grid)
    garr = jnp.asarray(grid, f32)
    hms = jnp.repeat(garr, g)            # row-major: hm outer, hf inner
    hfs = jnp.tile(garr, g)
    errs = jax.jit(jax.vmap(_walk_forward_mape, in_axes=(None, 0, 0)))(
        hourly, hms, hfs)
    i = int(jnp.argmin(errs))            # first minimum == loop's `<`
    return float(grid[i // g]), float(grid[i % g])


def calibrate_half_lives_loop(hourly: jnp.ndarray,
                              grid=(0.25, 0.5, 1.0, 2.0, 4.0, 8.0)
                              ) -> Tuple[float, float]:
    """Legacy per-combo Python loop (re-traces the forecast per pair);
    kept as the reference the vectorized selection is tested against."""
    best = (0.5, 4.0)
    best_err = jnp.inf
    for hm in grid:
        for hf in grid:
            err = _walk_forward_mape(hourly, hm, hf)
            if err < best_err:
                best_err, best = err, (hm, hf)
    return best
