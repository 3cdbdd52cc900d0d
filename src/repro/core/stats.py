"""Streaming sufficient statistics for the prediction layer.

The rescan prediction pipeline (``stages.forecast_stage`` /
``stages.power_stage``) carries seven full rolling-history arrays
``(n, H, 24)`` in ``SimState`` and rescans them every day, so day-step
cost and state memory grow with the history length H. This module owns
the O(1)-per-day replacement: first-class incremental estimators carried
as one pytree, ``PredictorState``, sized O(n * 24)-ish INDEPENDENT of H.

Estimators
----------
* **EWMA levels** — weekly mean, hour-of-week and day-of-week factor
  levels. The carried recursion is EXACTLY ``forecast.ewma``'s step
  (``forecast.ewma_update`` with ``forecast.ewma_alpha``): applying the
  incremental update T times from ``x[0]`` equals the batch scan bitwise
  (property-tested). The weekly-mean level updates daily on the trailing
  7-day mean with the half-life converted to days
  (``WMEAN_HL_DAYS = 7 * 0.5``); each hour/day-of-week factor slot
  updates once per week at the rescan's weekly half-life — the same
  cadence the rescan's week-folded scan applies.
* **Exponentially-weighted regression moments** — the previous-day
  deviation corrector (through-origin coef, mirroring
  ``forecast.deviation_coef`` on dow-factored deviations) and the
  ``R(h) = a + b log u`` reservations-to-usage model. Daily decay
  half-lives are chosen so the effective sample size matches the rescan
  windows (8 days for the corrector, 28 days for the ratio fit).
* **Exact ring buffers** — kept ONLY where a windowed statistic
  genuinely needs the window: trailing scalar prediction-error rings for
  the Theta 97%-quantile (eq. 2, 90 days) and the (1-gamma) power-capping
  quantile (28 days, compressed to one scalar per day), plus a 28-day
  usage ring for the PD piecewise-power refits — the breakpoints are
  window quantiles of usage, so ``stages.power_stage`` over the ring is
  bitwise-identical to the rescan's ``hist_usage[:, -28:]`` fit (the
  ring IS that slice), normal equations and all.

Equivalence contract (tested in tests/test_streaming.py)
--------------------------------------------------------
``init_predictor`` warm-starts every estimator from a burned-in history
window using the SAME rescan functions, so at the handoff day the
streaming forecasts of the EWMA components (``uif``/``tuf``/``tr``,
hence ``theta``) match the rescan bitwise; the ratio/alpha terms match
to float tolerance (moment-form vs centered-form least squares). From
there the two paths are different estimators of the same quantities —
the rescan re-partitions a sliding H-window into weeks each day, which
has no O(1) update — and a >=14-day dual run pins their drift to a
documented tolerance.
"""
from __future__ import annotations

from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import forecast
from repro.core.admission import hour_sum

f32 = jnp.float32

# rescan window sizes mirrored by the exact rings
THETA_WINDOW = 90            # eq. 2: 97%-quantile of daily T_R errors
GAMMA_WINDOW = 28            # (1-gamma) quantile of hourly U_IF errors
USAGE_WINDOW = 28            # PD power refits + breakpoint quantiles
WEEK = 7

# daily-update half-lives of the EW estimators. The weekly-mean level
# converts the rescan's 0.5-week half-life to update steps of one day;
# the regression moments match the rescan windows' effective sample
# size: a daily decay rho has ESS (1+rho)/(1-rho), so ESS=8 (corrector)
# -> rho=7/9 -> hl ~ 2.76 d, ESS=28 (ratio fit) -> rho=27/29 -> ~9.7 d.
WMEAN_HL_DAYS = 7.0 * 0.5
DEV_HL_DAYS = 2.76
RATIO_HL_DAYS = 9.7


def decay_from_half_life(half_life_days: float) -> jnp.ndarray:
    """Per-day retention factor rho = 0.5 ** (1 / half_life)."""
    return jnp.exp(jnp.log(0.5) / jnp.maximum(half_life_days, 1e-3))


# -------------------------------------------------------------- primitives

def ring_push(ring: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Drop the oldest entry along axis 1, append ``x`` (chronological
    order — oldest first, like the rescan history arrays)."""
    return jnp.concatenate([ring[:, 1:], x[:, None]], axis=1)


def ring_quantile(ring: jnp.ndarray, q) -> jnp.ndarray:
    """q-quantile over the window axis (axis 1). Exact — the ring holds
    the raw trailing values, not a sketch."""
    return jnp.quantile(ring, q, axis=1)


class EWMoments(NamedTuple):
    """Exponentially-weighted simple-regression moments of (x, y) sample
    batches: y ~ a + b x via the normal equations in moment form. All
    leaves (n,)."""
    w: jnp.ndarray               # decayed sample count
    sx: jnp.ndarray              # sum x
    sy: jnp.ndarray              # sum y
    sxx: jnp.ndarray             # sum x^2
    sxy: jnp.ndarray             # sum x y


def ew_init(x: jnp.ndarray, y: jnp.ndarray) -> EWMoments:
    """Unweighted moments of an initial sample batch. x, y: (n, t)."""
    return EWMoments(
        w=jnp.full(x.shape[:1], float(x.shape[1]), f32),
        sx=jnp.sum(x, axis=1), sy=jnp.sum(y, axis=1),
        sxx=jnp.sum(x * x, axis=1), sxy=jnp.sum(x * y, axis=1))


def ew_update(m: EWMoments, x: jnp.ndarray, y: jnp.ndarray, rho
              ) -> EWMoments:
    """Decay by ``rho`` then absorb one day's sample batch. x, y: (n, t)."""
    t = float(x.shape[1])
    return EWMoments(
        w=rho * m.w + t,
        sx=rho * m.sx + jnp.sum(x, axis=1),
        sy=rho * m.sy + jnp.sum(y, axis=1),
        sxx=rho * m.sxx + jnp.sum(x * x, axis=1),
        sxy=rho * m.sxy + jnp.sum(x * y, axis=1))


def ew_linfit(m: EWMoments):
    """(a, b) of y ~ a + b x from the moments (normal equations)."""
    xm = m.sx / jnp.clip(m.w, 1e-9, None)
    ym = m.sy / jnp.clip(m.w, 1e-9, None)
    b = (m.sxy - m.sx * ym) / jnp.clip(m.sxx - m.sx * xm, 1e-9, None)
    return ym - b * xm, b


class DevMoments(NamedTuple):
    """EW moments of the previous-day deviation corrector: next-day
    deviation ~ coef * previous-day deviation (through the origin,
    mirroring ``forecast.deviation_coef``). All leaves (n,)."""
    sxx: jnp.ndarray
    sxy: jnp.ndarray
    prev: jnp.ndarray            # yesterday's deviation (today's x)


def dev_init(dev: jnp.ndarray) -> DevMoments:
    """Moments from an initial deviation series. dev: (n, t), oldest
    first — the same (x, y) = (dev[:-1], dev[1:]) pairing and sum order
    as ``forecast.deviation_coef`` (bitwise at the handoff)."""
    x, y = dev[:, :-1], dev[:, 1:]
    return DevMoments(sxx=jnp.sum(x * x, axis=1),
                      sxy=jnp.sum(x * y, axis=1), prev=dev[:, -1])


def dev_update(m: DevMoments, dev_today: jnp.ndarray, rho) -> DevMoments:
    """Decay, absorb the (yesterday, today) deviation pair, carry today."""
    return DevMoments(sxx=rho * m.sxx + m.prev * m.prev,
                      sxy=rho * m.sxy + m.prev * dev_today,
                      prev=dev_today)


def dev_coef(m: DevMoments) -> jnp.ndarray:
    """clip(Sxy / Sxx, -1, 1) — ``forecast.deviation_coef``'s estimate."""
    return jnp.clip(m.sxy / jnp.clip(m.sxx, 1e-9, None), -1.0, 1.0)


# ---------------------------------------------------------- PredictorState

class PredictorState(NamedTuple):
    """The streaming prediction layer's entire carry: O(n) in the fleet,
    O(1) in the history length. Week rings are day-of-week indexed (slot
    d%7 holds the most recent day with that dow — together the trailing
    7 days); error/usage rings are chronological (oldest first)."""
    # inflexible hourly usage U_IF
    uif_day_ring: jnp.ndarray    # (n, 7) trailing daily means, dow slots
    uif_prev: jnp.ndarray        # (n, 24) yesterday's hourly actuals
    uif_wmean: jnp.ndarray       # (n,) weekly-mean EWMA level
    uif_how: jnp.ndarray         # (n, 7, 24) hour-of-week factor levels
    uif_dev: DevMoments          # corrector moments on daily-mean devs
    # daily flexible usage T_UF
    flex_ring: jnp.ndarray       # (n, 7)
    flex_wmean: jnp.ndarray      # (n,)
    flex_dow: jnp.ndarray        # (n, 7) day-of-week factor levels
    flex_dev: DevMoments
    # daily total reservations T_R
    res_ring: jnp.ndarray        # (n, 7)
    res_wmean: jnp.ndarray       # (n,)
    res_dow: jnp.ndarray         # (n, 7)
    res_dev: DevMoments
    # reservations-to-usage ratio R(h) = a + b log u
    ratio: EWMoments
    # exact trailing-error rings (scalar per day)
    theta_err_ring: jnp.ndarray  # (n, <=90) daily T_R relative errors
    gamma_err_ring: jnp.ndarray  # (n, <=28) daily (1-gamma) U_IF error q
    # exact usage window for the PD power refits (breakpoints are window
    # quantiles -> power_stage over this ring == rescan bitwise)
    usage_ring: jnp.ndarray      # (n, <=28, 24)


def pytree_nbytes(tree) -> int:
    """Total bytes of a pytree's array leaves (concrete or abstract)."""
    return int(sum(l.size * l.dtype.itemsize
                   for l in jax.tree_util.tree_leaves(tree)))


def predictor_nbytes(pred: PredictorState) -> int:
    """Total bytes of the streaming carry."""
    return pytree_nbytes(pred)


def replaced_hist_nbytes(state) -> int:
    """Bytes of the seven rescan history arrays PredictorState replaces
    (``hist_*`` in a rescan SimState)."""
    return int(sum(getattr(state, k).size * getattr(state, k).dtype.itemsize
                   for k in ("hist_uif", "hist_flex_daily", "hist_res_daily",
                             "hist_usage", "hist_res", "hist_tr_pred",
                             "hist_uif_pred")))


# ------------------------------------------------------------ init/forecast

def _dow_slots(day, k: int) -> jnp.ndarray:
    """Day-of-week slots of the trailing ``k`` days (oldest first) when
    ``day`` is today (the next day to simulate)."""
    return (day - k + jnp.arange(k)) % WEEK


def _dow_ring(daily_hist: jnp.ndarray, day) -> jnp.ndarray:
    """Scatter the trailing 7 daily values into dow slots. (n, H) -> (n, 7)."""
    return jnp.zeros(daily_hist.shape[:1] + (WEEK,), f32).at[
        :, _dow_slots(day, WEEK)].set(daily_hist[:, -WEEK:])


def _dev_init_hourly(hourly_hist: jnp.ndarray) -> DevMoments:
    """Corrector moments from an hourly history window, computed
    per-cluster under vmap with the weekly level/factors recomputed
    locally — the same compile structure (and the same positional fold
    columns, ``forecast.POS8``) as ``forecast_inflexible``, so the
    handoff coefficient matches the rescan bitwise."""
    pos8 = jnp.asarray(forecast.POS8)

    def one(h):
        wm = forecast.weekly_mean_forecast(h.mean(axis=1))
        fa = forecast.hourly_factor_forecast(h)
        dev = h[-8:].mean(axis=1) - wm * fa[pos8].mean(axis=-1)
        return (jnp.sum(dev[:-1] * dev[:-1]),
                jnp.sum(dev[:-1] * dev[1:]), dev[-1])
    sxx, sxy, prev = jax.vmap(one)(hourly_hist)
    return DevMoments(sxx=sxx, sxy=sxy, prev=prev)


def _dev_init_daily(daily_hist: jnp.ndarray) -> DevMoments:
    """Corrector moments from a daily-total history window (mirrors
    ``forecast_daily_total``'s fit, per-cluster under vmap)."""
    pos8 = jnp.asarray(forecast.POS8)

    def one(d):
        wm = forecast.weekly_mean_forecast(d)
        fa = forecast.daily_factor_forecast(d)
        dev = d[-8:] - wm * fa[pos8]
        return (jnp.sum(dev[:-1] * dev[:-1]),
                jnp.sum(dev[:-1] * dev[1:]), dev[-1])
    sxx, sxy, prev = jax.vmap(one)(daily_hist)
    return DevMoments(sxx=sxx, sxy=sxy, prev=prev)


def init_predictor(hist_uif, hist_flex_daily, hist_res_daily, hist_usage,
                   hist_res, hist_tr_pred, hist_uif_pred, day, gamma
                   ) -> PredictorState:
    """Warm-start every streaming estimator from a burned-in history
    window (the arrays a rescan ``SimState`` carries; ``day`` is the next
    day to simulate). EWMA levels and corrector moments are computed by
    the SAME rescan functions/op-orders, so the handoff-day streaming
    forecast matches the rescan bitwise on the EWMA components."""
    n, H = hist_uif.shape[0], hist_uif.shape[1]
    if H < WEEK:
        raise ValueError(f"streaming init needs >= {WEEK} days of history, "
                         f"got {H}")

    # the rescan fold is positional (column j <-> absolute dow
    # (day + j) % 7 — the trailing whole-week window starts on the
    # forecast day's dow); rolling by `day` converts the levels to the
    # ABSOLUTE dow slots the streaming carry indexes by
    def abs_slots(factors):
        return jnp.roll(factors, day, axis=1)

    uif_daily = hist_uif.mean(axis=2)                       # (n, H)
    uif_wmean = jax.vmap(forecast.weekly_mean_forecast)(uif_daily)
    uif_how = abs_slots(jax.vmap(forecast.hourly_factor_forecast)(hist_uif))
    uif_dev = _dev_init_hourly(hist_uif)

    flex_wmean = jax.vmap(forecast.weekly_mean_forecast)(hist_flex_daily)
    flex_dow = abs_slots(
        jax.vmap(forecast.daily_factor_forecast)(hist_flex_daily))
    flex_dev = _dev_init_daily(hist_flex_daily)

    res_wmean = jax.vmap(forecast.weekly_mean_forecast)(hist_res_daily)
    res_dow = abs_slots(
        jax.vmap(forecast.daily_factor_forecast)(hist_res_daily))
    res_dev = _dev_init_daily(hist_res_daily)

    u28 = hist_usage[:, -USAGE_WINDOW:]
    r28 = hist_res[:, -USAGE_WINDOW:]
    x = jnp.log(jnp.clip(u28, 1e-9, None)).reshape(n, -1)
    y = (r28 / jnp.clip(u28, 1e-9, None)).reshape(n, -1)
    ratio = ew_init(x, y)

    th = hist_tr_pred[:, -THETA_WINDOW:]
    theta_err = (hist_res_daily[:, -THETA_WINDOW:] - th) \
        / jnp.clip(jnp.abs(th), 1e-9, None)
    up = hist_uif_pred[:, -GAMMA_WINDOW:]
    eps_h = (hist_uif[:, -GAMMA_WINDOW:] - up) \
        / jnp.clip(jnp.abs(up), 1e-9, None)               # (n, W, 24)
    gamma_err = jnp.quantile(eps_h, 1.0 - gamma, axis=2)  # (n, W)

    return PredictorState(
        uif_day_ring=_dow_ring(uif_daily, day),
        uif_prev=hist_uif[:, -1],
        uif_wmean=uif_wmean, uif_how=uif_how, uif_dev=uif_dev,
        flex_ring=_dow_ring(hist_flex_daily, day),
        flex_wmean=flex_wmean, flex_dow=flex_dow, flex_dev=flex_dev,
        res_ring=_dow_ring(hist_res_daily, day),
        res_wmean=res_wmean, res_dow=res_dow, res_dev=res_dev,
        ratio=ratio,
        theta_err_ring=theta_err.astype(f32),
        gamma_err_ring=gamma_err.astype(f32),
        usage_ring=u28)


def streaming_forecast(pred: PredictorState, day, gamma
                       ) -> Dict[str, jnp.ndarray]:
    """Next-day forecast dict (same keys as ``stages.forecast_stage``)
    from the streaming carry — O(1) in history length. ``day`` is the
    day being forecast; ``day``/``gamma`` may be traced."""
    dow = day % WEEK
    dow_prev = (day - 1) % WEEK

    # U_IF(h): weekly level x hour-of-week factors + prev-day correction
    base = pred.uif_wmean[:, None] * pred.uif_how[:, dow]
    prev_pred = pred.uif_wmean[:, None] * pred.uif_how[:, dow_prev]
    dev_prev = pred.uif_prev - prev_pred
    uif = jnp.clip(base + dev_coef(pred.uif_dev)[:, None] * dev_prev,
                   0.0, None)

    # T_UF(d), T_R(d): weekly level x dow factors + prev-day correction
    def daily_total(ring, wmean, dow_f, dev):
        nxt = wmean * dow_f[:, dow]
        prev = wmean * dow_f[:, dow_prev]
        return jnp.clip(nxt + dev_coef(dev) * (ring[:, dow_prev] - prev),
                        0.0, None)

    tuf = daily_total(pred.flex_ring, pred.flex_wmean, pred.flex_dow,
                      pred.flex_dev)
    tr = daily_total(pred.res_ring, pred.res_wmean, pred.res_dow,
                     pred.res_dev)

    ra, rb = ew_linfit(pred.ratio)
    eps97 = ring_quantile(pred.theta_err_ring, 0.97)
    theta = forecast.theta_requirement(tr, eps97)
    alpha = jax.vmap(forecast.alpha_inflation)(theta, uif, tuf, ra, rb)
    # (1-gamma) hourly inflexible error: trailing mean of the DAILY
    # (1-gamma) hour-quantiles (the rescan pools 28x24 hourly errors; the
    # ring compresses each day to one scalar — documented approximation)
    epsq = jnp.mean(pred.gamma_err_ring, axis=1)
    uif_q = uif * (1.0 + jnp.clip(epsq, 0.0, 1.0)[:, None])
    return {"uif": uif, "tuf": tuf, "tr": tr, "ratio_a": ra, "ratio_b": rb,
            "theta": theta, "alpha": alpha, "uif_q": uif_q}


def predictor_update(pred: PredictorState, fc: Dict[str, jnp.ndarray],
                     day, gamma, u_if, flex_daily, res_daily, usage_total,
                     reservations) -> PredictorState:
    """Absorb one observed day — O(1) in history length.

    ``fc`` is the forecast issued for this ``day`` (so prediction errors
    pair same-day like the rescan's ``hist_*_pred`` rolls); ``u_if``,
    ``usage_total``, ``reservations`` are (n, 24) actuals; ``flex_daily``
    / ``res_daily`` are (n,) daily totals."""
    dow = day % WEEK
    rho_dev = decay_from_half_life(DEV_HL_DAYS)
    rho_ratio = decay_from_half_life(RATIO_HL_DAYS)
    a_mean = forecast.ewma_alpha(WMEAN_HL_DAYS)
    a_factor = forecast.ewma_alpha(4.0)      # weekly cadence per dow slot

    # exact error rings (same-day prediction/actual pairing)
    tr_err = (res_daily - fc["tr"]) / jnp.clip(jnp.abs(fc["tr"]), 1e-9,
                                               None)
    eps_h = (u_if - fc["uif"]) / jnp.clip(jnp.abs(fc["uif"]), 1e-9, None)
    gamma_err = jnp.quantile(eps_h, 1.0 - gamma, axis=1)

    # deviations vs the PRE-update levels (the prediction actually made)
    uif_daily = u_if.mean(axis=1)
    dev_u = uif_daily - pred.uif_wmean * pred.uif_how[:, dow].mean(axis=-1)
    dev_f = flex_daily - pred.flex_wmean * pred.flex_dow[:, dow]
    dev_r = res_daily - pred.res_wmean * pred.res_dow[:, dow]

    # trailing-week rings, then the EWMA level updates on them
    uif_ring = pred.uif_day_ring.at[:, dow].set(uif_daily)
    flex_ring = pred.flex_ring.at[:, dow].set(flex_daily)
    res_ring = pred.res_ring.at[:, dow].set(res_daily)
    wk_u = uif_ring.mean(axis=1)
    wk_f = flex_ring.mean(axis=1)
    wk_r = res_ring.mean(axis=1)

    x = jnp.log(jnp.clip(usage_total, 1e-9, None))
    y = reservations / jnp.clip(usage_total, 1e-9, None)

    return pred._replace(
        uif_day_ring=uif_ring, uif_prev=u_if,
        uif_wmean=forecast.ewma_update(pred.uif_wmean, wk_u, a_mean),
        uif_how=pred.uif_how.at[:, dow].set(forecast.ewma_update(
            pred.uif_how[:, dow],
            u_if / jnp.clip(wk_u[:, None], 1e-9, None), a_factor)),
        uif_dev=dev_update(pred.uif_dev, dev_u, rho_dev),
        flex_ring=flex_ring,
        flex_wmean=forecast.ewma_update(pred.flex_wmean, wk_f, a_mean),
        flex_dow=pred.flex_dow.at[:, dow].set(forecast.ewma_update(
            pred.flex_dow[:, dow],
            flex_daily / jnp.clip(wk_f, 1e-9, None), a_factor)),
        flex_dev=dev_update(pred.flex_dev, dev_f, rho_dev),
        res_ring=res_ring,
        res_wmean=forecast.ewma_update(pred.res_wmean, wk_r, a_mean),
        res_dow=pred.res_dow.at[:, dow].set(forecast.ewma_update(
            pred.res_dow[:, dow],
            res_daily / jnp.clip(wk_r, 1e-9, None), a_factor)),
        res_dev=dev_update(pred.res_dev, dev_r, rho_dev),
        ratio=ew_update(pred.ratio, x, y, rho_ratio),
        theta_err_ring=ring_push(pred.theta_err_ring, tr_err),
        gamma_err_ring=ring_push(pred.gamma_err_ring, gamma_err),
        usage_ring=ring_push(pred.usage_ring, usage_total))


# ------------------------------------------------- hour-grain advancement

class HourAccum(NamedTuple):
    """Partial-day accumulator: the hour-grain extension of the day-grain
    ``predictor_update`` recursion. The MPC recourse loop (``core.mpc``)
    pushes one observed hour at a time; ``hour_finalize`` absorbs the
    completed day into the ``PredictorState`` carry.

    Columns are scattered in hour order and ``hour_finalize`` takes the
    daily totals of the assembled columns by ``admission.hour_sum``, so
    chaining 24 ``hour_update`` calls and finalizing is BITWISE identical
    to the daily batch ``predictor_update`` on the same arrays
    (property-tested in tests/test_mpc_properties.py). A running total
    per hour would not be: the compiler may fuse ``+ usage * ratio``
    into one fused multiply-add, which rounds once where the batch path
    rounds twice."""
    hour: jnp.ndarray            # () int32 hours absorbed so far
    u_if: jnp.ndarray            # (n, 24) realized inflexible columns
    use_flex: jnp.ndarray        # (n, 24) realized flexible columns
    usage: jnp.ndarray           # (n, 24) u_if + use_flex
    res: jnp.ndarray             # (n, 24) reservations = usage * ratio


def hour_accum_init(u_if: jnp.ndarray) -> HourAccum:
    """Empty accumulator for a day whose (n, 24) columns are shaped (and,
    under ``shard_map``, typed) like ``u_if``."""
    z24 = jnp.zeros_like(u_if)
    return HourAccum(hour=jnp.zeros((), jnp.int32), u_if=z24,
                     use_flex=z24, usage=z24, res=z24)


def hour_update(acc: HourAccum, hour, u_if_h, use_flex_h, ratio_h
                ) -> HourAccum:
    """Absorb one observed hour — O(1) work per step, O(n * 24) state.
    ``hour`` may be traced (the MPC sub-scan carries it); ``u_if_h`` /
    ``use_flex_h`` / ``ratio_h`` are (n,) actuals for that hour."""
    usage_h = u_if_h + use_flex_h
    res_h = usage_h * ratio_h
    return HourAccum(
        hour=acc.hour + 1,
        u_if=acc.u_if.at[:, hour].set(u_if_h),
        use_flex=acc.use_flex.at[:, hour].set(use_flex_h),
        usage=acc.usage.at[:, hour].set(usage_h),
        res=acc.res.at[:, hour].set(res_h))


def hour_finalize(pred: PredictorState, acc: HourAccum,
                  fc: Dict[str, jnp.ndarray], day, gamma) -> PredictorState:
    """Close the day: absorb the hour-grain accumulator into the
    streaming carry. Equals the daily batch ``predictor_update`` on the
    same realized arrays (the accumulator reconstructs them exactly)."""
    return predictor_update(pred, fc, day, gamma, acc.u_if,
                            hour_sum(acc.use_flex), hour_sum(acc.res),
                            acc.usage, acc.res)
