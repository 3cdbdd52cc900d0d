"""Plain reference of the fleet simulator and of the VCC solves.

A straightforward implementation of the semantics the program claims,
written without the program's code: the day-ahead risk-aware VCC problem
of arXiv 2106.11750 section III (eq. 2-4), solved by projected gradient
with campus dual ascent, and the open-loop day cycle around it (grid
simulation and day-ahead intensity forecast, PD power-model fit,
EWMA load forecasts, spatial pre-shift, fluid admission against the VCC,
SLO pause feedback, emissions ledger), over the same seeded inputs.

It differs from the program where a reference should: the conservation
projection is the exact breakpoint solution rather than 50 bisection
passes, reductions are plain sums, small linear systems are solved by
Gauss-Jordan elimination, and nothing is pinned for batch invariance.

The simulator runs in float32. The solves run in ``solve_dt``: float32
is the reference, bfloat16 the control, which has to come out as not
correct (bfloat16 through the whole simulator breaks the PD power fit's
normal equations and returns no number at all).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

H = 24
CI_COAL, CI_GAS = 0.95, 0.45


def normal(key, shape, dt):
    return jax.random.normal(key, shape).astype(dt)


def hsum(x):
    return jnp.sum(x, axis=-1)


# ------------------------------------------------------------- the solver

def project(z, lo, ub):
    """Exact Euclidean projection of each row onto {sum = 0} n [lo, ub]:
    the shift nu with sum_h clip(z - nu, lo, ub) = 0 lies between two
    neighbouring breakpoints z - ub, z - lo, where the sum is linear."""
    bp = jnp.sort(jnp.concatenate([z - ub, z - lo], axis=1), axis=1)
    f = jnp.sum(jnp.clip(z[:, None, :] - bp[:, :, None], lo[:, None, :],
                         ub[:, None, :]), axis=2)            # non-increasing
    j = jnp.clip(jnp.sum(f > 0, axis=1), 1, bp.shape[1] - 1)
    b0 = jnp.take_along_axis(bp, (j - 1)[:, None], 1)[:, 0]
    b1 = jnp.take_along_axis(bp, j[:, None], 1)[:, 0]
    f0 = jnp.take_along_axis(f, (j - 1)[:, None], 1)[:, 0]
    f1 = jnp.take_along_axis(f, j[:, None], 1)[:, 0]
    slope = f0 - f1
    t = jnp.where(slope > 0, f0 / jnp.where(slope > 0, slope, 1), 0)
    nu = b0 + jnp.clip(t, 0, 1) * (b1 - b0)
    return jnp.clip(z - nu[:, None], lo, ub)


def day_bounds(p):
    """Box on the hourly deviations delta and the clusters that can be
    shaped at all (section III-C: power capping, machine capacity, the
    drop limit, daily conservation)."""
    drop = p["drop_limit"]
    tau24 = jnp.clip(p["tau"][:, None] / 24, 1e-9, None)
    ub = jnp.minimum((p["u_pow_cap"][:, None] - p["u_if_q"]) / tau24 - 1,
                     (p["capacity"][:, None] / p["ratio"] - p["u_if"])
                     / tau24 - 1)
    ub = jnp.clip(ub, -drop, 24)
    lo = jnp.full_like(ub, -drop)
    ok = (jnp.sum(ub, 1) >= 0) & (p["tau"] > 1e-6) \
        & jnp.all(ub > -drop + 1e-9, axis=1)
    return lo, ub, ok


def _ascent(p, delta, mu, lo, ub, outer, inner, c):
    """``outer`` rounds of ``inner`` projected-gradient steps on the
    linearized carbon cost plus a softmax-smoothed hourly peak, each
    round followed by a clipped ascent step on the campus duals."""
    dt = delta.dtype
    tau24 = p["tau"][:, None] / 24
    temp = c["temp_frac"] * jnp.clip(jnp.mean(p["pow_nom"]), 1e-6, None)
    g = jnp.clip(jnp.max(p["pi"] * tau24, 1, keepdims=True), 1e-9, None)
    lr = c["lr"] / (g * jnp.clip(p["lambda_e"] * jnp.max(p["eta"], 1,
                                                         keepdims=True)
                                 + p["lambda_p"], 1e-9, None))
    m = p["campus_limit"].shape[0]

    def round_(carry, _):
        d, mu = carry
        price = (p["lambda_p"] + mu[p["campus"]])[:, None]

        def step(_, d):
            w = jax.nn.softmax((p["pow_nom"] + p["pi"] * d * tau24) / temp,
                               1)
            grad = (p["lambda_e"] * p["eta"] + price * w) * p["pi"] * tau24
            return project(d - lr * grad, lo, ub)

        d = jax.lax.fori_loop(0, inner, step, d)
        y = jnp.max(p["pow_nom"] + p["pi"] * d * tau24, 1)
        load = jax.ops.segment_sum(y, p["campus"], num_segments=m)
        lim = p["campus_limit"]
        mu = jnp.clip(mu + c["rho"] * (load - lim)
                      / jnp.clip(lim, 1e-9, None), 0, None).astype(dt)
        return (d, mu), None

    (delta, mu), _ = jax.lax.scan(round_, (delta, mu), None, length=outer)
    return delta, mu


def _vcc(p, delta, ok):
    shaped = (p["u_if"] + (1 + delta) * p["tau"][:, None] / 24) * p["ratio"]
    cap = p["capacity"][:, None]
    return jnp.where(ok[:, None], jnp.minimum(shaped, cap), cap)


def solve_day(p, c):
    """The day-ahead fleet solve from delta = 0: (delta, mu, vcc, ok)."""
    lo, ub, ok = day_bounds(p)
    lo = jnp.where(ok[:, None], lo, 0)
    ub = jnp.where(ok[:, None], ub, 0)
    mu0 = jnp.zeros_like(p["campus_limit"])
    delta, mu = _ascent(p, jnp.zeros_like(p["eta"]), mu0, lo, ub,
                        c["outer_iters"], c["inner_iters"], c)
    return delta, mu, _vcc(p, delta, ok), ok


# ------------------------------------------------------------ the grid

def _bump(hours, peak, width):
    d = jnp.minimum(jnp.abs(hours - peak), 24 - jnp.abs(hours - peak))
    return jnp.exp(-0.5 * (d / width) ** 2)


def zone_day(key, zp, dt):
    """One day of a zone's hourly carbon intensity (kg/kWh) from its
    generation mix: diurnal demand, solar under AR(1) clearness, wind
    under AR(1) strength and hourly gusts, carbon-free baseload, and a
    coal/gas thermal residual."""
    hours = jnp.arange(H, dtype=dt)
    k1, k2, k3 = jax.random.split(key, 3)
    # AR(1) weather started at 0: its first day is 0.7 * 0 + sqrt(.51) e
    clear = jax.nn.sigmoid(1.0 + jnp.sqrt(jnp.asarray(0.51, dt))
                           * normal(k1, (1,), dt)[0] * zp["weather_vol"] * 5)
    windy = jax.nn.sigmoid(0.5 + jnp.sqrt(jnp.asarray(0.51, dt))
                           * normal(k2, (1,), dt)[0] * zp["weather_vol"] * 6)
    demand = 1 + zp["demand_amp"] * (0.6 * _bump(hours, 19.0, 3.5)
                                     + 0.4 * _bump(hours, 9.0, 2.5))
    gust = jnp.clip(1 + 0.15 * normal(k3, (1, H), dt)[0], 0.3, 1.7)
    green = zp["solar_cap"] * clear * _bump(hours, 12.5, 2.8) \
        + zp["wind_cap"] * windy * gust + zp["baseload"]
    coal = jnp.clip(zp["coal_share"], 0, 1)
    return jnp.maximum(demand - green, 0.02) * (
        coal * CI_COAL + (1 - coal) * CI_GAS) / demand


def grid_day(zone, hist, key, green, coal, dt):
    """(actual (z, 24), day-ahead forecast (z, 24)) of every zone: the
    forecast blends a week's climatology with yesterday, sees 80% of
    tomorrow's deviation, and errs in proportion to zone volatility."""
    z = hist.shape[0]
    zp = dict(zone, solar_cap=zone["solar_cap"] * green,
              wind_cap=zone["wind_cap"] * green,
              coal_share=zone["coal_share"] * coal)
    keys = jax.random.split(key, 2 * z)
    act = jax.vmap(lambda k, q: zone_day(k, q, dt))(keys[:z], zp)
    base = 0.6 * jnp.mean(hist[:, -7:], 1) + 0.4 * hist[:, -1]
    err = jax.vmap(lambda k: normal(k, (H,), dt))(keys[z:]) \
        * (zp["weather_vol"] * 0.15)[:, None] * jnp.abs(act)
    fc = jnp.clip(base + 0.8 * (act - base) + err, 1e-3, None)
    return act, fc


# ------------------------------------------------------------ the load

def actual_load(truth, day, key, cap_day, arr_scale, arr_hour, dt):
    """(inflexible usage, flexible arrivals, reservation ratio), (n, 24)."""
    n = cap_day.shape[0]
    hours = jnp.arange(H, dtype=dt)
    wk = jnp.cos(2 * jnp.pi * (day % 7).astype(dt) / 7)
    u_if = truth["base_if"][:, None] * (
        1 + truth["diurnal_amp"][:, None]
        * _bump(hours[None], truth["peak_hour"][:, None], 4.0)) \
        * (1 + truth["weekly_amp"][:, None] * wk) \
        * (1 + truth["noise"][:, None]
           * normal(jax.random.fold_in(key, 2), (n, H), dt))
    u_if = jnp.minimum(u_if, 0.98 * cap_day[:, None])
    prof = 0.6 + 0.8 * jnp.exp(-0.5 * ((hours - 11) / 5) ** 2)
    arr = truth["arr_level"][:, None] * prof * 24 / jnp.sum(prof) \
        * (1 + 0.5 * truth["weekly_amp"][:, None] * wk) \
        * (1 + 2.5 * truth["noise"][:, None]
           * normal(jax.random.fold_in(key, 3), (n, H), dt))
    arr = jnp.clip(arr, 0, None) * arr_scale[:, None]
    if arr_hour is not None:
        arr = arr * arr_hour[None]
    ratio = jnp.clip(truth["ratio_a"][:, None] + truth["ratio_b"][:, None]
                     * jnp.log(jnp.clip(u_if + arr, 1e-6, None)), 1.05, 3.0)
    return u_if, arr, ratio


def admit(vcc, u_if, arr, ratio, cap, queue0, power, intensity, allowance):
    """Fluid admission, hour by hour: inflexible work always runs,
    queued and arriving flexible work runs while reservations stay under
    the hour's VCC and usage under machine capacity."""
    def hour(q, x):
        v, u, a, r = x
        room = jnp.minimum(jnp.clip(v - u * r, 0, None) / jnp.maximum(r, 1),
                           jnp.clip(cap - u, 0, None))
        run = jnp.minimum(q + a, room)
        return q + a - run, run

    q_end, run = jax.lax.scan(hour, queue0, (vcc.T, u_if.T, arr.T, ratio.T))
    usage = u_if + run.T
    pw = jax.vmap(power, 1, 1)(usage)
    arrived = hsum(arr)
    return {"usage": usage, "res": usage * ratio, "power": pw,
            "carbon": pw * intensity, "served": hsum(run.T),
            "arrived": arrived, "queue_end": q_end,
            "unmet": jnp.clip(q_end - queue0 - allowance * arrived, 0,
                              None)}


# ------------------------------------------------------- the power model

def _solve_small(A, b):
    """Solve the small SPD system A x = b by Cholesky factorization,
    with each pivot kept positive."""
    k = A.shape[-1]
    L = jnp.zeros_like(A)
    for j in range(k):
        d = jnp.sqrt(jnp.clip(A[j, j] - jnp.sum(L[j, :j] ** 2), 1e-12, None))
        L = L.at[j, j].set(d)
        for i in range(j + 1, k):
            L = L.at[i, j].set((A[i, j] - jnp.sum(L[i, :j] * L[j, :j])) / d)
    y = jnp.zeros_like(b)
    for i in range(k):
        y = y.at[i].set((b[i] - jnp.sum(L[i, :i] * y[:i])) / L[i, i])
    x = jnp.zeros_like(b)
    for i in reversed(range(k)):
        x = x.at[i].set((y[i] - jnp.sum(L[i + 1:, i] * x[i + 1:])) / L[i, i])
    return x


def fit_power(cpu, watts):
    """Least-squares piecewise-linear fit of one PD's power in its usage
    (hinges at the usage quartiles), in window coordinates with a ridge
    relative to the window length. Returns (coef (5,), breaks (3,))."""
    breaks = jnp.quantile(cpu, jnp.asarray([0.25, 0.5, 0.75], cpu.dtype))
    lo = jnp.min(cpu)
    span = jnp.clip(jnp.max(cpu) - lo, 1e-6, None)
    x = (cpu - lo) / span
    X = jnp.stack([jnp.ones_like(x), x]
                  + [jnp.maximum(x - (b - lo) / span, 0) for b in breaks], 1)
    # a TPU multiplies float32 matrices in bfloat16 passes unless told
    # otherwise, and these normal equations are ill-conditioned
    dot = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    A = dot(X.T, X) + 1e-4 * cpu.shape[0] * jnp.eye(5, dtype=cpu.dtype)
    c = _solve_small(A, dot(X.T, watts))
    return jnp.concatenate([c[:1] - c[1:2] * lo / span, c[1:] / span]), \
        breaks


def power_model(params, hist_usage, key, dt):
    """Refit every PD's power curve on the last 28 days of cluster usage
    and return (power(u) (n,) -> (n,), slope(u) (n,) -> (n,))."""
    lam, cap = params["lam"], params["truth"]["capacity"]
    n, k = lam.shape
    u = hist_usage[:, -28:].reshape(n, 1, -1) * lam[..., None] \
        / jnp.clip(cap, 1e-6, None)[:, None, None]
    u = u.reshape(n * k, -1)
    true_w = (params["pd_idle"][:, None] + params["pd_slope"][:, None]
              * jnp.power(jnp.clip(u, 0, 1), params["pd_curve"][:, None])) \
        * (1 + 0.01 * normal(key, u.shape, dt))
    coef, breaks = jax.vmap(fit_power)(u, true_w)

    def at(u_c):
        x = (lam * u_c[:, None] / jnp.clip(cap, 1e-6, None)[:, None]
             ).reshape(-1)
        return x

    def power(u_c):
        x = at(u_c)
        p = coef[:, 0] + coef[:, 1] * x + jnp.sum(
            coef[:, 2:] * jnp.maximum(x[:, None] - breaks, 0), 1)
        return jnp.sum(p.reshape(n, k), 1)

    def slope(u_c):
        x = at(u_c)
        s = coef[:, 1] + jnp.sum(jnp.where(x[:, None] > breaks,
                                           coef[:, 2:], 0), 1)
        s = s / jnp.repeat(jnp.clip(cap, 1e-6, None), k)
        return jnp.sum(s.reshape(n, k) * lam, 1)

    return power, slope


# ---------------------------------------------------------- the forecasts

def _ewma(x, half_life):
    a = 1 - jnp.exp(jnp.log(0.5) / half_life)
    level = x[0]
    for i in range(1, x.shape[0]):
        level = a * x[i] + (1 - a) * level
    return level


def _weeks(x):
    nw = x.shape[0] // 7
    return x[x.shape[0] - 7 * nw:].reshape((nw, 7) + x.shape[1:])


def _corrector(actual8, pred8):
    """Least-squares coefficient of a day's deviation on the previous
    day's, over the last eight days, clipped to [-1, 1]."""
    dev = actual8 - pred8
    return jnp.clip(jnp.sum(dev[:-1] * dev[1:])
                    / jnp.clip(jnp.sum(dev[:-1] ** 2), 1e-9, None), -1, 1)


# day-of-week slots of the trailing whole weeks, counted from the forecast
# day: slot 0 is the forecast day's weekday, slot 6 yesterday's
LAST8 = jnp.asarray([6, 0, 1, 2, 3, 4, 5, 6])


def forecast_hourly(h):
    """Next day's hourly usage from a cluster's daily history (days, 24):
    EWMA weekly level (half-life half a week) times EWMA hour-of-week
    factors (half-life four weeks), corrected by yesterday's deviation."""
    w = _weeks(h)
    level = _ewma(jnp.mean(w, (1, 2)), 0.5)
    fac = _ewma(w / jnp.clip(jnp.mean(w, (1, 2), keepdims=True), 1e-9,
                             None), 4.0)
    k = _corrector(jnp.mean(h[-8:], 1), level * jnp.mean(fac[LAST8], 1))
    return jnp.clip(level * fac[0] + k * (h[-1] - level * fac[6]), 0, None)


def forecast_daily(x):
    """Next day's total from a daily series, by the same method."""
    w = _weeks(x)
    level = _ewma(jnp.mean(w, 1), 0.5)
    fac = _ewma(w / jnp.clip(jnp.mean(w, 1, keepdims=True), 1e-9, None),
                4.0)
    k = _corrector(x[-8:], level * fac[LAST8])
    return jnp.clip(level * fac[0] + k * (x[-1] - level * fac[6]), 0, None)


def ratio_of(a, b, u):
    return jnp.clip(a + b * jnp.log(jnp.clip(u, 1e-9, None)), 1, 10)


def rel_err_quantile(pred, actual, q):
    return jnp.quantile((actual - pred)
                        / jnp.clip(jnp.abs(pred), 1e-9, None), q)


def forecasts(s, gamma):
    """The day-ahead forecasts of section III-B from the history."""
    n = s["hist_uif"].shape[0]
    dt = s["hist_uif"].dtype
    uif = jax.vmap(forecast_hourly)(s["hist_uif"])
    tuf = jax.vmap(forecast_daily)(s["hist_flex"])
    tr = jax.vmap(forecast_daily)(s["hist_resd"])
    use = s["hist_usage"][:, -28:].reshape(n, -1)
    res = s["hist_res"][:, -28:].reshape(n, -1)
    r = res / jnp.clip(use, 1e-9, None)
    x = jnp.log(jnp.clip(use, 1e-9, None))
    xc = x - jnp.mean(x, 1, keepdims=True)
    rb = jnp.sum(xc * (r - jnp.mean(r, 1, keepdims=True)), 1) \
        / jnp.clip(jnp.sum(xc ** 2, 1), 1e-9, None)
    ra = jnp.mean(r, 1) - rb * jnp.mean(x, 1)
    e97 = jax.vmap(lambda p, a: rel_err_quantile(p, a, 0.97))(
        s["hist_tr_pred"], s["hist_resd"])
    theta = tr * (1 + jnp.clip(e97, 0, 2))               # eq. 2
    rr = ratio_of(ra[:, None], rb[:, None], uif + tuf[:, None] / 24)
    alpha = jnp.clip((theta - hsum(uif * rr))             # eq. 3
                     / jnp.clip(hsum(tuf[:, None] / 24 * rr), 1e-9, None),
                     0.5, 4.0)
    eq = jax.vmap(lambda p, a: rel_err_quantile(
        p.reshape(-1), a.reshape(-1), (1 - gamma).astype(dt)))(
        s["hist_uif_pred"][:, -28:], s["hist_uif"][:, -28:])
    return {"uif": uif, "tuf": tuf, "tr": tr, "ra": ra, "rb": rb,
            "alpha": alpha, "uif_q": uif * (1 + jnp.clip(eq, 0, 1))[:, None]}


def spatial_shift(p, mobility):
    """Move daily budgets toward the clusters where a CPU-day costs the
    least carbon, each exporting at most ``mobility`` of its budget and
    importing at most that or its spare daily capacity: the exact
    minimizer of the linear cost over the fleet's conservation."""
    price = jnp.mean(p["eta"] * p["pi"], 1)
    head = jnp.clip(jnp.sum(jnp.clip(p["capacity"][:, None] / p["ratio"]
                                     - p["u_if"], 0, None), 1) - p["tau"],
                    0, None)
    lo = -mobility * p["tau"]
    ub = jnp.minimum(mobility * p["tau"], head)
    order = jnp.argsort(price)
    room = (ub - lo)[order]
    spend = jnp.clip(-jnp.sum(lo) - (jnp.cumsum(room) - room), 0, room)
    shift = lo + jnp.zeros_like(lo).at[order].set(spend)
    return jnp.clip(p["tau"] + shift, 0, None)


# -------------------------------------------------------------- the day

def _roll(h, new):
    return jnp.concatenate([h[:, 1:], new[:, None]], 1)


def simulate(row, fleet, solver, days, solve_dt="float32"):
    """One rollout: ``hist_days`` unshaped burn-in days, campus contracts
    at 97% of the burned-in fitted campus peaks, then ``days`` shaped
    days, each with one day-ahead solve in ``solve_dt``. Returns the
    ledger's per-cluster totals and the burned-in flexible backlog."""
    dt = jnp.float32
    n, m, z = fleet["n_clusters"], fleet["n_campuses"], fleet["n_zones"]
    hd = fleet["hist_days"]
    cast = (lambda a: a.astype(dt)
            if jnp.issubdtype(a.dtype, jnp.floating) else a)
    row = jax.tree.map(cast, row)
    truth, zone, key = row["truth"], row["zone"], row["key"]
    cap = truth["capacity"]
    campus = jnp.arange(n) % m
    zmap = campus % z
    allowance = fleet["slo_allowance"]
    zeros = jnp.zeros((n, hd, H), dt)
    s = {"day": jnp.zeros((), jnp.int32), "hist_uif": zeros,
         "hist_flex": jnp.zeros((n, hd), dt),
         "hist_resd": jnp.zeros((n, hd), dt), "hist_usage": zeros,
         "hist_res": zeros, "carbon": jnp.zeros((z, hd, H), dt),
         "queue": jnp.zeros((n,), dt), "cf_queue": jnp.zeros((n,), dt)}
    ones_z = jnp.ones((z,), dt)

    def burn(s, _):
        k = jax.random.fold_in(key, s["day"])
        act, _ = grid_day(zone, s["carbon"], jax.random.fold_in(k, 4),
                          ones_z, ones_z, dt)
        u_if, arr, ratio = actual_load(truth, s["day"], k, cap,
                                       jnp.ones_like(cap), None, dt)
        r = admit(jnp.broadcast_to(cap[:, None] * 10, (n, H)), u_if, arr,
                  ratio, cap, s["queue"], lambda u: 100 + 300 * u,
                  act[zmap], allowance)
        return dict(s, day=s["day"] + 1,
                    hist_uif=_roll(s["hist_uif"], u_if),
                    hist_flex=_roll(s["hist_flex"], r["served"]),
                    hist_resd=_roll(s["hist_resd"], hsum(r["res"])),
                    hist_usage=_roll(s["hist_usage"], r["usage"]),
                    hist_res=_roll(s["hist_res"], r["res"]),
                    carbon=_roll(s["carbon"], act),
                    queue=r["queue_end"], cf_queue=r["queue_end"]), None

    s, _ = jax.lax.scan(burn, s, None, length=hd)
    queue0 = s["queue"]
    power, _ = power_model(row, s["hist_usage"],
                           jax.random.fold_in(key, 999), dt)
    peak = jnp.max(jax.vmap(power, 1, 1)(
        s["hist_usage"][:, -7:].reshape(n, -1)), 1)
    limit = 0.97 * jax.ops.segment_sum(peak, campus, num_segments=m)
    izeros = jnp.zeros((n,), jnp.int32)
    s = dict(s, hist_tr_pred=s["hist_resd"], hist_uif_pred=s["hist_uif"],
             streak=izeros, pause=izeros, allowed=jnp.ones((n,), bool))
    names = ("carbon_kg", "kwh", "served", "arrived", "cf_carbon_kg",
             "cf_kwh", "cf_served")
    led = {k: jnp.zeros((n,), dt) for k in names}
    hour_ch = {k: row.get(k) for k in ("arrival_hour_scale",
                                       "carbon_hour_scale")}

    def day(carry, d):
        s, led = carry
        k = jax.random.fold_in(key, s["day"])
        cap_day = cap * row["cap_scale"][d]
        power, slope = power_model(row, s["hist_usage"],
                                   jax.random.fold_in(k, 1), dt)
        fc = forecasts(s, row["gamma"])
        act, eta_fc = grid_day(zone, s["carbon"], jax.random.fold_in(k, 4),
                               row["green_scale"][d], row["coal_scale"][d],
                               dt)
        if hour_ch["carbon_hour_scale"] is not None:
            act = act * hour_ch["carbon_hour_scale"][d][None]
        tau = fc["alpha"] * fc["tuf"] + s["queue"]
        u_nom = fc["uif"] + tau[:, None] / 24
        p = {"eta": eta_fc[zmap], "u_if": fc["uif"], "u_if_q": fc["uif_q"],
             "tau": tau, "pow_nom": jax.vmap(power, 1, 1)(u_nom),
             "pi": jax.vmap(slope, 1, 1)(u_nom),
             "u_pow_cap": 0.95 * cap * row["cap_scale"][d],
             "capacity": cap_day,
             "ratio": ratio_of(fc["ra"][:, None], fc["rb"][:, None], u_nom),
             "campus": campus,
             "campus_limit": limit * row["campus_scale"][d],
             "lambda_e": row["lambda_e"], "lambda_p": row["lambda_p"],
             "drop_limit": 0.8}
        p["tau"] = spatial_shift(p, row["mobility"])
        p = {k: v.astype(solve_dt) if jnp.issubdtype(v.dtype, jnp.floating)
             else v for k, v in p.items() if k != "drop_limit"}
        _, _, vcc, ok = solve_day(dict(p, drop_limit=0.8), solver)
        vcc = vcc.astype(dt)
        gate = s["allowed"] & ok
        vcc = jnp.where(gate[:, None], vcc, cap_day[:, None] * 10)
        arr_hour = None if hour_ch["arrival_hour_scale"] is None \
            else hour_ch["arrival_hour_scale"][d]
        u_if, arr, ratio = actual_load(truth, s["day"], k, cap_day,
                                       row["arrival_scale"][d], arr_hour,
                                       dt)
        eta = act[zmap]
        r = admit(vcc, u_if, arr, ratio, cap_day, s["queue"], power, eta,
                  allowance)
        cf = admit(jnp.broadcast_to(cap_day[:, None] * 10, (n, H)), u_if,
                   arr, ratio, cap_day, s["cf_queue"], power, eta,
                   allowance)
        # SLO feedback: two crowded days in a row pause shaping a week
        paused = s["pause"] > 0
        crowded = hsum(r["res"]) >= fleet["slo_margin"] * hsum(vcc)
        streak = jnp.where(paused, s["streak"],
                           jnp.where(crowded, s["streak"] + 1, 0))
        trig = ~paused & (streak >= 2)
        pause = jnp.where(trig, fleet["slo_pause_days"],
                          jnp.maximum(s["pause"] - 1, 0))
        s = dict(s, day=s["day"] + 1, streak=jnp.where(trig, 0, streak),
                 pause=pause, allowed=pause == 0,
                 hist_uif=_roll(s["hist_uif"], u_if),
                 hist_flex=_roll(s["hist_flex"], r["served"]),
                 hist_resd=_roll(s["hist_resd"], hsum(r["res"])),
                 hist_usage=_roll(s["hist_usage"], r["usage"]),
                 hist_res=_roll(s["hist_res"], r["res"]),
                 hist_tr_pred=_roll(s["hist_tr_pred"], fc["tr"]),
                 hist_uif_pred=_roll(s["hist_uif_pred"], fc["uif"]),
                 carbon=_roll(s["carbon"], act),
                 queue=r["queue_end"], cf_queue=cf["queue_end"])
        led = {"carbon_kg": led["carbon_kg"] + hsum(r["carbon"]),
               "kwh": led["kwh"] + hsum(r["power"]),
               "served": led["served"] + r["served"],
               "arrived": led["arrived"] + r["arrived"],
               "cf_carbon_kg": led["cf_carbon_kg"] + hsum(cf["carbon"]),
               "cf_kwh": led["cf_kwh"] + hsum(cf["power"]),
               "cf_served": led["cf_served"] + cf["served"]}
        return (s, led), None

    (s, led), _ = jax.lax.scan(day, (s, led), jnp.arange(days))
    return dict(led, queue0=queue0, queue_end=s["queue"],
                usage=s["hist_usage"][:, hd - days:])
