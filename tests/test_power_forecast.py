"""Power models (paper §III-A: MAPE < 5%) and day-ahead forecasting
(§III-B): EWMA pipeline, ratio model, quantiles, eq. (3) inflation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import forecast, power, stages


def test_pd_fit_mape_under_5pct():
    key = jax.random.PRNGKey(0)
    n_pd, t = 32, 24 * 28
    truth = power.PDTruth(
        idle_kw=60 + 40 * jax.random.uniform(jax.random.fold_in(key, 1),
                                             (n_pd,)),
        slope_kw=250 + 150 * jax.random.uniform(jax.random.fold_in(key, 2),
                                                (n_pd,)),
        curve=0.8 + 0.5 * jax.random.uniform(jax.random.fold_in(key, 3),
                                             (n_pd,)))
    cpu = 0.2 + 0.6 * jax.random.uniform(jax.random.fold_in(key, 4),
                                         (n_pd, t))
    pw = power.simulate_pd_power(jax.random.fold_in(key, 5), truth, cpu)
    coef, breaks = jax.jit(jax.vmap(power.fit_pd_model))(cpu, pw)
    mapes = np.asarray(power.daily_mape_b(coef, breaks, cpu, pw))
    # paper: daily MAPE < 5% for > 95% of PDs
    assert (mapes < 0.05).mean() > 0.95, mapes.max()


def test_slope_is_derivative():
    cpu = jnp.linspace(0.05, 0.95, 500)
    pw = 100 + 300 * cpu ** 1.2
    coef, breaks = power.fit_pd_model(cpu, pw)
    u = jnp.asarray([0.3, 0.6, 0.8])
    eps = 1e-3
    fd = (power.pd_power(coef, breaks, u + eps)
          - power.pd_power(coef, breaks, u - eps)) / (2 * eps)
    sl = power.pd_slope(coef, breaks, u)
    np.testing.assert_allclose(np.asarray(fd), np.asarray(sl), rtol=1e-2)


def _ref_basis(u, breaks):
    """The hinge basis [1, u, relu(u - b_k)...] as a (t, K+2) array."""
    cols = [jnp.ones_like(u), u]
    for k in range(breaks.shape[0]):
        cols.append(jnp.maximum(u - breaks[k], 0.0))
    return jnp.stack(cols, axis=-1)


def _ref_fit(cpu, pw):
    """The per-PD fit, each row on its own: its own quantiles, min and
    max, and the full outer products."""
    qs = jnp.linspace(0.0, 1.0, power.N_BREAKS + 2)[1:-1]
    breaks = jnp.quantile(cpu, qs)
    lo = jnp.min(cpu)
    span = jnp.clip(jnp.max(cpu) - lo, 1e-6, None)
    X = _ref_basis((cpu - lo) / span, (breaks - lo) / span)
    XtX = power._pairwise_sum(X[..., :, None] * X[..., None, :], axis=-3) \
        + 1e-4 * cpu.shape[-1] * jnp.eye(X.shape[-1])
    Xty = power._pairwise_sum(X * pw[..., None], axis=-2)
    cx = power._solve_spd(XtX, Xty)
    coef = jnp.concatenate([cx[:1] - cx[1:2] * lo / span, cx[1:] / span])
    return coef, breaks


@jax.jit
def _ref_power_stage(hist, lam, capacity, pdt, key):
    """Each PD's usage row (lam * u) / cap, sorted and fitted per PD."""
    n, npd = lam.shape
    u_cl = hist[:, -28:].reshape(n, -1)
    u_pd = (lam[..., None] * u_cl[:, None, :]).reshape(n * npd, -1)
    cap = jnp.clip(capacity[:, None, None].repeat(npd, 1)
                   .reshape(n * npd, 1), 1e-6, None)
    u_norm = u_pd / cap
    p_pd = power.simulate_pd_power(key, power.PDTruth(*pdt), u_norm)
    return jax.vmap(_ref_fit)(u_norm, p_pd)


@pytest.mark.parametrize("npd,window", [
    (1, "random"), (2, "random"), (4, "random"), (2, "ties"),
    (2, "flat")])
def test_power_stage_matches_per_pd_fit(npd, window):
    """One sort per cluster, the PD order statistics mapped from it, and
    the distinct normal-equation entries: the same bits as sorting and
    fitting every PD row. ``ties`` rounds usage to a coarse grid;
    ``flat`` holds one cluster constant and another within 1e-5, so
    their spans hit the 1e-6 clip."""
    rng = np.random.RandomState(npd)
    n = 16
    hist = rng.uniform(50, 400, (n, 30, 24)).astype(np.float32)
    if window == "ties":
        hist = np.round(hist / 25) * 25
    if window == "flat":
        hist[3] = 123.0
        hist[5] = 200.0 + 1e-5 * rng.rand(30, 24)
    lam = rng.dirichlet(np.ones(npd), n).astype(np.float32)
    cap = rng.uniform(300, 900, n).astype(np.float32)
    pdt = tuple(jnp.asarray(rng.uniform(a, b, n * npd), jnp.float32)
                for a, b in ((60, 100), (250, 400), (0.7, 1.3)))
    key = jax.random.PRNGKey(7)
    coef, breaks = _ref_power_stage(hist, lam, cap, pdt, key)
    got = jax.jit(lambda h, lm, c, p, k: stages.power_stage(
        h, lm, c, power.PDTruth(*p), k))(hist, lam, cap, pdt, key)
    np.testing.assert_array_equal(np.asarray(got.coef), np.asarray(coef))
    np.testing.assert_array_equal(np.asarray(got.breaks),
                                  np.asarray(breaks))


@pytest.mark.parametrize("qs", [power.BREAK_QS, [0.0], [1.0]],
                         ids=["fit", "q0", "q1"])
def test_quantiles_sorted_matches_jnp_quantile(qs):
    """Order statistics of a sorted row, mapped through a non-decreasing
    map and combined, equal ``jnp.quantile`` of the mapped row bitwise
    (rows with ties included)."""
    rng = np.random.RandomState(0)
    x = rng.uniform(0, 300, (6, 672)).astype(np.float32)
    x[1] = np.round(x[1] / 50) * 50
    lam = jnp.asarray(rng.uniform(0.1, 0.9, (6, 3)), jnp.float32)
    cap = jnp.asarray(rng.uniform(300, 900, (6, 1, 1)), jnp.float32)

    def f(v):                       # (6, m) -> (6, 3, m)
        return (lam[..., None] * v[:, None, :]) / cap

    qs = np.asarray(qs, np.float32)
    got = jax.jit(lambda x: power.quantiles_sorted(
        jnp.sort(x, axis=-1, stable=False), qs, f))(x)
    want = jax.jit(lambda x: jnp.moveaxis(
        jnp.quantile(f(x), qs, axis=-1), 0, -1))(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("t", [672, 90, 7])
def test_normal_equations_match_full_outer_products(t):
    """The 19 distinct sums equal ``_pairwise_sum`` of the full outer
    products bitwise, alone and batched (t = 7 takes the zero-padded
    halvings)."""
    rng = np.random.RandomState(t)
    x = jnp.asarray(rng.uniform(0, 1, (3, t)), jnp.float32)
    q = jnp.asarray(np.sort(rng.uniform(0, 1, (3, power.N_BREAKS)), -1),
                    jnp.float32)
    y = jnp.asarray(rng.uniform(100, 400, (3, t)), jnp.float32)

    def full(x, q, y):
        X = _ref_basis(x, q)
        return (power._pairwise_sum(X[:, :, None] * X[:, None, :], axis=-3),
                power._pairwise_sum(X * y[:, None], axis=-2))

    for got, want in (
            (jax.jit(jax.vmap(power.normal_equations))(x, q, y),
             jax.jit(jax.vmap(full))(x, q, y)),
            (jax.jit(power.normal_equations)(x[0], q[0], y[0]),
             jax.jit(full)(x[0], q[0], y[0]))):
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_usage_fractions_near_constant():
    key = jax.random.PRNGKey(2)
    base = jnp.asarray([0.4, 0.3, 0.2, 0.1])[:, None]
    usage = base * (5.0 + jnp.sin(jnp.arange(200.0))[None]) \
        * (1 + 0.01 * jax.random.normal(key, (4, 200)))
    lam = power.usage_fractions(usage)
    np.testing.assert_allclose(np.asarray(lam), np.asarray(base[:, 0]),
                               atol=0.01)


def _history(days=35, seed=0):
    rng = np.random.RandomState(seed)
    hours = np.arange(24)
    prof = 1 + 0.3 * np.exp(-0.5 * ((hours - 14) / 4.0) ** 2)
    hist = []
    for d in range(days):
        wk = 1 + 0.1 * np.cos(2 * np.pi * (d % 7) / 7)
        hist.append(5.0 * prof * wk * (1 + 0.03 * rng.randn(24)))
    return jnp.asarray(np.stack(hist))


def test_inflexible_forecast_accuracy():
    hist = _history()
    pred = forecast.forecast_inflexible(hist[:-1], jnp.asarray(34 % 7))
    ape = np.abs(np.asarray(pred) - np.asarray(hist[-1])) \
        / np.asarray(hist[-1])
    assert np.median(ape) < 0.10        # paper Fig 7: median < 10%


def test_ratio_model_monotone_decreasing():
    rng = np.random.RandomState(0)
    usage = jnp.asarray(np.exp(rng.uniform(0, 3, size=500)))
    res = usage * (1.1 + 0.5 / jnp.sqrt(usage))
    a, b = forecast.fit_ratio_model(usage, res)
    r_small = forecast.ratio_at(a, b, jnp.asarray(1.0))
    r_big = forecast.ratio_at(a, b, jnp.asarray(20.0))
    assert float(r_big) <= float(r_small)
    assert float(r_big) >= 1.0          # ratio >= 1 by construction


def test_alpha_solves_eq3():
    """Plugging alpha back into eq. (3) must reproduce Theta."""
    key = jax.random.PRNGKey(3)
    uif = 4.0 + jax.random.uniform(key, (24,))
    tuf = jnp.asarray(30.0)
    a, b = jnp.asarray(1.4), jnp.asarray(-0.05)
    theta = jnp.asarray(230.0)
    alpha = forecast.alpha_inflation(theta, uif, tuf, a, b)
    u_nom = uif + tuf / 24.0
    r = forecast.ratio_at(a, b, u_nom)
    lhs = jnp.sum((uif + alpha * tuf / 24.0) * r)
    # exact unless alpha hit its [0.5, 4] clip
    if 0.5 < float(alpha) < 4.0:
        np.testing.assert_allclose(float(lhs), float(theta), rtol=1e-4)


def test_deviation_corrector_unbiased_on_periodic_series():
    """Regression for the deviation-corrector bug: the coef used to be
    fit against a CONSTANT weekly level, so a purely periodic series
    (zero true deviations) leaked its day-of-week pattern into the
    'deviations' and produced a spurious correction. Fit against the
    dow-factored weekly predictions, an exactly periodic history must
    forecast (close to) exactly."""
    pattern = np.asarray([1.2, 1.1, 1.0, 0.9, 0.8, 1.05, 0.95])
    days = 35
    daily = jnp.asarray(10.0 * pattern[np.arange(days) % 7], jnp.float32)
    hours = 1.0 + 0.3 * np.sin(np.arange(24) / 24.0 * 2 * np.pi)
    hourly = jnp.asarray(
        10.0 * pattern[np.arange(days) % 7][:, None] * hours[None],
        jnp.float32)
    for dow_next in range(7):
        hist = daily[:days - 7 + dow_next]
        truth = float(daily[days - 7 + dow_next])
        pred = float(forecast.forecast_daily_total(
            hist, jnp.asarray(hist.shape[0] % 7)))
        assert abs(pred - truth) / truth < 1e-3, (dow_next, pred, truth)
        hist_h = hourly[:days - 7 + dow_next]
        pred_h = forecast.forecast_inflexible(
            hist_h, jnp.asarray(hist_h.shape[0] % 7))
        ape = np.abs(np.asarray(pred_h)
                     - np.asarray(hourly[days - 7 + dow_next])) \
            / np.asarray(hourly[days - 7 + dow_next])
        assert ape.max() < 1e-3, (dow_next, ape.max())


def test_calibrate_half_lives_vectorized_matches_loop():
    """The single vmapped+jitted grid evaluation must select the same
    half-lives as the legacy per-combo Python loop (fixed seed)."""
    hist = _history(days=42, seed=3)
    grid = (0.25, 1.0, 4.0)
    got = forecast.calibrate_half_lives(hist, grid=grid)
    want = forecast.calibrate_half_lives_loop(hist, grid=grid)
    assert got == want, (got, want)


def test_theta_is_97th_quantile_requirement():
    preds = jnp.full((90,), 100.0)
    actuals = jnp.asarray(100.0 + np.random.RandomState(0).randn(90) * 5)
    q = forecast.relative_error_quantile(preds, actuals, 0.97)
    theta = forecast.theta_requirement(jnp.asarray(100.0), q)
    # Theta must cover ~97% of historical outcomes
    covered = (np.asarray(actuals) <= float(theta)).mean()
    assert covered >= 0.95
