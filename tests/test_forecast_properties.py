"""Hypothesis property tests for core/forecast.py (PR 3 satellite).

Pinned invariants of the day-ahead forecasting pipeline (paper §III-B1 /
eq. 2-3): quantile monotonicity, EWMA/weekly-mean boundedness, and the
eq. 3 alpha inflation being >= 1 and non-decreasing in the trailing
forecast error on self-consistent inputs.

Skips as a unit when the `hypothesis` capability is absent (the CI
workflow installs it and runs these under the fixed-seed `ci` profile
registered in conftest.py).
"""
import pytest

hypothesis = pytest.importorskip(
    "hypothesis",
    reason="capability check: the `hypothesis` package is not importable "
           "here; CI installs it (see .github/workflows/ci.yml) and runs "
           "these property tests under the fixed-seed 'ci' profile")
import hypothesis.extra.numpy as hnp  # noqa: E402
import hypothesis.strategies as st  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from hypothesis import given, settings  # noqa: E402

from repro.core import forecast  # noqa: E402

SET = dict(max_examples=25, deadline=None,
           suppress_health_check=[hypothesis.HealthCheck.too_slow])


@given(
    pred=hnp.arrays(np.float32, (30,),
                    elements=st.floats(0.5, 10.0, width=32)),
    act=hnp.arrays(np.float32, (30,),
                   elements=st.floats(0.125, 20.0, width=32)),
    q1=st.floats(0.05, 0.95),
    dq=st.floats(0.0, 0.049),
)
@settings(**SET)
def test_relative_error_quantile_monotone_in_q(pred, act, q1, dq):
    """Higher quantile level -> larger (1-gamma) error inflation: the
    power-capping chance constraint tightens monotonically with gamma."""
    lo = forecast.relative_error_quantile(jnp.asarray(pred),
                                          jnp.asarray(act), q1)
    hi = forecast.relative_error_quantile(jnp.asarray(pred),
                                          jnp.asarray(act), q1 + dq)
    assert float(hi) >= float(lo) - 1e-6


@given(
    x=hnp.arrays(np.float32, (21,),
                 elements=st.floats(0.0, 100.0, width=32)),
    hl=st.floats(0.125, 16.0),
)
@settings(**SET)
def test_ewma_bounded_by_input_range(x, hl):
    """EWMA is a convex combination chain: the level never escapes
    [min(x), max(x)]."""
    level = float(forecast.ewma(jnp.asarray(x), hl))
    assert x.min() - 1e-4 <= level <= x.max() + 1e-4


@given(
    daily=hnp.arrays(np.float32, (28,),
                     elements=st.floats(0.125, 50.0, width=32)),
    hl=st.floats(0.125, 8.0),
)
@settings(**SET)
def test_weekly_mean_forecast_bounded_by_input_range(daily, hl):
    """The weekly-mean forecast averages then EWMAs: it stays within the
    range of the daily history."""
    fc = float(forecast.weekly_mean_forecast(jnp.asarray(daily), hl))
    assert daily.min() - 1e-4 <= fc <= daily.max() + 1e-4


@given(
    uif=hnp.arrays(np.float32, (24,),
                   elements=st.floats(0.125, 5.0, width=32)),
    tuf=st.floats(0.5, 20.0),
    ratio_a=st.floats(1.05, 2.0),
    eps=st.floats(0.0, 2.0),
    deps=st.floats(0.0, 1.0),
)
@settings(**SET)
def test_alpha_inflation_geq_one_and_monotone_in_error(uif, tuf, ratio_a,
                                                       eps, deps):
    """eq. 3 semantics on self-consistent inputs: when the reservations
    forecast equals the reservations implied by (uif, tuf, R) exactly,
    alpha == 1 at zero trailing error, alpha >= 1 for any eps_q97 >= 0,
    and alpha is non-decreasing in eps (less accurate forecasts inflate
    the flexible budget more). The production pipeline clips to
    [0.5, 4.0] because real histories need not be self-consistent."""
    uif_j = jnp.asarray(uif)
    tuf_j = jnp.asarray(tuf, jnp.float32)
    a = jnp.asarray(ratio_a, jnp.float32)
    b = jnp.zeros((), jnp.float32)          # flat ratio: R == ratio_a
    u_nom = uif_j + tuf_j / 24.0
    r = forecast.ratio_at(a, b, u_nom)
    tr_consistent = jnp.sum((uif_j + tuf_j / 24.0) * r)

    def alpha_at(e):
        theta = forecast.theta_requirement(tr_consistent,
                                           jnp.asarray(e, jnp.float32))
        return float(forecast.alpha_inflation(theta, uif_j, tuf_j, a, b))

    a0 = alpha_at(0.0)
    assert abs(a0 - 1.0) < 5e-3             # perfect forecast -> alpha 1
    a1, a2 = alpha_at(eps), alpha_at(min(eps + deps, 2.0))
    assert a1 >= 1.0 - 5e-3                 # (f32 sum accumulation slack)
    assert a2 >= a1 - 1e-5                  # monotone in trailing error


@given(
    tr=st.floats(0.125, 100.0),
    eps=st.floats(-1.0, 3.0),
)
@settings(**SET)
def test_theta_requirement_bounds(tr, eps):
    """Theta = T_R-hat * (1 + clip(eps, 0, 2)): never below the forecast,
    at most 3x it (eq. 2 with the production clip)."""
    theta = float(forecast.theta_requirement(
        jnp.asarray(tr, jnp.float32), jnp.asarray(eps, jnp.float32)))
    assert tr * (1.0 - 1e-6) <= theta <= 3.0 * tr * (1.0 + 1e-6)
