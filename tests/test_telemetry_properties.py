"""Property tests for the telemetry calibration metric primitives.

``sim.telemetry.mape`` / ``bias`` / ``coverage`` / ``level_drift`` are
the in-graph forecast-calibration channels; these pin their algebraic
invariants over random inputs: coverage is a fraction in [0, 1], MAPE is
non-negative, a zero-error forecast has exactly zero bias, zero MAPE and
full coverage, and the drift gauge vanishes exactly at the trailing-
window mean it is measured against.
"""
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis",
    reason="capability check: the `hypothesis` package is not importable "
           "here; CI installs it (see .github/workflows/ci.yml) and runs "
           "these property tests under the fixed-seed 'ci' profile")
import hypothesis.extra.numpy as hnp  # noqa: E402
import hypothesis.strategies as st  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from hypothesis import given, settings  # noqa: E402

from repro.core.admission import hour_sum  # noqa: E402
from repro.sim import telemetry as T  # noqa: E402

SET = dict(max_examples=25, deadline=None,
           suppress_health_check=[hypothesis.HealthCheck.too_slow])


@given(
    pred=hnp.arrays(np.float32, (3, 24),
                    elements=st.floats(0.0, 50.0, width=32)),
    act=hnp.arrays(np.float32, (3, 24),
                   elements=st.floats(0.0, 50.0, width=32)),
)
@settings(**SET)
def test_coverage_in_unit_interval_and_mape_nonneg(pred, act):
    cov = np.asarray(T.coverage(jnp.asarray(pred), jnp.asarray(act)))
    assert np.all(cov >= 0.0) and np.all(cov <= 1.0)
    m = np.asarray(T.mape(jnp.asarray(pred), jnp.asarray(act)))
    assert np.all(m >= 0.0)


@given(
    act=hnp.arrays(np.float32, (4, 24),
                   elements=st.floats(0.125, 50.0, width=32)),
)
@settings(**SET)
def test_zero_error_forecast_zero_bias_zero_mape_full_coverage(act):
    a = jnp.asarray(act)
    np.testing.assert_array_equal(np.asarray(T.bias(a, a)),
                                  np.zeros(act.shape[0], np.float32))
    np.testing.assert_array_equal(np.asarray(T.mape(a, a)),
                                  np.zeros(act.shape[0], np.float32))
    # actual <= its own bound everywhere -> coverage exactly 1
    np.testing.assert_array_equal(np.asarray(T.coverage(a, a)),
                                  np.ones(act.shape[0], np.float32))


@given(
    trail=hnp.arrays(np.float32, (4, 7),
                     elements=st.floats(0.125, 50.0, width=32)),
)
@settings(**SET)
def test_level_drift_nonneg_and_zero_at_trailing_mean(trail):
    tr = jnp.asarray(trail)
    fc = 0.5 * (tr.min(axis=1) + tr.max(axis=1))
    d = np.asarray(T.level_drift(fc, tr))
    assert np.all(d >= 0.0)
    mean = T.level_drift(hour_sum(tr) / 7.0, tr)
    np.testing.assert_allclose(np.asarray(mean), 0.0, atol=1e-6)
