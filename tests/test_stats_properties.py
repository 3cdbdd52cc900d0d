"""Hypothesis property tests for core/stats.py (PR 5 satellite).

The streaming prediction layer's contract: every incremental estimator
equals its batch recomputation —

  * the EWMA carry applies ``forecast.ewma``'s recursion EXACTLY, so
    stepping ``ewma_update`` over a series is bitwise the batch scan;
  * exponentially-weighted regression moments reproduce a direct
    weighted least-squares fit within float tolerance;
  * ring buffers are exact windows: their quantiles equal the quantile
    of the trailing raw values bitwise.

Skips as a unit when the `hypothesis` capability is absent (the CI
workflow installs it and runs these under the fixed-seed `ci` profile).
"""
import pytest

hypothesis = pytest.importorskip(
    "hypothesis",
    reason="capability check: the `hypothesis` package is not importable "
           "here; CI installs it (see .github/workflows/ci.yml) and runs "
           "these property tests under the fixed-seed 'ci' profile")
import hypothesis.extra.numpy as hnp  # noqa: E402
import hypothesis.strategies as st  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from hypothesis import given, settings  # noqa: E402

from repro.core import forecast, stats  # noqa: E402

SET = dict(max_examples=25, deadline=None,
           suppress_health_check=[hypothesis.HealthCheck.too_slow])


@given(
    x=hnp.arrays(np.float32, (20,),
                 elements=st.floats(0.0, 100.0, width=32)),
    hl=st.floats(0.125, 16.0),
)
@settings(**SET)
def test_ewma_incremental_matches_batch_scan_bitwise(x, hl):
    """Carrying ``ewma_update`` across the series is the SAME recursion
    ``forecast.ewma`` scans — level bitwise-equal at every length. The
    incremental step runs COMPILED (``jax.jit``), as it always does in
    the streaming day step: XLA contracts the step's mul+add identically
    in the straight-line and scan-body forms (fully-eager dispatch may
    differ in the last ulp — the repo-wide eager-vs-compiled caveat)."""
    upd = jax.jit(forecast.ewma_update)
    alpha = forecast.ewma_alpha(hl)
    level = jnp.asarray(x[0])
    for i, xi in enumerate(x[1:], start=2):
        level = upd(level, jnp.asarray(xi), alpha)
        batch = forecast.ewma(jnp.asarray(x[:i]), hl)
        np.testing.assert_array_equal(np.asarray(level), np.asarray(batch))


@given(
    x=hnp.arrays(np.float64, (6, 8),
                 elements=st.floats(-5.0, 5.0, width=64)),
    noise=hnp.arrays(np.float64, (6, 8),
                     elements=st.floats(-0.5, 0.5, width=64)),
    a=st.floats(-2.0, 2.0),
    b=st.floats(-2.0, 2.0),
    hl=st.floats(1.0, 20.0),
)
@settings(**SET)
def test_ew_moments_match_direct_weighted_least_squares(x, noise, a, b, hl):
    """T daily batches absorbed through ``ew_update`` fit y ~ a + b x
    identically (within float tolerance) to a direct weighted LSQ with
    per-day weights rho^(T-1-t)."""
    T, k = x.shape
    y = a + b * x + noise
    rho = float(stats.decay_from_half_life(hl))
    m = stats.ew_init(jnp.asarray(x[:1], jnp.float32).reshape(1, -1),
                      jnp.asarray(y[:1], jnp.float32).reshape(1, -1))
    for t in range(1, T):
        m = stats.ew_update(m, jnp.asarray(x[t:t + 1], jnp.float32),
                            jnp.asarray(y[t:t + 1], jnp.float32), rho)
    a_s, b_s = stats.ew_linfit(m)
    # direct weighted normal equations in float64
    w = np.repeat(rho ** np.arange(T - 1, -1, -1.0), k)
    xf, yf = x.reshape(-1), y.reshape(-1)
    sw, sx, sy = w.sum(), (w * xf).sum(), (w * yf).sum()
    sxx, sxy = (w * xf * xf).sum(), (w * xf * yf).sum()
    den = sxx - sx * sx / sw
    if den < 1e-3 * sw:        # degenerate x spread: fit ill-conditioned
        return
    b_d = (sxy - sx * sy / sw) / den
    a_d = sy / sw - b_d * sx / sw
    np.testing.assert_allclose(float(b_s[0]), b_d, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(float(a_s[0]), a_d, rtol=2e-3, atol=2e-2)


@given(
    init=hnp.arrays(np.float32, (3, 5),
                    elements=st.floats(-10.0, 10.0, width=32)),
    pushes=hnp.arrays(np.float32, (9, 3),
                      elements=st.floats(-10.0, 10.0, width=32)),
    q=st.floats(0.0, 1.0),
)
@settings(**SET)
def test_ring_buffer_quantiles_exact(init, pushes, q):
    """After any number of pushes the ring holds EXACTLY the trailing W
    values; its quantile equals the quantile of that window bitwise."""
    ring = jnp.asarray(init)
    hist = [init[:, i] for i in range(init.shape[1])]
    for row in pushes:
        ring = stats.ring_push(ring, jnp.asarray(row))
        hist.append(row)
        window = jnp.asarray(np.stack(hist[-init.shape[1]:], axis=1))
        np.testing.assert_array_equal(np.asarray(ring), np.asarray(window))
        np.testing.assert_array_equal(
            np.asarray(stats.ring_quantile(ring, q)),
            np.asarray(jnp.quantile(window, q, axis=1)))


@given(
    dev=hnp.arrays(np.float32, (9,),
                   elements=st.floats(-3.0, 3.0, width=32)),
)
@settings(**SET)
def test_dev_moments_init_matches_deviation_coef(dev):
    """``dev_init`` + ``dev_coef`` on a deviation series reproduce
    ``forecast.deviation_coef``'s through-origin estimate bitwise (same
    pairing, same sum order, same clips)."""
    d = jnp.asarray(dev)[None]
    got = stats.dev_coef(stats.dev_init(d))
    want = forecast.deviation_coef(d[0], jnp.zeros_like(d[0]))
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want))
