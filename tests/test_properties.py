"""Hypothesis property tests on system invariants (deliverable c).

This module (and its siblings test_forecast_properties.py) skips AS A
UNIT where the `hypothesis` package is not importable — a concrete
capability check, not a bare skip: the bare-metal image pins only the jax
toolchain, while the CI workflow installs hypothesis and runs these under
the fixed-seed "ci" profile registered in conftest.py, so the properties
are exercised on every push even when local environments lack the
package. Deterministic (non-hypothesis) coverage of the same subsystems
lives in test_risk.py / test_vcc_opt.py / test_ledger_invariants.py.
"""
import pytest

hypothesis = pytest.importorskip(
    "hypothesis",
    reason="capability check: the `hypothesis` package is not importable "
           "here; CI installs it (see .github/workflows/ci.yml) and runs "
           "these property tests under the fixed-seed 'ci' profile")
import hypothesis.extra.numpy as hnp  # noqa: E402
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings

from repro.core.vcc import project_conservation
from repro.kernels.linear_scan.ref import gla_chunked, gla_naive

SET = dict(max_examples=25, deadline=None,
           suppress_health_check=[hypothesis.HealthCheck.too_slow])


@given(
    z=hnp.arrays(np.float32, (3, 24),
                 elements=st.floats(-5, 5, width=32)),
    width=st.floats(0.2, 3.0),
)
@settings(**SET)
def test_projection_properties(z, width):
    """Projection onto {sum=0} ∩ [lo, ub]: feasibility + idempotence."""
    lo = np.full((3, 24), -1.0, np.float32)
    ub = np.full((3, 24), width, np.float32)
    p = project_conservation(jnp.asarray(z), jnp.asarray(lo),
                             jnp.asarray(ub), iters=60)
    assert np.all(np.asarray(p) >= lo - 1e-4)
    assert np.all(np.asarray(p) <= ub + 1e-4)
    assert np.abs(np.asarray(p.sum(1))).max() < 1e-3
    p2 = project_conservation(p, jnp.asarray(lo), jnp.asarray(ub), iters=60)
    assert np.abs(np.asarray(p2 - p)).max() < 1e-3


@given(
    seed=st.integers(0, 2**16),
    s=st.integers(5, 60),
    chunk=st.sampled_from([4, 8, 16, 32]),
    strict=st.booleans(),
)
@settings(**SET)
def test_gla_chunk_invariance(seed, s, chunk, strict):
    """Chunked GLA == sequential recurrence for any chunking."""
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 4)
    B, H, K, V = 1, 2, 4, 4
    q = jax.random.normal(ks[0], (B, s, H, K))
    k = jax.random.normal(ks[1], (B, s, H, K))
    v = jax.random.normal(ks[2], (B, s, H, V))
    ld = -jnp.abs(jax.random.normal(ks[3], (B, s, H, K))) * 2.0
    o1, h1 = gla_chunked(q, k, v, ld, strict=strict, chunk=chunk)
    o2, h2 = gla_naive(q, k, v, ld, strict=strict)
    assert float(jnp.abs(o1 - o2).max()) < 1e-4
    assert float(jnp.abs(h1 - h2).max()) < 1e-4


@given(seed=st.integers(0, 2**16))
@settings(**SET)
def test_carbon_intensity_positive_bounded(seed):
    from repro.core import carbon
    zone = carbon.default_zones(4)[seed % 4]
    ci = carbon.simulate_zone(jax.random.PRNGKey(seed), zone, 3)
    arr = np.asarray(ci)
    assert arr.shape == (3, 24)
    assert np.all(arr > 0)
    assert np.all(arr < 1.2)           # below pure-coal intensity


@given(
    seed=st.integers(0, 2**16),
    scale=st.floats(0.125, 10.0),
)
@settings(**SET)
def test_compression_error_feedback_unbiased(seed, scale):
    """Over repeated steps with constant gradient g, the error-feedback
    compressor's cumulative output converges to the true cumulative sum."""
    from repro.optim.compression import init_error_feedback, roundtrip
    rng = np.random.RandomState(seed)
    g = {"w": jnp.asarray(rng.randn(8, 8).astype(np.float32) * scale)}
    ef = init_error_feedback(g)
    total = jnp.zeros_like(g["w"])
    steps = 30
    for _ in range(steps):
        out, ef = roundtrip(g, ef)
        total = total + out["w"]
    rel = float(jnp.abs(total - steps * g["w"]).max()) \
        / (float(jnp.abs(g["w"]).max()) * steps + 1e-9)
    assert rel < 0.02


@given(
    u=hnp.arrays(np.float32, (16,), elements=st.floats(0.0625, 0.9375,
                                                       width=32)),
)
@settings(**SET)
def test_power_model_monotone_on_monotone_data(u):
    """Fit on a monotone curve -> predictions ordered like inputs."""
    from repro.core import power
    cpu = jnp.linspace(0.01, 1.0, 300)
    pw = 50.0 + 400.0 * cpu ** 1.1
    coef, breaks = power.fit_pd_model(cpu, pw)
    us = np.sort(np.unique(u))
    if len(us) < 2:
        return
    pred = np.asarray(power.pd_power(coef, breaks, jnp.asarray(us)))
    assert np.all(np.diff(pred) > -1.0)     # monotone up to fit noise
