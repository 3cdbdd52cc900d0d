"""Hypothesis property tests for the intra-day MPC recourse layer.

Two contracts the closed loop leans on:

  * **Hour-grain == day-grain predictor advancement** — chaining 24
    ``stats.hour_update`` calls and closing the day with
    ``stats.hour_finalize`` is BITWISE the daily batch
    ``stats.predictor_update`` on the assembled arrays: the accumulator
    scatters columns in hour order and accumulates daily totals by the
    same ordered adds as ``admission.hour_sum``, so the streaming carry
    cannot drift depending on which grain observed the day.
  * **Suffix re-solve feasibility** — for ANY committed prefix and
    re-solve hour, ``vcc.solve_vcc_suffix`` keeps elapsed hours pinned,
    keeps the remaining hours inside the day-ahead box, and satisfies
    the tightened suffix conservation (sum of the whole day ~ 0) on
    every cluster it reports ``shaped``; clusters whose prefix cannot
    be conserved keep their plan exactly.

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

from repro.core import stats, vcc  # noqa: E402
from repro.core.admission import hour_sum  # noqa: E402

SET = dict(max_examples=15, deadline=None,
           suppress_health_check=[hypothesis.HealthCheck.too_slow])

N, HIST, GAMMA = 3, 14, 0.05


def _predictor(seed=0):
    """A PredictorState warm-started from a synthetic rescan window."""
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 7)
    u = jax.random.uniform
    hist_uif = 0.3 + 0.2 * u(ks[0], (N, HIST, 24))
    hist_flex = 2.0 + u(ks[1], (N, HIST))
    hist_res = 8.0 + u(ks[2], (N, HIST))
    hist_usage = 0.5 + 0.3 * u(ks[3], (N, HIST, 24))
    hist_resv = hist_usage * 1.3
    hist_tr_pred = hist_res * (1.0 + 0.05 * u(ks[4], (N, HIST)))
    hist_uif_pred = hist_uif * (1.0 + 0.05 * u(ks[5], (N, HIST, 24)))
    day = jnp.asarray(HIST, jnp.int32)
    return stats.init_predictor(hist_uif, hist_flex, hist_res, hist_usage,
                                hist_resv, hist_tr_pred, hist_uif_pred,
                                day, GAMMA), day


@given(
    u_if=hnp.arrays(np.float32, (N, 24),
                    elements=st.floats(0.0078125, 2.0, width=32)),
    use_flex=hnp.arrays(np.float32, (N, 24),
                        elements=st.floats(0.0, 1.0, width=32)),
    ratio=hnp.arrays(np.float32, (N, 24),
                     elements=st.floats(1.0, 2.0, width=32)),
)
@settings(**SET)
def test_hourly_chain_equals_daily_batch_update_bitwise(u_if, use_flex,
                                                        ratio):
    pred, day = _predictor()
    fc = stats.streaming_forecast(pred, day, GAMMA)
    u_if, use_flex, ratio = map(jnp.asarray, (u_if, use_flex, ratio))

    acc = stats.hour_accum_init(u_if)
    upd = jax.jit(stats.hour_update)
    for h in range(24):
        acc = upd(acc, jnp.asarray(h, jnp.int32), u_if[:, h],
                  use_flex[:, h], ratio[:, h])
    chained = stats.hour_finalize(pred, acc, fc, day, GAMMA)

    usage = u_if + use_flex
    res = usage * ratio
    batch = stats.predictor_update(pred, fc, day, GAMMA, u_if,
                                   hour_sum(use_flex), hour_sum(res),
                                   usage, res)
    for name, a, b in zip(chained._fields, chained, batch):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


@given(
    hour=st.integers(1, 23),
    jitter=hnp.arrays(np.float32, (6, 24),
                      elements=st.floats(-0.3125, 0.3125, width=32)),
    seed=st.integers(0, 3),
)
@settings(**SET)
def test_suffix_resolve_satisfies_tightened_conservation(hour, jitter,
                                                         seed):
    p = vcc.synthetic_problem(6, seed=seed, n_campuses=2)
    sol = vcc.solve_vcc(p, inner_iters=20, outer_iters=5,
                        use_pallas=False)
    lo, ub, _ = vcc.delta_bounds(p)
    # committed prefix: the plan perturbed inside the day-ahead box (a
    # realized prefix need not conserve — that is the point of recourse)
    committed = jnp.clip(sol.delta + jnp.asarray(jitter), lo, ub)
    sfx = vcc.solve_vcc_suffix(p, committed, sol.mu, hour,
                               use_pallas=False)
    d = np.asarray(sfx.delta)
    feas = np.asarray(sfx.shaped)
    # elapsed hours pinned bitwise, feasible or not
    np.testing.assert_array_equal(d[:, :hour],
                                  np.asarray(committed)[:, :hour])
    if feas.any():
        # suffix inside the day-ahead box ...
        assert (d[feas][:, hour:]
                >= np.asarray(lo)[feas][:, hour:] - 1e-5).all()
        assert (d[feas][:, hour:]
                <= np.asarray(ub)[feas][:, hour:] + 1e-5).all()
        # ... and the tightened conservation holds: suffix sum cancels
        # the committed prefix, i.e. the whole day sums to ~0
        np.testing.assert_allclose(np.asarray(hour_sum(sfx.delta))[feas],
                                   0.0, atol=1e-3)
    if (~feas).any():
        # infeasible clusters keep their plan exactly and fall back to
        # the unshaped curve
        np.testing.assert_array_equal(d[~feas],
                                      np.asarray(committed)[~feas])
        np.testing.assert_allclose(
            np.asarray(sfx.vcc)[~feas],
            np.broadcast_to(np.asarray(p.capacity)[~feas, None],
                            d[~feas].shape), rtol=1e-6)
