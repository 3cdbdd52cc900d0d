"""Streaming prediction layer (PR 5 tentpole): equivalence + plumbing.

Contracts pinned here:

  * **Handoff bitwise** — ``stats.init_predictor`` warm-starts the
    streaming estimators from a burned-in rescan window with the SAME
    functions/op-orders, so the first streaming forecast equals the
    rescan forecast bitwise on the EWMA components (uif/tuf/tr, hence
    theta) and to float tolerance on the ratio-model terms (moment-form
    vs centered-form least squares). Both sides are evaluated eagerly:
    ulp-level equality across different jit compile units is out of
    contract repo-wide (same caveat as ``rollout_sequential``).
  * **Dual-run drift** — replaying 14 rescan days through the streaming
    predictor (same actuals) keeps every forecast within a documented
    tolerance: the two paths are different-memory estimators of the same
    quantities (the rescan re-partitions a sliding window daily, which
    has no O(1) update).
  * **State size** — the streaming carry replaces the seven (n, H[, 24])
    history windows with O(1)-in-H state: strictly smaller already at
    modest H, and at the year-scale H=364.
  * **Plumbing** — streaming rollouts run under jit+vmap end to end;
    ensembles (n_members > 1) are rejected with a clear error.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core import stages, stats
from repro.sim import (Scenario, SimConfig, build_batch, build_params,
                       make_day_step, make_init, rollout_batch)
from repro.sim.engine import _day_xs
from repro.sim.report import state_nbytes

N, M, Z, PDS, HIST = 4, 2, 2, 2, 28
CFG = SimConfig(n_clusters=N, n_campuses=M, n_zones=Z, pds_per_cluster=PDS,
                hist_days=HIST)
CFG_S = dataclasses.replace(CFG, streaming=True)
SCEN = Scenario("stream_probe", lambda_e=0.5)

# documented dual-run drift tolerance (max |streaming - rescan| / mean
# |rescan| per forecast component per day; measured ~0.19/0.15/0.04 for
# uif/tuf/tr over 14 days at this config)
DRIFT_TOL = {"uif": 0.35, "tuf": 0.35, "tr": 0.35, "alpha": 0.35,
             "uif_q": 0.45}


@pytest.fixture(scope="module")
def rescan_side():
    params = build_params(CFG, SCEN, seed=0, days=16)
    state = jax.jit(make_init(CFG))(params)
    return params, state


@pytest.fixture(scope="module")
def predictor(rescan_side):
    params, s = rescan_side
    return stats.init_predictor(
        s.hist_uif, s.hist_flex_daily, s.hist_res_daily, s.hist_usage,
        s.hist_res, s.hist_tr_pred, s.hist_uif_pred, s.day, params.gamma)


def test_handoff_forecast_bitwise_on_ewma_components(rescan_side,
                                                     predictor):
    params, s = rescan_side
    fc_r = stages.forecast_stage(
        s.hist_uif, s.hist_flex_daily, s.hist_res_daily, s.hist_usage,
        s.hist_res, s.hist_tr_pred, s.hist_uif_pred, s.day, params.gamma)
    fc_s = stats.streaming_forecast(predictor, s.day, params.gamma)
    for k in ("uif", "tuf", "tr", "theta"):
        np.testing.assert_array_equal(np.asarray(fc_r[k]),
                                      np.asarray(fc_s[k]), err_msg=k)
    for k in ("ratio_a", "ratio_b", "alpha", "uif_q"):
        np.testing.assert_allclose(np.asarray(fc_r[k]),
                                   np.asarray(fc_s[k]), rtol=1e-3,
                                   atol=1e-3, err_msg=k)


def test_streaming_init_power_fit_is_rescan_fit(rescan_side, predictor):
    """The usage ring IS the trailing 28-day window the rescan power fit
    slices: the fitted PD models agree bitwise."""
    params, s = rescan_side
    key = jax.random.fold_in(
        jax.random.fold_in(params.key, s.day), 1)
    m_r = stages.power_stage(s.hist_usage, params.lam,
                             params.truth["capacity"],
                             stages.pd_truth(params), key)
    m_s = stages.power_stage(predictor.usage_ring, params.lam,
                             params.truth["capacity"],
                             stages.pd_truth(params), key)
    np.testing.assert_array_equal(np.asarray(m_r.coef), np.asarray(m_s.coef))
    np.testing.assert_array_equal(np.asarray(m_r.breaks),
                                  np.asarray(m_s.breaks))


def test_dual_run_14_day_drift_within_tolerance(rescan_side, predictor):
    """>= 14-day dual run: step the rescan engine, replay its realized
    telemetry through the streaming predictor, compare every day's
    forecasts. Day 0 must be exact on the EWMA components; every day
    stays inside DRIFT_TOL."""
    params, s = rescan_side
    pred = predictor
    step = jax.jit(make_day_step(CFG))
    for d in range(14):
        fc_s = stats.streaming_forecast(pred, s.day, params.gamma)
        s2, out = step(params, s, _day_xs(params, d))
        for k, tol in DRIFT_TOL.items():
            a, b = np.asarray(out.fc[k]), np.asarray(fc_s[k])
            drift = np.max(np.abs(a - b)) / (np.mean(np.abs(a)) + 1e-9)
            assert drift < tol, (k, d, drift)
        if d == 0:
            np.testing.assert_allclose(np.asarray(out.fc["tr"]),
                                       np.asarray(fc_s["tr"]), rtol=1e-6)
        pred = stats.predictor_update(
            pred, fc_s, s.day, params.gamma, s2.hist_uif[:, -1],
            out.res.served, stages.hour_sum(out.res.reservations),
            out.res.usage_total, out.res.reservations)
        s = s2


def test_streaming_state_strictly_smaller(rescan_side, predictor):
    _, s = rescan_side
    pred_b = stats.predictor_nbytes(predictor)
    hist_b = stats.replaced_hist_nbytes(s)
    assert pred_b < hist_b, (pred_b, hist_b)
    # and the full carried streaming state beats the rescan state
    params = build_params(CFG_S, SCEN, seed=0, days=3)
    s_stream = jax.jit(make_init(CFG_S))(params)
    assert state_nbytes(s_stream) < state_nbytes(s)


def test_streaming_state_smaller_than_hist_windows_at_h364():
    """At a year of history (H=364) the PredictorState is strictly
    smaller than the seven hist_* windows it replaces, read from the
    burned-in states' shapes (no burn-in runs)."""
    params = build_params(CFG, SCEN, seed=0, days=1)
    rescan = jax.eval_shape(
        make_init(dataclasses.replace(CFG, hist_days=364)), params)
    stream = jax.eval_shape(
        make_init(dataclasses.replace(CFG_S, hist_days=364)), params)
    pred_b = stats.predictor_nbytes(stream.pred)
    hist_b = stats.replaced_hist_nbytes(rescan)
    assert pred_b < hist_b, (pred_b, hist_b)


def test_streaming_rollout_batch_runs_under_jit_vmap():
    days = 5
    scens = [SCEN, Scenario("stream_probe_hot", lambda_e=2.0)]
    batch = build_batch(CFG_S, scens, [0, 1], days)
    state, led, traj = rollout_batch(CFG_S, days)(batch)
    for leaf in jax.tree_util.tree_leaves(led):
        assert np.isfinite(np.asarray(leaf)).all()
    assert (np.asarray(led.carbon_kg).sum(axis=-1) > 0).all()
    assert (np.asarray(led.served).sum(axis=-1) > 0).all()
    assert np.asarray(traj["carbon_kg"]).shape == (4, days)
    # the carried streaming state kept its O(1) shape (incl. the 7-day
    # carbon window — the slice the forecaster actually reads)
    assert state.hist_uif.shape[2] == 0
    assert state.carbon_hist.shape[2] == stats.WEEK
    assert state.pred.usage_ring.shape[-2:] == (stats.USAGE_WINDOW, 24)


def test_streaming_rejects_forecast_ensembles():
    with pytest.raises(ValueError, match="streaming"):
        stages.make_day_step(stages.StageConfig(streaming=True,
                                                n_members=4))


def test_streaming_init_requires_a_week():
    with pytest.raises(ValueError, match="hist_days"):
        stages.make_init(4, 2, 2, hist_days=6, streaming=True)
