"""End-to-end behaviour of the paper's system: the CICS day cycle shifts
flexible load toward green hours while preserving daily totals and honoring
the SLO feedback loop (paper §IV)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.sim import (Scenario, SimConfig, build_params, make_day_step,
                       make_init)
from repro.sim.engine import _day_xs

N_DAYS = 4


@pytest.fixture(scope="module")
def fleet_run():
    """Four engine days of a neutral 8-cluster fleet; each record holds
    the day's StepOut."""
    cfg = SimConfig(n_clusters=8, n_campuses=2, n_zones=2,
                    pds_per_cluster=4, hist_days=91)
    sc = Scenario("system", lambda_e=0.5, lambda_p=0.05, gamma=0.05)
    params = build_params(cfg, sc, seed=1, days=N_DAYS)
    st = jax.jit(make_init(cfg))(params)
    step = jax.jit(make_day_step(cfg))
    recs = []
    for d in range(N_DAYS):
        st, out = step(params, st, _day_xs(params, d))
        recs.append(out)
    return cfg, params.truth["capacity"], st, recs


def test_delta_anticorrelates_with_carbon(fleet_run):
    cfg, capacity, st, recs = fleet_run
    corrs = []
    for rec in recs:
        sol, eta = rec.sol, rec.eta_act
        for c in range(cfg.n_clusters):
            if bool(sol.shaped[c]) and float(jnp.std(sol.delta[c])) > 1e-6:
                corrs.append(np.corrcoef(np.asarray(sol.delta[c]),
                                         np.asarray(eta[c]))[0, 1])
    assert corrs, "no shaped clusters"
    assert np.mean(corrs) < -0.25, np.mean(corrs)


def test_daily_conservation_of_flexible_budget(fleet_run):
    cfg, capacity, st, recs = fleet_run
    for rec in recs:
        sol = rec.sol
        assert float(jnp.abs(sol.delta.sum(axis=1)).max()) < 1e-3


def test_vcc_within_machine_capacity(fleet_run):
    cfg, capacity, st, recs = fleet_run
    for rec in recs:
        assert bool(jnp.all(rec.vcc_curve <= capacity[:, None] * 10.0
                            + 1e-3))
        sol = rec.sol
        shaped = np.asarray(sol.shaped)
        vccs = np.asarray(sol.vcc)[shaped]
        caps = np.asarray(capacity)[shaped]
        assert np.all(vccs <= caps[:, None] + 1e-3)


def test_inflexible_usage_untouched(fleet_run):
    """Shaping never reduces inflexible usage (it is always admitted)."""
    cfg, capacity, st, recs = fleet_run
    for rec in recs:
        res = rec.res
        assert bool(jnp.all(res.usage_total >= res.usage_flex - 1e-5))


def test_slo_violation_rate_controlled(fleet_run):
    cfg, capacity, st, recs = fleet_run
    rate = float((st.violation_days
                  / jnp.clip(st.observed_days, 1, None)).mean())
    assert rate <= 0.35            # early-operation bound


def test_carbon_savings_vs_unshaped(fleet_run):
    """Shaped days should emit no more carbon during the dirtiest hours
    than the same load unshaped (weight power by intensity rank)."""
    cfg, capacity, st, recs = fleet_run
    dirty_shaped, dirty_flat = [], []
    for rec in recs:
        res, eta = rec.res, rec.eta_act
        shaped = np.asarray(rec.sol.shaped)
        if not shaped.any():
            continue
        p = np.asarray(res.power)[shaped]
        e = np.asarray(eta)[shaped]
        top = e >= np.quantile(e, 0.75, axis=1, keepdims=True)
        dirty_shaped.append((p * top).sum() / p.sum())
        dirty_flat.append(top.mean())
    assert dirty_shaped, "no shaped clusters"
    # fraction of power spent in dirty hours < fraction of hours
    assert np.mean(dirty_shaped) <= np.mean(dirty_flat) + 0.01
