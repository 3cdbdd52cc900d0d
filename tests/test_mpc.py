"""Intra-day MPC recourse layer (core/mpc.py + the mpc=True day step).

Contract under test:

  * ``StageConfig.mpc`` / ``SimConfig.mpc`` default OFF — the closed
    loop is opt-in; the mpc=False rollout lowers to the same HLO text
    as the graph traced with the verbatim pre-MPC ``run_day``
    (``_legacy_run_day`` below, monkeypatched in).
  * ``vcc.solve_vcc_suffix`` pins elapsed hours at the committed
    deviations, keeps the suffix inside the day-ahead box and preserves
    whole-day conservation; infeasible clusters keep their plan.
  * ``mpc.mpc_day`` with the recourse gate closed reproduces the
    open-loop ``admission.run_day`` BITWISE (shared admission_tick /
    finalize_day — the controller cannot fork from open-loop semantics).
  * With the gate open and a forecast-busting intensity divergence the
    trigger actually fires and the enforced curve departs from the
    00:00 plan.
  * An mpc=True rollout runs under jit+vmap end to end (with streaming
    and telemetry stacked on) and emits sane recourse diagnostics.
  * On every forecast-bust scenario the closed loop improves carbon or
    within-24h flex service over the open loop of the same batch.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import admission, mpc, stages, vcc
from repro.core.admission import hour_sum
from repro.sim import (SimConfig, build_batch, build_params,
                       forecast_bust_library, make_init,
                       mpc_recourse_rows, rollout_batch)
from repro.sim.engine import _day_xs

f32 = jnp.float32


def _power_fn(u):
    return 100.0 + 300.0 * u


def _day_inputs(n, seed=0):
    """Synthetic realized day: (u_if, arrivals, ratio, intensity)."""
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 4)
    u_if = 0.4 + 0.05 * jax.random.normal(ks[0], (n, 24))
    arrivals = 0.15 + 0.1 * jax.random.uniform(ks[1], (n, 24))
    ratio = jnp.full((n, 24), 1.3)
    intensity = jnp.abs(0.3 + 0.2 * jax.random.normal(ks[3], (n, 24)))
    return u_if, arrivals, ratio, intensity


def test_mpc_defaults_off():
    assert stages.StageConfig().mpc is False
    assert SimConfig().mpc is False
    # and the engine threads the flag through
    assert SimConfig(mpc=True).stage_config().mpc is True


def test_suffix_solve_pins_elapsed_and_conserves():
    p = vcc.synthetic_problem(8, seed=3, n_campuses=2)
    sol = vcc.solve_vcc(p, use_pallas=False)
    hour = 9
    # committed prefix: the day-ahead plan's own deviations (conserving)
    sfx = vcc.solve_vcc_suffix(p, sol.delta, sol.mu, hour,
                               use_pallas=False)
    feas = np.asarray(sfx.shaped)
    assert feas.any()
    lo, ub, _ = vcc.delta_bounds(p)
    d = np.asarray(sfx.delta)
    # elapsed hours pinned bitwise at the committed deviations
    np.testing.assert_array_equal(d[feas][:, :hour],
                                  np.asarray(sol.delta)[feas][:, :hour])
    # suffix stays inside the day-ahead box, whole day conserves
    assert (d[feas][:, hour:] >= np.asarray(lo)[feas][:, hour:] - 1e-5) \
        .all()
    assert (d[feas][:, hour:] <= np.asarray(ub)[feas][:, hour:] + 1e-5) \
        .all()
    np.testing.assert_allclose(np.asarray(hour_sum(sfx.delta))[feas], 0.0,
                               atol=5e-4)


def test_suffix_infeasible_cluster_keeps_plan():
    """A realized prefix that spent more than the whole budget cannot be
    conserved by any suffix — the cluster must keep its current plan
    (lo == ub == committed) and fall back to the unshaped curve."""
    p = vcc.synthetic_problem(4, seed=5, n_campuses=2)
    sol = vcc.solve_vcc(p, use_pallas=False)
    hour = 12
    # force cluster 0's committed prefix to +24 per hour: the remaining
    # hours would need sum(delta) = -288, far below 24 * drop_limit
    bad = sol.delta.at[0, :hour].set(24.0)
    sfx = vcc.solve_vcc_suffix(p, bad, sol.mu, hour, use_pallas=False)
    assert not bool(sfx.shaped[0])
    np.testing.assert_array_equal(np.asarray(sfx.delta)[0],
                                  np.asarray(bad)[0])
    np.testing.assert_allclose(np.asarray(sfx.vcc)[0],
                               float(p.capacity[0]), rtol=1e-6)


def test_mpc_day_gate_closed_is_open_loop_bitwise():
    """gate=False every cluster -> the enforced curve is the unshaped
    10x-capacity curve every hour and no re-solve is ever accepted: the
    DayResult must equal ``admission.run_day`` on that same curve
    BITWISE."""
    n = 6
    p = vcc.synthetic_problem(n, seed=7, n_campuses=2)
    sol = vcc.solve_vcc(p, use_pallas=False)
    u_if, arrivals, ratio, intensity = _day_inputs(n)
    gate = jnp.zeros((n,), bool)
    queue0 = jnp.asarray(np.linspace(0.0, 0.4, n), f32)
    res, vcc_real, acc, diag = mpc.mpc_day(
        p, sol, p.tau, gate, p.capacity, u_if, arrivals, ratio, queue0,
        _power_fn, intensity, use_pallas=False)
    open_curve = jnp.broadcast_to(p.capacity[:, None] * 10.0, (n, 24))
    ref = admission.run_day(open_curve, u_if, arrivals, ratio, p.capacity,
                            queue0, _power_fn, intensity)
    for field in admission.DayResult.__dataclass_fields__:
        np.testing.assert_array_equal(
            np.asarray(getattr(res, field)),
            np.asarray(getattr(ref, field)), err_msg=field)
    np.testing.assert_array_equal(np.asarray(vcc_real),
                                  np.asarray(open_curve))
    # no recourse accepted, accumulator saw all 24 hours
    assert float(diag.recourse_frac.max()) == 0.0
    assert int(acc.hour) == 24
    np.testing.assert_array_equal(
        np.asarray(admission.hour_sum(acc.use_flex)),
        np.asarray(res.served))


def test_mpc_day_triggers_on_intensity_divergence():
    """A 2.5x realized-vs-forecast intensity spike trips the eta trigger:
    shaped clusters re-plan and the enforced curve departs from the
    00:00 plan's curve on later hours."""
    n = 6
    p = vcc.synthetic_problem(n, seed=11, n_campuses=2)
    sol = vcc.solve_vcc(p, use_pallas=False)
    u_if = p.u_if                       # actuals match forecast (no MAPE)
    arrivals = jnp.full((n, 24), 0.1)
    ratio = p.ratio
    intensity = p.eta * 2.5             # forecast-busting spike
    gate = sol.shaped
    assert bool(gate.any())
    queue0 = jnp.zeros((n,), f32)
    res, vcc_real, acc, diag = mpc.mpc_day(
        p, sol, p.tau, gate, p.capacity, u_if, arrivals, ratio, queue0,
        _power_fn, intensity, use_pallas=False)
    g = np.asarray(gate)
    assert float(np.asarray(diag.recourse_frac)[g].max()) > 0.0
    assert float(np.asarray(diag.recourse_depth)[g].max()) > 0.0
    plan_curve = np.asarray(mpc.gated_curve(p, sol.delta, p.tau, gate,
                                            p.capacity))
    assert np.abs(np.asarray(vcc_real)[g] - plan_curve[g]).max() > 1e-4
    # hour 0 is always enforced from the 00:00 plan (recourse starts
    # after the first observation)
    np.testing.assert_allclose(np.asarray(vcc_real)[:, 0],
                               plan_curve[:, 0], rtol=1e-6)


def test_mpc_rollout_batch_runs_with_streaming_and_telemetry():
    cfg = SimConfig(n_clusters=4, n_campuses=2, n_zones=2,
                    pds_per_cluster=2, hist_days=14, streaming=True,
                    telemetry=True, mpc=True)
    days = 2
    scens = forecast_bust_library(days=days)[:1]
    params = build_batch(cfg, scens, seeds=[0], days=days)
    from repro.sim import make_init
    queue_init = jax.vmap(jax.jit(make_init(cfg)))(params).queue
    state, led, traj = rollout_batch(cfg, days)(params)
    assert np.isfinite(np.asarray(led.carbon_kg)).all()
    t = traj["telemetry"]
    frac = np.asarray(t.mpc_recourse_frac)
    depth = np.asarray(t.mpc_recourse_depth)
    assert frac.shape == (1, days, cfg.n_clusters)
    assert ((frac >= 0.0) & (frac <= 1.0)).all()
    assert (depth >= 0.0).all()
    # queue conservation survives the closed loop: burned-in backlog +
    # arrivals = served + final backlog
    lhs = float(queue_init.sum() + np.asarray(led.arrived).sum())
    rhs = float(np.asarray(led.served).sum()
                + np.asarray(state.queue).sum())
    np.testing.assert_allclose(lhs, rhs, rtol=1e-4)


def test_mpc_off_rollout_has_zero_recourse_telemetry():
    """telemetry=True, mpc=False: the record carries the recourse gauges
    as all-zeros placeholders (TRACE_FIELDS is flag-invariant)."""
    cfg = SimConfig(n_clusters=4, n_campuses=2, n_zones=2,
                    pds_per_cluster=2, hist_days=14, telemetry=True)
    days = 2
    scens = forecast_bust_library(days=days)[:1]
    params = build_batch(cfg, scens, seeds=[0], days=days)
    _, _, traj = rollout_batch(cfg, days)(params)
    assert float(np.abs(np.asarray(
        traj["telemetry"].mpc_recourse_frac)).max()) == 0.0


# ------------------------------------------------ the accepted re-plans

LEGACY_TRAJ = {"carbon_kg", "cf_carbon_kg", "kwh", "peak_kw", "queue"}


def _bust_batch(cfg, days, n_scen=2):
    return build_batch(cfg, forecast_bust_library(days=days)[:n_scen],
                       seeds=[0], days=days)


def test_recourse_hours_counts_the_accepted_replans():
    """mpc=True, rescan: ``traj["recourse_hours"]`` (days,) is the
    cluster-hours whose re-solved suffix was accepted, the telemetry
    record's per-cluster recourse fraction times 24 summed over
    clusters, with telemetry off as with it on."""
    kw = dict(n_clusters=4, n_campuses=2, n_zones=2, pds_per_cluster=2,
              hist_days=14, mpc=True)
    days = 3
    batch = _bust_batch(SimConfig(**kw), days)
    _, _, traj = rollout_batch(SimConfig(**kw), days)(batch)
    _, _, traj_on = rollout_batch(SimConfig(**kw, telemetry=True),
                                  days)(batch)
    count = np.asarray(traj["recourse_hours"])
    frac = np.asarray(traj_on["telemetry"].mpc_recourse_frac)
    assert count.shape == (2, days)
    want = (frac * 24).sum(axis=-1)
    np.testing.assert_array_equal(
        np.asarray(traj_on["recourse_hours"]), want)
    np.testing.assert_array_equal(count, want)
    assert count.max() > 0
    assert set(traj) == LEGACY_TRAJ | {"recourse_hours"}


def test_recourse_hours_zero_with_every_gate_closed():
    """Every cluster's shaping paused: no re-plan is ever accepted."""
    cfg = SimConfig(n_clusters=4, n_campuses=2, n_zones=2,
                    pds_per_cluster=2, hist_days=14, mpc=True)
    days = 2
    p = build_params(cfg, forecast_bust_library(days=days)[0], 0, days)
    s = jax.jit(make_init(cfg))(p)
    s = s._replace(shaping_allowed=jnp.zeros_like(s.shaping_allowed))
    step = jax.jit(stages.make_day_step(cfg.stage_config()))
    _, out = step(p, s, _day_xs(p, 0))
    assert float(out.recourse_hours) == 0.0


def _legacy_run_day(vcc, u_if, arrivals, ratio, capacity, queue0, power_fn,
                    intensity, allowance_frac: float = 0.25):
    """Verbatim pre-MPC ``admission.run_day`` (inline tick + hard-coded
    0.25 late-day allowance; ``allowance_frac`` accepted for call
    compatibility, unused — the default-config trace passes 0.25). The
    collapse probe traces the day step against THIS to certify that
    ``mpc=False`` still compiles to the byte-identical open-loop HLO."""
    def tick(queue, inp):
        vcc_h, uif_h, arr_h, r_h = inp
        flex_room_res = jnp.clip(vcc_h - uif_h * r_h, 0.0, None)
        flex_room = flex_room_res / jnp.clip(r_h, 1.0, None)
        flex_room = jnp.minimum(flex_room,
                                jnp.clip(capacity - uif_h, 0.0, None))
        demand = queue + arr_h
        use_flex = jnp.minimum(demand, flex_room)
        queue = demand - use_flex
        return queue, (use_flex, queue)

    xs = (vcc.T, u_if.T, arrivals.T, ratio.T)
    queue_end, (use_flex, queue_traj) = jax.lax.scan(tick, queue0, xs)
    use_flex = use_flex.T                       # (n, 24)
    usage_total = u_if + use_flex
    reservations = usage_total * ratio
    power = jax.vmap(power_fn, in_axes=1, out_axes=1)(usage_total)
    carbon = power * intensity
    arrived = hour_sum(arrivals)
    served = hour_sum(use_flex)
    allowance = 0.25 * arrived
    unmet = jnp.clip(queue_end - queue0 - allowance, 0.0, None)
    return admission.DayResult(
        usage_flex=use_flex, usage_total=usage_total,
        reservations=reservations, power=power, carbon=carbon,
        served=served, arrived=arrived, queue_end=queue_end, unmet=unmet)


def test_mpc_off_traj_and_hlo_unchanged(monkeypatch):
    """mpc=False: the traj keys are the legacy ones, and the whole
    rollout lowers to the text of the graph traced with the verbatim
    pre-MPC admission (``_legacy_run_day``)."""
    cfg = SimConfig(n_clusters=4, n_campuses=2, n_zones=2,
                    pds_per_cluster=2, hist_days=14)
    days = 2
    batch = _bust_batch(cfg, days, n_scen=1)
    _, _, traj = rollout_batch(cfg, days)(batch)
    assert set(traj) == LEGACY_TRAJ
    now = jax.jit(rollout_batch(cfg, days)).lower(batch).as_text()
    monkeypatch.setattr(admission, "run_day", _legacy_run_day)
    legacy = jax.jit(rollout_batch(cfg, days)).lower(batch).as_text()
    assert now == legacy


# ------------------------------------------- closed loop against open loop

BUST_DAYS = 4
BUST_SCENARIOS = forecast_bust_library(BUST_DAYS)


@pytest.fixture(scope="module")
def recourse_rows():
    """One forecast-bust batch (3 scenarios x seeds 0, 1, 4 days) rolled
    out twice, mpc=True against mpc=False."""
    base = dict(n_clusters=4, n_campuses=2, n_zones=2, pds_per_cluster=2,
                hist_days=14)
    cfg_open = SimConfig(**base)
    seeds = [0, 1]
    batch = build_batch(cfg_open, BUST_SCENARIOS, seeds, BUST_DAYS)
    _, led_open, _ = rollout_batch(cfg_open, BUST_DAYS)(batch)
    _, led_mpc, _ = rollout_batch(SimConfig(**base, mpc=True),
                                  BUST_DAYS)(batch)
    rows = mpc_recourse_rows(led_mpc, led_open,
                             [s.name for s in BUST_SCENARIOS], len(seeds))
    return {r["scenario"]: r for r in rows}


@pytest.mark.parametrize("scenario", [s.name for s in BUST_SCENARIOS])
def test_mpc_recourse_beats_open_loop(recourse_rows, scenario):
    """Hourly recourse improves carbon (%) or within-24h flex service
    (pp) over committing to the 00:00 plan, on every bust row."""
    r = recourse_rows[scenario]
    best = max(r["carbon_vs_open_pct"], r["flex24h_vs_open_pp"])
    assert best >= 0.0, (r["carbon_vs_open_pct"], r["flex24h_vs_open_pp"])
