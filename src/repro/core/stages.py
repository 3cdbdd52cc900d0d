"""The staged CICS day cycle — the ONE implementation of the paper's loop.

Every simulated day is the same pipeline (paper Fig. 4/5):

  carbon_stage    — scenario-perturbed grid simulation + day-ahead
                    intensity forecast per zone
  power_stage     — refit PD piecewise-linear power models on history
  forecast_stage  — day-ahead U_IF(h), T_UF(d), T_R(d), R(h), trailing
                    -error quantiles -> Theta, alpha (eq. 3)
  optimize_stage  — fleetwide risk-aware VCCs (eq. 4) + optional spatial
                    pre-shift; PGD inner loop via kernels.vcc_pgd; with
                    StageConfig.n_members > 1 the objective is a CVaR
                    over K forecast-ensemble members (core.risk) at
                    SimParams.risk_beta
  (SLO gate)      — paused clusters get VCC = machine capacity
  observe_stage   — Borg-like admission on ACTUAL load, shaped + unshaped
                    counterfactual in the same trace
  slo_stage       — violation detection + shaping-pause feedback

Each stage is a pure, jit/vmap-safe function from array pytrees to array
pytrees, with an ``optimization_barrier`` materialization pin at its
boundary: XLA must not re-fuse (and re-round) a stage's output when its
consumers change, or the sim engine's bitwise batched==sequential parity
contract breaks. ``make_day_step`` composes the stages into one pure day;
``burnin_step``/``make_init`` build a burned-in state under ``lax.scan``.

``sim.engine`` scans/vmaps ``make_day_step`` across days and a
(scenario x seed) batch; ``jitted_day_step`` is the same day compiled
alone, for drivers that step it from a Python loop. There is no second
copy of the day cycle.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import (admission, carbon, forecast, mpc, power, risk,
                        slo, spatial, stats, vcc)

f32 = jnp.float32

# ordered sum over the last axis: the batch-invariant reduction primitive
# (single definition — the parity contract depends on these staying one op)
hour_sum = admission.hour_sum


# ------------------------------------------------------------- fleet synth

def cluster_truth(key, n: int):
    """Latent per-cluster load-generating processes."""
    ks = jax.random.split(key, 10)
    capacity = jnp.exp(jax.random.normal(ks[0], (n,)) * 0.4 + 2.3)  # ~10 CPU
    flex_share = jnp.clip(0.08 + 0.5 * jax.random.uniform(ks[1], (n,)),
                          0.05, 0.6)
    base_if = capacity * (0.35 + 0.2 * jax.random.uniform(ks[2], (n,)))
    diurnal_amp = 0.15 + 0.2 * jax.random.uniform(ks[3], (n,))
    peak_hour = 8.0 + 10.0 * jax.random.uniform(ks[4], (n,))
    weekly_amp = 0.05 + 0.1 * jax.random.uniform(ks[5], (n,))
    noise = 0.02 + 0.06 * jax.random.uniform(ks[6], (n,))
    arr_level = capacity * flex_share * (0.5 + 0.4 *
                                         jax.random.uniform(ks[7], (n,)))
    ratio_a = 1.15 + 0.3 * jax.random.uniform(ks[8], (n,))
    ratio_b = -0.05 - 0.08 * jax.random.uniform(ks[9], (n,))
    return {"capacity": capacity, "flex_share": flex_share,
            "base_if": base_if, "diurnal_amp": diurnal_amp,
            "peak_hour": peak_hour, "weekly_amp": weekly_amp,
            "noise": noise, "arr_level": arr_level,
            "ratio_a": ratio_a, "ratio_b": ratio_b}


def sample_inflexible(key, truth, day):
    """Actual inflexible hourly usage for one day. (n, 24)."""
    hours = jnp.arange(24, dtype=f32)
    d = jnp.minimum(jnp.abs(hours[None] - truth["peak_hour"][:, None]),
                    24 - jnp.abs(hours[None] - truth["peak_hour"][:, None]))
    diurnal = 1.0 + truth["diurnal_amp"][:, None] * jnp.exp(
        -0.5 * (d / 4.0) ** 2)
    weekly = 1.0 + truth["weekly_amp"][:, None] * jnp.cos(
        2 * jnp.pi * (day % 7) / 7.0)
    eps = 1.0 + truth["noise"][:, None] * jax.random.normal(
        key, (truth["base_if"].shape[0], 24))
    return truth["base_if"][:, None] * diurnal * weekly * eps


def sample_arrivals(key, truth, day):
    """Flexible CPU-hour arrivals per hour. (n, 24)."""
    hours = jnp.arange(24, dtype=f32)
    prof = 0.6 + 0.8 * jnp.exp(-0.5 * ((hours[None] - 11.0) / 5.0) ** 2)
    weekly = 1.0 + 0.5 * truth["weekly_amp"][:, None] * jnp.cos(
        2 * jnp.pi * (day % 7) / 7.0)
    eps = 1.0 + 2.5 * truth["noise"][:, None] * jax.random.normal(
        key, (truth["arr_level"].shape[0], 24))
    return jnp.clip(truth["arr_level"][:, None] * prof * weekly * eps / 24.0
                    * 24.0 / prof.sum() * 24.0, 0.0, None)


def true_ratio(truth, usage):
    return jnp.clip(truth["ratio_a"][:, None]
                    + truth["ratio_b"][:, None]
                    * jnp.log(jnp.clip(usage, 1e-6, None)), 1.05, 3.0)


def synth_params(seed: int, n_clusters: int, pds_per_cluster: int,
                 n_zones: int) -> Dict[str, object]:
    """Synthesize the array-only fleet parameter leaves that
    ``sim.scenarios.build_params`` composes scenarios onto: latent truth,
    PD power-curve truth, PD usage fractions, stacked zone params, and the
    rollout PRNG key. Pure: identical inputs -> identical arrays."""
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 8)
    n, npds = n_clusters, pds_per_cluster
    truth = cluster_truth(ks[0], n)
    npd = n * npds
    return {
        "key": jax.random.fold_in(key, 17),
        "truth": truth,
        "pd_idle": 60.0 + 40.0 * jax.random.uniform(ks[1], (npd,)),
        "pd_slope": 250.0 + 150.0 * jax.random.uniform(ks[2], (npd,)),
        "pd_curve": 0.8 + 0.5 * jax.random.uniform(ks[3], (npd,)),
        "lam": jax.nn.softmax(jax.random.normal(ks[4], (n, npds)), axis=1),
        "zone": carbon.stack_zone_params(carbon.default_zones(n_zones)),
    }


# ------------------------------------------------------------ state pytrees

class SimParams(NamedTuple):
    """Per-rollout day-cycle parameters. All leaves are arrays; stacking a
    list of SimParams along axis 0 gives the (scenario x seed) batch."""
    key: jnp.ndarray                  # PRNG key data, (2,) uint32
    truth: Dict[str, jnp.ndarray]     # latent cluster processes, (n,)
    pd_idle: jnp.ndarray              # (n*pds,)
    pd_slope: jnp.ndarray             # (n*pds,)
    pd_curve: jnp.ndarray             # (n*pds,)
    lam: jnp.ndarray                  # (n, pds) PD usage fractions
    zone: Dict[str, jnp.ndarray]      # grid-mix params, (z,)
    lambda_e: jnp.ndarray             # () carbon price
    lambda_p: jnp.ndarray             # () peak-power price
    gamma: jnp.ndarray                # () power-capping violation prob
    mobility: jnp.ndarray             # () spatial-shift mobility (0 = off)
    risk_beta: jnp.ndarray            # () CVaR tail fraction (1 = neutral)
    green_scale: jnp.ndarray          # (days, z) solar+wind multiplier
    coal_scale: jnp.ndarray           # (days, z) coal-share multiplier
    cap_scale: jnp.ndarray            # (days, n) capacity multiplier
    arrival_scale: jnp.ndarray        # (days, n) flexible-demand multiplier
    campus_scale: jnp.ndarray         # (days, m) campus power-limit scale
    # Intraday forecast-busting channels (sim.scenarios Intraday*
    # perturbations): hourly multipliers applied to the ACTUALS after the
    # day-ahead forecasts are drawn, so the planner is blind to them
    # until the hours realize. The None default flattens to an empty
    # pytree subtree — absent channels leave every compiled graph
    # byte-identical (same mechanism as StepOut.telemetry).
    arrival_hour_scale: Optional[jnp.ndarray] = None   # (days, 24)
    carbon_hour_scale: Optional[jnp.ndarray] = None    # (days, 24)


class SimState(NamedTuple):
    """Array-only day-cycle state (the scan carry).

    Rescan mode carries the seven rolling ``hist_*`` windows (oldest
    first) and ``pred=None``; streaming mode
    (``StageConfig.streaming=True``) carries the O(1)
    ``stats.PredictorState`` in ``pred``, the ``hist_*`` leaves become
    zero-length stubs (shape (n, 0[, 24]) — dropped from memory, never
    read), and ``carbon_hist`` is truncated to the trailing 7 days the
    carbon forecaster actually consumes."""
    day: jnp.ndarray                  # () int32
    campus: jnp.ndarray               # (n,) int32
    zmap: jnp.ndarray                 # (n,) int32 zone of cluster
    campus_limit: jnp.ndarray         # (m,) kW
    u_pow_cap: jnp.ndarray            # (n,)
    hist_uif: jnp.ndarray             # (n, H, 24)
    hist_flex_daily: jnp.ndarray      # (n, H)
    hist_res_daily: jnp.ndarray       # (n, H)
    hist_usage: jnp.ndarray           # (n, H, 24)
    hist_res: jnp.ndarray             # (n, H, 24)
    hist_tr_pred: jnp.ndarray         # (n, H)
    hist_uif_pred: jnp.ndarray        # (n, H, 24)
    carbon_hist: jnp.ndarray          # (z, H, 24)
    queue: jnp.ndarray                # (n,) shaped-run backlog
    cf_queue: jnp.ndarray             # (n,) counterfactual backlog
    crowded_streak: jnp.ndarray       # (n,) int32
    pause_left: jnp.ndarray           # (n,) int32
    violation_days: jnp.ndarray       # (n,) int32
    observed_days: jnp.ndarray        # (n,) int32
    shaping_allowed: jnp.ndarray      # (n,) bool
    pred: Optional[stats.PredictorState] = None   # streaming carry


class StepOut(NamedTuple):
    """Everything one day produces beyond the carried state. Consumers
    keep what they need (the engine reduces to DayMetrics inside its scan
    body) — unused leaves are dead-code-eliminated by XLA."""
    res: admission.DayResult          # shaped admission result
    cf: admission.DayResult           # unshaped counterfactual result
    sol: vcc.VCCSolution
    vcc_curve: jnp.ndarray            # (n, 24) post-SLO-gate VCC (with
    #                                   StageConfig.mpc the hour-by-hour
    #                                   ENFORCED curve, not the 00:00 plan)
    fc: Dict[str, jnp.ndarray]        # forecast dict
    prob: vcc.VCCProblem              # problem actually optimized
    eta_act: jnp.ndarray              # (n, 24) actual intensity per cluster
    # DayTelemetry record (sim.telemetry) when StageConfig.telemetry; the
    # default None flattens to an EMPTY pytree subtree, so the legacy
    # (telemetry=False) compiled graph stays byte-identical
    telemetry: Optional[object] = None
    # () cluster-hours whose re-solved suffix the MPC loop accepted this
    # day, when StageConfig.mpc (whatever the telemetry flag); None, an
    # empty subtree, in the open loop
    recourse_hours: Optional[jnp.ndarray] = None


@dataclass(frozen=True)
class StageConfig:
    """Static knobs of the staged day cycle (hashable: keys the jit
    cache). Shapes live in the state/params arrays, not here."""
    slo_margin: float = 1.0
    slo_pause_days: int = 7
    joint_spatial: bool = False   # True = joint spatio-temporal optimize
    #                               (spatial.solve_joint: delta and the
    #                               budget shift s descended together);
    #                               False = the paper-mode graph with the
    #                               greedy spatial pre-shift (mobility=0
    #                               makes the shift exactly zero)
    n_members: int = 1            # forecast-ensemble size K (1 = eq. 4
    #                               point-forecast path, graph unchanged;
    #                               K > 1 = CVaR over sampled realizations
    #                               at SimParams.risk_beta — core.risk)
    streaming: bool = False       # True = O(1) streaming prediction layer
    #                               (stats.PredictorState carry instead of
    #                               the (n, H, 24) hist_* rescans); False
    #                               keeps the legacy rescan graph
    #                               byte-identical (golden trace)
    use_pallas: Optional[bool] = None   # VCC PGD kernel dispatch (None=auto)
    interpret: bool = False             # Pallas interpreter (CPU tests)
    telemetry: bool = False       # True = thread a sim.telemetry
    #                               DayTelemetry record (solver
    #                               convergence, forecast calibration,
    #                               SLO/headroom gauges) through the day
    #                               step; False keeps the compiled graph
    #                               byte-identical to the legacy day
    #                               (collapse contract, HLO-tested)
    mpc: bool = False             # True = intra-day MPC recourse: each
    #                               hour observes the realized load /
    #                               intensity and warm-starts a short
    #                               suffix re-solve of the remaining
    #                               hours' VCC (core.mpc); False keeps
    #                               the open-loop day-ahead graph
    #                               byte-identical (collapse contract,
    #                               HLO-tested like `telemetry`)
    slo_allowance: float = 0.25   # late-day arrival fraction NOT counted
    #                               as unmet (admission.finalize_day);
    #                               the default reproduces the historical
    #                               hard-coded 0.25


def pd_truth(params: SimParams) -> power.PDTruth:
    return power.PDTruth(idle_kw=params.pd_idle, slope_kw=params.pd_slope,
                         curve=params.pd_curve)


def roll(hist, new):
    """Drop oldest day, append new. hist (n, H[, 24]); new (n[, 24])."""
    return jnp.concatenate([hist[:, 1:], new[:, None]], axis=1)


# ----------------------------------------------------------------- stages

def carbon_stage(zone: Dict[str, jnp.ndarray], carbon_hist, key,
                 green_scale, coal_scale):
    """Draw one day of actual zone intensity + its day-ahead forecast.

    zone: dict of (z,) grid-mix params; carbon_hist: (z, H, 24);
    green/coal_scale: (z,) scenario multipliers. Returns barrier-pinned
    (act_z (z, 24), fc_z (z, 24))."""
    z = carbon_hist.shape[0]
    zp = dict(zone)
    zp["solar_cap"] = zp["solar_cap"] * green_scale
    zp["wind_cap"] = zp["wind_cap"] * green_scale
    zp["coal_share"] = zp["coal_share"] * coal_scale
    keys = jax.random.split(key, 2 * z)
    act_z = carbon.simulate_zones_from(keys[:z], zp, 1)[:, 0]     # (z, 24)
    fc_z = jax.vmap(carbon.forecast_day_ahead)(
        keys[z:], carbon_hist, act_z, zp["weather_vol"] * 0.15)
    return jax.lax.optimization_barrier((act_z, fc_z))


class PowerModel(NamedTuple):
    """Fitted cluster power model as arrays (the power_stage output)."""
    coef: jnp.ndarray       # (n*pds, K+2) piecewise-linear coefficients
    breaks: jnp.ndarray     # (n*pds, K) hinge locations
    lam: jnp.ndarray        # (n, pds) PD usage fractions
    cap_pd: jnp.ndarray     # (n*pds,) cluster capacity per PD row


def power_stage(hist_usage, lam, capacity, pdt: power.PDTruth, key
                ) -> PowerModel:
    """Fit PD piecewise power models on recent cluster usage history.

    hist_usage: (n, hist, 24); lam: (n, pds); capacity: (n,);
    pdt: power.PDTruth with (n*pds,) fields. jit/vmap-safe.
    """
    n, npd = lam.shape
    u_cl = hist_usage[:, -28:].reshape(n, -1)                # (n, t)
    cap = jnp.clip(capacity, 1e-6, None)[:, None, None]

    def to_pd(v):       # (n, m) cluster usage -> (n, pds, m) PD usage / cap
        return (lam[..., None] * v[:, None, :]) / cap

    u_norm = to_pd(u_cl).reshape(n * npd, -1)
    p_pd = power.simulate_pd_power(key, pdt, u_norm)
    # lam >= 0 and cap > 0 make to_pd non-decreasing (in float32 too), so
    # a PD's order statistics are its cluster's, mapped: one sort per
    # cluster, not per PD, and the same bits as sorting the PD rows
    s = jnp.sort(u_cl, axis=-1, stable=False)
    ends = to_pd(jnp.stack([s[:, 0], s[:, -1]], -1)).reshape(n * npd, 2)
    breaks = power.quantiles_sorted(s, power.BREAK_QS, to_pd).reshape(
        n * npd, -1)
    coef = jax.vmap(power.fit_pd_window)(u_norm, p_pd, ends[:, 0],
                                         ends[:, 1], breaks)
    # materialization point: keeps the fitted model's numerics independent
    # of how downstream consumers fuse (bitwise batched/sequential parity)
    coef, breaks = jax.lax.optimization_barrier((coef, breaks))
    cap_pd = capacity[:, None].repeat(npd, 1).reshape(-1)
    return PowerModel(coef=coef, breaks=breaks, lam=lam, cap_pd=cap_pd)


def model_power(m: PowerModel, u_cluster):
    """Cluster power at cluster CPU usage. (n,) -> (n,) kW."""
    n, npd = m.lam.shape
    u_pd_now = (m.lam * u_cluster[:, None]).reshape(-1)
    u_n = u_pd_now / jnp.clip(m.cap_pd, 1e-6, None)
    p = jax.vmap(power.pd_power)(m.coef, m.breaks, u_n[:, None])[:, 0]
    return p.reshape(n, npd).sum(axis=1)


def model_slope(m: PowerModel, u_cluster):
    """Local cluster slope d kW / d cluster-CPU. (n,) -> (n,)."""
    n, npd = m.lam.shape
    u_pd_now = (m.lam * u_cluster[:, None]).reshape(-1)
    u_n = u_pd_now / jnp.clip(m.cap_pd, 1e-6, None)
    s = jax.vmap(power.pd_slope)(m.coef, m.breaks, u_n[:, None])[:, 0]
    s = s / jnp.clip(m.cap_pd, 1e-6, None)
    return (s.reshape(n, npd) * m.lam).sum(axis=1)


def forecast_stage(hist_uif, hist_flex_daily, hist_res_daily, hist_usage,
                   hist_res, hist_tr_pred, hist_uif_pred, day, gamma):
    """Next-day forecasting pipeline from rolling history arrays.

    All (n, hist[, 24]); day/gamma may be traced. Returns the
    barrier-pinned forecast dict consumed by optimize_stage."""
    n = hist_uif.shape[0]
    dow = jnp.asarray(day % 7)
    uif_pred = jax.vmap(lambda h: forecast.forecast_inflexible(h, dow))(
        hist_uif)
    tuf_pred = jax.vmap(lambda d: forecast.forecast_daily_total(d, dow))(
        hist_flex_daily)
    tr_pred = jax.vmap(lambda d: forecast.forecast_daily_total(d, dow))(
        hist_res_daily)
    ra, rb = jax.vmap(forecast.fit_ratio_model)(
        hist_usage[:, -28:].reshape(n, -1),
        hist_res[:, -28:].reshape(n, -1))
    eps97 = jax.vmap(lambda p, a: forecast.relative_error_quantile(
        p[-90:], a[-90:], 0.97))(hist_tr_pred, hist_res_daily)
    theta = forecast.theta_requirement(tr_pred, eps97)
    alpha = jax.vmap(forecast.alpha_inflation)(theta, uif_pred, tuf_pred,
                                               ra, rb)
    # (1-gamma) hourly inflexible quantile from trailing prediction errors
    epsq = jax.vmap(lambda p, a: forecast.relative_error_quantile(
        p[-28:].reshape(-1), a[-28:].reshape(-1), 1 - gamma))(
        hist_uif_pred, hist_uif)
    uif_q = uif_pred * (1.0 + jnp.clip(epsq, 0.0, 1.0)[:, None])
    fc = {"uif": uif_pred, "tuf": tuf_pred, "tr": tr_pred,
          "ratio_a": ra, "ratio_b": rb, "theta": theta, "alpha": alpha,
          "uif_q": uif_q}
    return jax.lax.optimization_barrier(fc)


def forecast_stage_streaming(pred: stats.PredictorState, day, gamma):
    """O(1) streaming counterpart of ``forecast_stage``: the same
    barrier-pinned forecast dict from the ``stats.PredictorState`` carry
    instead of rescanning the (n, H, 24) history windows."""
    return jax.lax.optimization_barrier(
        stats.streaming_forecast(pred, day, gamma))


def build_problem_arrays(fc, eta_fc, power_fn, slope_fn, queue, u_pow_cap,
                         capacity, campus, campus_limit, lambda_e, lambda_p
                         ) -> vcc.VCCProblem:
    """Assemble the fleetwide VCC problem from the forecast dict + carbon
    forecast + structural arrays (risk-aware budget, eq. 3)."""
    # risk-aware daily flexible budget (eq. 3) + carried-over queue
    tau = fc["alpha"] * fc["tuf"] + queue
    u_nom = fc["uif"] + tau[:, None] / 24.0
    pow_nom = jax.vmap(power_fn, in_axes=1, out_axes=1)(u_nom)
    pi = jax.vmap(slope_fn, in_axes=1, out_axes=1)(u_nom)
    ratio = forecast.ratio_at(fc["ratio_a"][:, None], fc["ratio_b"][:, None],
                              u_nom)
    return vcc.VCCProblem(
        eta=eta_fc, u_if=fc["uif"], u_if_q=fc["uif_q"], tau=tau,
        pow_nom=pow_nom, pi=pi, u_pow_cap=u_pow_cap,
        capacity=capacity, ratio=ratio, campus=campus,
        campus_limit=campus_limit, lambda_e=lambda_e, lambda_p=lambda_p)


def optimize_stage(cfg: StageConfig, fc, eta_fc, model: PowerModel, queue,
                   u_pow_cap, cap_day, campus, campus_limit, lambda_e,
                   lambda_p, mobility, ens: Optional[Dict] = None):
    """Fleetwide risk-aware VCC optimization. The PGD machinery is the
    ``core.solver`` layer throughout; kernels dispatch per
    cfg.use_pallas/interpret.

    Spatial flexibility (two statically selected graphs, keyed by
    ``cfg.joint_spatial``):

    * False (default) — the greedy spatial pre-shift runs before the
      temporal solve; ``mobility == 0`` collapses the shift to exactly
      zero, keeping that path bitwise-identical to the pre-joint day
      cycle (golden-trace + parity contract; the trace's scenarios are
      all mobility=0). For ``mobility > 0`` the pre-shift is now the
      EXACT linear minimizer (``spatial.spatial_shift``) rather than a
      truncated PGD loop — an intentional result change for
      spatial-mobility scenarios.
    * True — ``spatial.solve_joint``: the temporal deviations and the
      daily budget shift are descended TOGETHER (bounds recomputed from
      the shifted budgets inside the fused step), warm-started from and
      never worse than the sequential two-phase answer.

    ``ens`` (the ``risk.day_ensembles`` dict, present iff cfg.n_members
    > 1) attaches K forecast realizations AFTER the budgets are placed:
    the temporal solve then descends the soft-CVaR member tilt instead of
    the point-forecast objective (under ``joint_spatial`` the joint solve
    places the budgets on the point forecast, then the CVaR solve shapes
    at the shifted budgets). With ens=None and joint_spatial=False this
    graph is IDENTICAL to the pre-ensemble day cycle.

    Returns ``(prob, sol, diag)``: ``diag`` is the solver-telemetry dict
    (``vcc.solve_vcc(..., telemetry=True)`` channels + ``joint_winner``)
    when ``cfg.telemetry``, else ``None`` — and the telemetry=False path
    calls the solvers EXACTLY as before (byte-identical graph)."""
    with jax.named_scope("solver.problem"):
        prob = build_problem_arrays(
            fc, eta_fc,
            lambda u: model_power(model, u), lambda u: model_slope(model, u),
            queue, u_pow_cap, cap_day, campus, campus_limit, lambda_e,
            lambda_p)
        prob = jax.lax.optimization_barrier(prob)
    diag = None
    if cfg.joint_spatial:
        with jax.named_scope("solver.spatial"):
            if cfg.telemetry:
                sol, tau_j, _, diag = spatial.solve_joint(
                    prob, mobility, use_pallas=cfg.use_pallas,
                    interpret=cfg.interpret, telemetry=True)
            else:
                sol, tau_j, _ = spatial.solve_joint(
                    prob, mobility, use_pallas=cfg.use_pallas,
                    interpret=cfg.interpret)
            sol, tau_j = jax.lax.optimization_barrier((sol, tau_j))
        prob = dataclasses.replace(prob, tau=tau_j)
        if ens is not None:
            prob = risk.attach_ensemble(prob, **ens)
            if cfg.telemetry:
                # the CVaR solve at the shifted budgets produces the final
                # delta: report ITS convergence, keep the joint verdict
                sol, diag2 = vcc.solve_vcc(prob, use_pallas=cfg.use_pallas,
                                           interpret=cfg.interpret,
                                           telemetry=True)
                diag = {**diag2, "joint_winner": diag["joint_winner"]}
            else:
                sol = vcc.solve_vcc(prob, use_pallas=cfg.use_pallas,
                                    interpret=cfg.interpret)
        if diag is not None:
            diag = jax.lax.optimization_barrier(diag)
        return prob, sol, diag
    with jax.named_scope("solver.spatial"):
        tau_shifted, _ = spatial.spatial_shift(prob, mobility=mobility)
        tau_shifted = jax.lax.optimization_barrier(tau_shifted)
    prob = dataclasses.replace(prob, tau=tau_shifted)
    if ens is not None:
        prob = risk.attach_ensemble(prob, **ens)
    if cfg.telemetry:
        sol, diag = vcc.solve_vcc(prob, use_pallas=cfg.use_pallas,
                                  interpret=cfg.interpret, telemetry=True)
        # the sequential path never runs the joint refinement: report the
        # degenerate 0.0 so the telemetry pytree is config-independent
        diag["joint_winner"] = jnp.zeros((), f32)
        diag = jax.lax.optimization_barrier(diag)
    else:
        sol = vcc.solve_vcc(prob, use_pallas=cfg.use_pallas,
                            interpret=cfg.interpret)
    return prob, sol, diag


def barrier_result(res: admission.DayResult) -> admission.DayResult:
    """Pin a DayResult as an XLA materialization point. Without it, XLA
    fuses admission outputs into downstream consumers, and the fusion plan
    (hence float rounding) shifts with batch extent — breaking bitwise
    batched-vs-sequential parity. Field order mirrors the dataclass."""
    vals = jax.lax.optimization_barrier(
        (res.usage_flex, res.usage_total, res.reservations, res.power,
         res.carbon, res.served, res.arrived, res.queue_end, res.unmet))
    return admission.DayResult(*vals)


def sample_day_truth(truth, day, day_key, cap_day, arr_scale,
                     arr_hour_scale=None):
    """Sample the day's actual load: (u_if, arrivals, ratio_true), pinned.

    ``arr_hour_scale`` (optional (24,)): intraday forecast-busting
    multiplier on arrivals — applied to the ACTUALS only, after the
    forecasts were issued. None (the default) traces the exact legacy op
    sequence (byte-identical compiled graph)."""
    u_if = sample_inflexible(jax.random.fold_in(day_key, 2), truth, day)
    u_if = jnp.minimum(u_if, 0.98 * cap_day[:, None])   # outage derates
    arrivals = sample_arrivals(jax.random.fold_in(day_key, 3), truth, day)
    arrivals = arrivals * arr_scale[:, None]
    if arr_hour_scale is not None:
        arrivals = arrivals * arr_hour_scale[None, :]
    ratio_true = true_ratio(truth, u_if + arrivals)
    # pin the sampled truth: its elementwise chain must not re-fuse (and
    # re-round) differently between the scan body and other contexts
    return jax.lax.optimization_barrier((u_if, arrivals, ratio_true))


def observe_stage(truth, day, day_key, vcc_curve, cap_day, arr_scale,
                  queue, cf_queue, power_fn, intensity,
                  allowance_frac: float = 0.25, arr_hour_scale=None):
    """Sample the day's true load and run shaped + counterfactual
    admission. Returns (shaped DayResult, counterfactual DayResult,
    u_if, arrivals), results barrier-pinned."""
    u_if, arrivals, ratio_true = sample_day_truth(
        truth, day, day_key, cap_day, arr_scale, arr_hour_scale)
    res = admission.run_day(vcc_curve, u_if, arrivals, ratio_true, cap_day,
                            queue, power_fn, intensity, allowance_frac)
    unshaped = jnp.broadcast_to(cap_day[:, None] * 10.0, vcc_curve.shape)
    cf = admission.run_day(unshaped, u_if, arrivals, ratio_true, cap_day,
                           cf_queue, power_fn, intensity, allowance_frac)
    return barrier_result(res), barrier_result(cf), u_if, arrivals


def observe_stage_mpc(truth, day, day_key, prob, sol, fc, gate, cap_day,
                      arr_scale, queue, cf_queue, power_fn, intensity,
                      allowance_frac: float = 0.25, arr_hour_scale=None,
                      use_pallas=None, interpret=False):
    """Closed-loop counterpart of ``observe_stage``: same sampled truth
    and same unshaped counterfactual, but the shaped run is the hourly
    MPC recourse loop (``core.mpc.mpc_day``) instead of open-loop
    admission under the 00:00 curve. Returns (res, cf, u_if, arrivals,
    enforced_vcc (n, 24), stats.HourAccum, mpc.MPCDiag)."""
    u_if, arrivals, ratio_true = sample_day_truth(
        truth, day, day_key, cap_day, arr_scale, arr_hour_scale)
    res, vcc_real, acc, diag = mpc.mpc_day(
        prob, sol, fc["tuf"], gate, cap_day, u_if, arrivals, ratio_true,
        queue, power_fn, intensity, allowance_frac=allowance_frac,
        use_pallas=use_pallas, interpret=interpret)
    unshaped = jnp.broadcast_to(cap_day[:, None] * 10.0, vcc_real.shape)
    cf = admission.run_day(unshaped, u_if, arrivals, ratio_true, cap_day,
                           cf_queue, power_fn, intensity, allowance_frac)
    vcc_real = jax.lax.optimization_barrier(vcc_real)
    return (barrier_result(res), barrier_result(cf), u_if, arrivals,
            vcc_real, acc, diag)


def slo_stage(slo_state, slo_cfg: slo.SLOConfig, daily_reservations,
              vcc_budget, unmet, arrived):
    """End-of-day SLO feedback: returns (new slo_state, shaping_allowed
    for the NEXT day). ``arrived`` scales the violation threshold
    (slo.SLOConfig.rel_tol)."""
    return slo.update(slo_state, slo_cfg, daily_reservations, vcc_budget,
                      unmet, arrived)


# ------------------------------------------------------------- composition

def make_day_step(cfg: StageConfig):
    """One pure CICS day: forecast -> optimize -> shape -> observe -> SLO.

    Returns step(params, state, xs) -> (state', StepOut) where xs holds
    this day's scenario-schedule slices (all-ones = the paper's nominal
    operation)."""
    slo_cfg = slo.SLOConfig(margin=cfg.slo_margin,
                            pause_days=cfg.slo_pause_days)
    if cfg.streaming and cfg.n_members > 1:
        raise ValueError(
            "StageConfig.streaming=True does not support forecast "
            "ensembles (n_members > 1): risk.day_ensembles bootstraps "
            "whole days of the hist_uif_pred/hist_uif error history, "
            "which the streaming state no longer carries")

    def step(params: SimParams, state: SimState, xs: Dict[str, jnp.ndarray]
             ) -> Tuple[SimState, StepOut]:
        day_key = jax.random.fold_in(params.key, state.day)
        cap_day = jax.lax.optimization_barrier(
            params.truth["capacity"] * xs["cap_scale"])
        # 1-2. power pipeline + load forecasting. Streaming: O(1) updates
        # over the PredictorState carry (the usage ring IS the 28-day
        # window the rescan power fit slices, so the fit is bitwise the
        # same); rescan: the legacy O(H) history-window graph.
        usage_window = (state.pred.usage_ring if cfg.streaming
                        else state.hist_usage)
        with jax.named_scope("stage.power"):
            model = power_stage(usage_window, params.lam,
                                params.truth["capacity"], pd_truth(params),
                                jax.random.fold_in(day_key, 1))
        with jax.named_scope("stage.forecast"):
            if cfg.streaming:
                fc = forecast_stage_streaming(state.pred, state.day,
                                              params.gamma)
            else:
                fc = forecast_stage(
                    state.hist_uif, state.hist_flex_daily,
                    state.hist_res_daily, state.hist_usage, state.hist_res,
                    state.hist_tr_pred, state.hist_uif_pred, state.day,
                    params.gamma)
        # 3. carbon pipeline: scenario-perturbed grid, day-ahead forecast
        with jax.named_scope("stage.carbon"):
            act_z, fc_z = carbon_stage(params.zone, state.carbon_hist,
                                       jax.random.fold_in(day_key, 4),
                                       xs["green_scale"], xs["coal_scale"])
            # intraday forecast-busting: perturb the ACTUAL intensity
            # after the day-ahead forecast is drawn (the planner is blind
            # until the hours realize; tomorrow's forecaster sees them via
            # carbon_hist)
            if "carbon_hour_scale" in xs:
                act_z = act_z * xs["carbon_hour_scale"][None, :]
            eta_act = act_z[state.zmap]
            eta_fc = fc_z[state.zmap]
        # 3b. forecast ensembles (K > 1 only: the n_members == 1 graph must
        # stay identical to the point-forecast day — parity/golden traces)
        ens = None
        if cfg.n_members > 1:
            with jax.named_scope("stage.ensembles"):
                ens = risk.day_ensembles(
                    jax.random.fold_in(day_key, 5), cfg.n_members,
                    fc["uif"], state.hist_uif_pred, state.hist_uif, fc_z,
                    state.carbon_hist, state.zmap, params.risk_beta)
        # 4. fleetwide risk-aware VCC optimization (+ spatial pre-shift)
        with jax.named_scope("stage.optimize"):
            prob, sol, sdiag = optimize_stage(
                cfg, fc, eta_fc, model, state.queue,
                state.u_pow_cap * xs["cap_scale"], cap_day, state.campus,
                state.campus_limit * xs["campus_scale"],
                params.lambda_e, params.lambda_p, params.mobility, ens=ens)
        # 5. SLO gate: paused clusters get VCC = machine capacity
        with jax.named_scope("stage.slo"):
            gate = state.shaping_allowed & sol.shaped
            vcc_curve = jnp.where(gate[:, None], sol.vcc,
                                  cap_day[:, None] * 10.0)
            vcc_curve = jax.lax.optimization_barrier(vcc_curve)
        # 6. real time: admission on ACTUAL load (+ counterfactual).
        # mpc=True runs the hourly recourse loop and the curve the SLO
        # detector sees is the hour-by-hour ENFORCED one, not the 00:00
        # plan; mpc=False traces the exact open-loop legacy graph.
        arr_hs = xs.get("arrival_hour_scale")
        mdiag = None
        acc = None
        recourse_hours = None
        with jax.named_scope("stage.observe"):
            if cfg.mpc:
                (res, cf, u_if, _, vcc_enforced, acc,
                 mdiag) = observe_stage_mpc(
                    params.truth, state.day, day_key, prob, sol, fc, gate,
                    cap_day, xs["arrival_scale"], state.queue,
                    state.cf_queue, lambda u: model_power(model, u),
                    eta_act, allowance_frac=cfg.slo_allowance,
                    arr_hour_scale=arr_hs, use_pallas=cfg.use_pallas,
                    interpret=cfg.interpret)
                # whole hours per cluster (k / 24 * 24 is exact in
                # float32), so the ordered sum is an exact count
                recourse_hours = hour_sum(mdiag.recourse_frac * 24.0)
            else:
                res, cf, u_if, _ = observe_stage(
                    params.truth, state.day, day_key, vcc_curve, cap_day,
                    xs["arrival_scale"], state.queue, state.cf_queue,
                    lambda u: model_power(model, u), eta_act,
                    allowance_frac=cfg.slo_allowance,
                    arr_hour_scale=arr_hs)
                vcc_enforced = vcc_curve
        # 7. telemetry + SLO feedback
        with jax.named_scope("stage.slo"):
            slo_state = {"crowded_streak": state.crowded_streak,
                         "pause_left": state.pause_left,
                         "violation_days": state.violation_days,
                         "observed_days": state.observed_days}
            new_slo, allowed = slo_stage(slo_state, slo_cfg,
                                         hour_sum(res.reservations),
                                         hour_sum(vcc_enforced), res.unmet,
                                         res.arrived)
        with jax.named_scope("stage.history"):
            if cfg.streaming:
                # O(1) telemetry: absorb the day into the streaming carry
                # (prediction errors pair same-day with the fc issued
                # above — exactly what the hist_*_pred rolls recorded for
                # later)
                if cfg.mpc:
                    # hour-grain chain: the 24 hour_update scatters
                    # finalize into the same PredictorState the daily
                    # batch would
                    pred_new = stats.hour_finalize(state.pred, acc, fc,
                                                   state.day, params.gamma)
                else:
                    pred_new = stats.predictor_update(
                        state.pred, fc, state.day, params.gamma, u_if,
                        res.served, hour_sum(res.reservations),
                        res.usage_total, res.reservations)
                telemetry = dict(pred=pred_new)
            else:
                # roll the rescan history windows (predictions included,
                # for the trailing-error quantiles)
                telemetry = dict(
                    hist_uif=roll(state.hist_uif, u_if),
                    hist_flex_daily=roll(state.hist_flex_daily, res.served),
                    hist_res_daily=roll(state.hist_res_daily,
                                        hour_sum(res.reservations)),
                    hist_usage=roll(state.hist_usage, res.usage_total),
                    hist_res=roll(state.hist_res, res.reservations),
                    hist_tr_pred=roll(state.hist_tr_pred, fc["tr"]),
                    hist_uif_pred=roll(state.hist_uif_pred, fc["uif"]))
            new_state = state._replace(
                day=state.day + 1,
                carbon_hist=roll(state.carbon_hist, act_z),
                queue=res.queue_end,
                cf_queue=cf.queue_end,
                crowded_streak=new_slo["crowded_streak"],
                pause_left=new_slo["pause_left"],
                violation_days=new_slo["violation_days"],
                observed_days=new_slo["observed_days"],
                shaping_allowed=allowed,
                **telemetry,
            )
        # 8. DayTelemetry record (telemetry=False leaves the default None
        # StepOut leaf -> empty pytree subtree -> unchanged compiled graph)
        telem = None
        if cfg.telemetry:
            # lazy: core must not import repro.sim at module level
            from repro.sim import telemetry as _telemetry
            with jax.named_scope("stage.telemetry"):
                if cfg.streaming:
                    trail = {"uif": state.pred.uif_day_ring,
                             "tuf": state.pred.flex_ring,
                             "tr": state.pred.res_ring}
                else:
                    trail = {"uif": hour_sum(state.hist_uif[:, -7:]),
                             "tuf": state.hist_flex_daily[:, -7:],
                             "tr": state.hist_res_daily[:, -7:]}
                telem = _telemetry.day_telemetry(
                    sdiag, fc, res, u_if, vcc_enforced,
                    pause_left=new_slo["pause_left"], shaped=sol.shaped,
                    trail=trail, recourse=mdiag)
        return new_state, StepOut(res=res, cf=cf, sol=sol,
                                  vcc_curve=vcc_enforced, fc=fc, prob=prob,
                                  eta_act=eta_act, telemetry=telem,
                                  recourse_hours=recourse_hours)

    return step


@functools.lru_cache(maxsize=None)
def jitted_day_step(cfg: StageConfig):
    """The day step compiled alone, one compile per StageConfig, for
    drivers that step it from a Python loop
    (``sim.engine.rollout_sequential``)."""
    return jax.jit(make_day_step(cfg))


# ------------------------------------------------------------ init/burn-in

def vary_like(tree, ref):
    """Give a pytree built from constants the varying mesh axes of
    ``ref``. Under ``shard_map`` a scan carry that starts from constants
    is typed as the same on every device, while the day step makes it
    vary with the device's slice of the batch; the carry's type must say
    so up front. Outside ``shard_map`` this returns ``tree`` unchanged."""
    vma = jax.typeof(ref).vma

    def cast(x):
        missing = tuple(sorted(vma - jax.typeof(x).vma))
        return jax.lax.pcast(x, missing, to="varying") if missing else x

    return jax.tree.map(cast, tree)


def burnin_step(params: SimParams, state: SimState) -> SimState:
    """One unshaped day with the cheap linear power proxy (history fill)."""
    day_key = jax.random.fold_in(params.key, state.day)
    cap = params.truth["capacity"]

    def proxy_power(u):
        return 100.0 + 300.0 * u

    with jax.named_scope("stage.carbon"):
        act_z, _ = carbon_stage(params.zone, state.carbon_hist,
                                jax.random.fold_in(day_key, 4),
                                jnp.ones_like(params.zone["solar_cap"]),
                                jnp.ones_like(params.zone["solar_cap"]))
    with jax.named_scope("stage.observe"):
        unshaped = jnp.broadcast_to(cap[:, None] * 10.0, (cap.shape[0], 24))
        res, _, u_if, _ = observe_stage(
            params.truth, state.day, day_key, unshaped, cap,
            jnp.ones_like(cap), state.queue, state.queue, proxy_power,
            act_z[state.zmap])
    return state._replace(
        day=state.day + 1,
        hist_uif=roll(state.hist_uif, u_if),
        hist_flex_daily=roll(state.hist_flex_daily, res.served),
        hist_res_daily=roll(state.hist_res_daily,
                            hour_sum(res.reservations)),
        hist_usage=roll(state.hist_usage, res.usage_total),
        hist_res=roll(state.hist_res, res.reservations),
        carbon_hist=roll(state.carbon_hist, act_z),
        queue=res.queue_end,
        cf_queue=res.queue_end,
    )


def make_init(n_clusters: int, n_campuses: int, n_zones: int,
              hist_days: int, streaming: bool = False):
    """init(params) -> burned-in SimState. jit- and vmap-compatible: the
    hist_days burn-in runs under lax.scan (one dispatch, not hundreds).

    With ``streaming=True`` the burn-in still fills the full history
    window (it is one-time cost), then every streaming estimator is
    warm-started from it (``stats.init_predictor`` — handoff-bitwise on
    the EWMA components) and the seven ``hist_*`` windows are dropped to
    zero-length stubs: the carried state becomes O(1) in hist_days."""
    n, m, z, H = n_clusters, n_campuses, n_zones, hist_days
    if streaming and H < 7:
        raise ValueError(f"streaming init needs hist_days >= 7, got {H}")
    campus_np = [i % m for i in range(n)]
    zmap_np = [(c % z) for c in campus_np]

    @jax.named_scope("engine.burnin")
    def init(params: SimParams) -> SimState:
        cap = params.truth["capacity"]
        state = SimState(
            day=jnp.zeros((), jnp.int32),
            campus=jnp.asarray(campus_np, jnp.int32),
            zmap=jnp.asarray(zmap_np, jnp.int32),
            campus_limit=jnp.zeros((m,), f32),
            u_pow_cap=cap * 0.95,
            hist_uif=jnp.zeros((n, H, 24), f32),
            hist_flex_daily=jnp.zeros((n, H), f32),
            hist_res_daily=jnp.zeros((n, H), f32),
            hist_usage=jnp.zeros((n, H, 24), f32),
            hist_res=jnp.zeros((n, H, 24), f32),
            hist_tr_pred=jnp.zeros((n, H), f32),
            hist_uif_pred=jnp.zeros((n, H, 24), f32),
            carbon_hist=jnp.zeros((z, H, 24), f32),
            queue=jnp.zeros((n,), f32),
            cf_queue=jnp.zeros((n,), f32),
            crowded_streak=jnp.zeros((n,), jnp.int32),
            pause_left=jnp.zeros((n,), jnp.int32),
            violation_days=jnp.zeros((n,), jnp.int32),
            observed_days=jnp.zeros((n,), jnp.int32),
            shaping_allowed=jnp.ones((n,), bool),
        )
        state = vary_like(state, cap)

        def burn(s, _):
            return burnin_step(params, s), None

        state, _ = jax.lax.scan(burn, state, None, length=H)
        # zero-error prediction prior; honest quantiles build up in-horizon
        state = state._replace(hist_tr_pred=state.hist_res_daily,
                               hist_uif_pred=state.hist_uif)
        # campus contracts: 97% of fitted-model campus peak over last week
        model = power_stage(state.hist_usage, params.lam, cap,
                            pd_truth(params),
                            jax.random.fold_in(params.key, 999))
        upow = jax.vmap(lambda u: model_power(model, u),
                        in_axes=1, out_axes=1)(
            state.hist_usage[:, -7:].reshape(n, -1))
        peak = upow.max(axis=1)
        limit = jax.ops.segment_sum(peak, state.campus,
                                    num_segments=m) * 0.97
        state = state._replace(campus_limit=limit.astype(f32))
        if streaming:
            pred = stats.init_predictor(
                state.hist_uif, state.hist_flex_daily,
                state.hist_res_daily, state.hist_usage, state.hist_res,
                state.hist_tr_pred, state.hist_uif_pred, state.day,
                params.gamma)
            state = state._replace(
                pred=pred,
                # carbon_stage's day-ahead forecast reads only the
                # trailing 7 days (carbon.forecast_day_ahead), so the
                # streaming carry keeps exactly that window — bitwise
                # the same forecasts, O(1) state in hist_days
                carbon_hist=state.carbon_hist[:, -stats.WEEK:],
                hist_uif=jnp.zeros((n, 0, 24), f32),
                hist_flex_daily=jnp.zeros((n, 0), f32),
                hist_res_daily=jnp.zeros((n, 0), f32),
                hist_usage=jnp.zeros((n, 0, 24), f32),
                hist_res=jnp.zeros((n, 0, 24), f32),
                hist_tr_pred=jnp.zeros((n, 0), f32),
                hist_uif_pred=jnp.zeros((n, 0, 24), f32))
        # materialize: burned-in state must not fuse into rollout consumers
        # (jit(init + rollout) would otherwise drift vs separate calls)
        return jax.lax.optimization_barrier(state)

    return init
