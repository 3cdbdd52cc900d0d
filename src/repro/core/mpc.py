"""Intra-day MPC recourse: the hourly closed loop over the day-ahead VCC.

The paper's pipeline commits a Virtual Capacity Curve once per day
(§III), so when actuals diverge from the day-ahead forecast the plan is
stale for up to 23 hours — exactly the regime where "Let's Wait Awhile"
shows shifting gains collapse. This module closes the loop at hour grain:

  each hour h:
    1. enforce the CURRENT plan's VCC for hour h through the same
       ``admission.admission_tick`` the open loop scans (shared code —
       the controller cannot fork from the open-loop semantics),
    2. absorb the realized hour into the ``stats.HourAccum`` hour-grain
       predictor accumulator (finalized into the streaming
       ``PredictorState`` at day close),
    3. nowcast the remaining hours — persistence-decay corrections of
       the intensity / inflexible forecasts from the latest observed
       ratio, and a demand-surprise term that grows the flexible budget
       tau when realized arrivals outrun the forecast's pro-rata share,
    4. warm-start a re-solve of the REMAINING hours' deviations
       (``vcc.solve_vcc_suffix``: elapsed hours pinned at realized
       values, conservation tightened to the suffix, outer 2 x inner 8 =
       16 PGD steps vs the day solve's 1600),
    5. accept the revised plan per cluster only when a staleness TRIGGER
       fires — the same signals the telemetry layer gauges (elapsed-hour
       ``uif_mape``, intensity forecast deviation, demand surprise vs
       the tau budget) — and record trigger/depth diagnostics.

Everything is elementwise ops + ``lax.scan`` + ordered ``hour_sum``
reductions, so the closed loop keeps the engine's bitwise
batched==sequential parity. The ``StageConfig.mpc=False`` day step never
calls into this module (Python-level flag), preserving the byte-identical
HLO collapse contract.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import admission, stats, vcc
from repro.core.admission import hour_sum

f32 = jnp.float32

# staleness-trigger thresholds (recourse accepts a re-solved suffix only
# when the day-ahead plan is measurably stale; under nominal forecast
# noise the loop stays open and the realized day matches the committed
# plan's intent)
MAPE_TRIGGER = 0.08      # elapsed-hour U_IF MAPE above typical noise
ETA_TRIGGER = 0.20       # |realized/forecast intensity - 1| last hour
SURGE_TRIGGER = 0.05     # demand surprise as a fraction of tau
# persistence-decay of the last observed forecast-error ratio applied to
# the remaining hours (h hours ahead decays as DECAY**h)
ETA_DECAY = 0.7
UIF_DECAY = 0.5


class MPCDiag(NamedTuple):
    """Per-cluster recourse diagnostics for the telemetry record."""
    recourse_frac: jnp.ndarray    # (n,) fraction of hours re-planned
    recourse_depth: jnp.ndarray   # (n,) mean |delta change| when re-planned


def gated_curve(p: vcc.VCCProblem, delta, tau, gate, cap_day):
    """The hourly reservation curve the scheduler enforces for plan
    ``(delta, tau)``: the ``solve_vcc`` curve formula under the SLO gate
    (paused / infeasible clusters see VCC = 10x capacity = unshaped)."""
    vcc_shaped = (p.u_if + (1.0 + delta) * tau[:, None] / 24.0) * p.ratio
    v = jnp.minimum(vcc_shaped, p.capacity[:, None])
    return jnp.where(gate[:, None], v, cap_day[:, None] * 10.0)


def mpc_day(prob: vcc.VCCProblem, sol: vcc.VCCSolution, tuf_fc, gate,
            cap_day, u_if, arrivals, ratio_true, queue0, power_fn,
            intensity, *, allowance_frac: float = 0.25,
            inner_iters: int = 8, outer_iters: int = 2,
            use_pallas: Optional[bool] = None, interpret: bool = False
            ) -> Tuple[admission.DayResult, jnp.ndarray, stats.HourAccum,
                       MPCDiag]:
    """Run one closed-loop day: 24 admission ticks with hourly warm-started
    suffix re-solves of the remaining VCC.

    ``prob``/``sol``: the day-ahead problem and its solution; ``tuf_fc``:
    the (n,) day-ahead flexible-total forecast (demand-surprise
    reference); ``gate``: (n,) bool = shaping_allowed & sol.shaped (fixed
    for the day — paused/infeasible clusters stay open-loop); ``u_if`` /
    ``arrivals`` / ``ratio_true`` / ``intensity``: (n, 24) actuals.

    Returns (DayResult, enforced_vcc (n, 24), HourAccum, MPCDiag). The
    enforced curve is the hour-by-hour curve admission actually saw —
    that is what the SLO crowding detector and the binding-fraction
    telemetry must be measured against, not the 00:00 plan.
    """
    n = prob.tau.shape[0]
    tau0 = prob.tau
    hours_f = jnp.arange(24, dtype=f32)

    carry0 = dict(
        queue=queue0,
        delta=sol.delta,
        tau=tau0,
        mu=sol.mu,
        acc=stats.hour_accum_init(u_if),
        vcc_real=jnp.zeros_like(u_if),
        arr_sofar=jnp.zeros_like(tau0),
        mape_sum=jnp.zeros_like(tau0),
        trig_hours=jnp.zeros_like(tau0),
        depth_sum=jnp.zeros_like(tau0),
    )
    xs = (jnp.arange(24), u_if.T, arrivals.T, ratio_true.T, intensity.T)

    @jax.named_scope("mpc.hour")
    def hour_step(c, x):
        h, uif_h, arr_h, r_h, eta_h = x
        # 1. enforce the current plan's curve for this hour
        curve = gated_curve(prob, c["delta"], c["tau"], gate, cap_day)
        vcc_h = curve[:, h]
        queue, use_flex_h = admission.admission_tick(
            c["queue"], vcc_h, uif_h, arr_h, r_h, cap_day)
        # 2. hour-grain predictor advancement
        acc = stats.hour_update(c["acc"], h, uif_h, use_flex_h, r_h)
        vcc_real = c["vcc_real"].at[:, h].set(vcc_h)
        # 3. staleness signals (the telemetry gauges, computed in-loop)
        fc_uif_h = prob.u_if[:, h]
        fc_eta_h = prob.eta[:, h]
        elapsed = (h + 1).astype(f32)
        arr_sofar = c["arr_sofar"] + arr_h
        mape_sum = c["mape_sum"] + jnp.abs(fc_uif_h - uif_h) \
            / jnp.clip(jnp.abs(uif_h), 1e-6, None)
        mape_el = mape_sum / elapsed
        r_eta = eta_h / jnp.clip(fc_eta_h, 1e-6, None)
        r_uif = uif_h / jnp.clip(fc_uif_h, 1e-6, None)
        q_extra = jnp.clip(arr_sofar - elapsed / 24.0 * tuf_fc, 0.0, None)
        trigger = (mape_el > MAPE_TRIGGER) \
            | (jnp.abs(r_eta - 1.0) > ETA_TRIGGER) \
            | (q_extra > SURGE_TRIGGER * jnp.clip(tau0, 1e-6, None))
        # 4. nowcast the remaining hours: persistence-decay corrections +
        #    the demand-surprise budget growth
        ahead = jnp.clip(hours_f[None, :] - elapsed, 0.0, None)
        rem = hours_f[None, :] >= elapsed          # (1, 24) hours > h
        eta_corr = 1.0 + (jnp.clip(r_eta, 0.25, 4.0) - 1.0)[:, None] \
            * ETA_DECAY ** ahead
        uif_corr = 1.0 + (jnp.clip(r_uif, 0.5, 2.0) - 1.0)[:, None] \
            * UIF_DECAY ** ahead
        p_now = dataclasses.replace(
            prob,
            eta=jnp.where(rem, prob.eta * eta_corr, prob.eta),
            u_if=jnp.where(rem, prob.u_if * uif_corr, prob.u_if),
            u_if_q=jnp.where(rem, prob.u_if_q * uif_corr, prob.u_if_q),
            tau=tau0 + q_extra)
        tau_new = p_now.tau
        # 5. warm start: elapsed hours pinned at realized deviations (in
        #    the NEW budget's units), remaining hours keep the planned
        #    USAGE (1+delta)*tau/24 re-expressed at the new budget
        tau24_new = jnp.clip(tau_new[:, None] / 24.0, 1e-9, None)
        pinned = acc.use_flex / tau24_new - 1.0
        scale = (c["tau"] / jnp.clip(tau_new, 1e-9, None))[:, None]
        delta_warm = jnp.where(rem, (1.0 + c["delta"]) * scale - 1.0,
                               pinned)
        with jax.named_scope("mpc.resolve"):
            sol_s = vcc.solve_vcc_suffix(
                p_now, delta_warm, c["mu"], h + 1, inner_iters=inner_iters,
                outer_iters=outer_iters, use_pallas=use_pallas,
                interpret=interpret)
        accept = gate & trigger & sol_s.shaped
        delta_next = jnp.where(accept[:, None], sol_s.delta, c["delta"])
        tau_next = jnp.where(accept, tau_new, c["tau"])
        # 6. recourse depth: mean |delta change| over the remaining hours
        rem_n = jnp.clip(hour_sum(rem.astype(f32)), 1.0, None)
        depth = hour_sum(jnp.abs(delta_next - c["delta"])
                         * rem.astype(f32)) / rem_n
        return dict(
            queue=queue, delta=delta_next, tau=tau_next, mu=sol_s.mu,
            acc=acc, vcc_real=vcc_real, arr_sofar=arr_sofar,
            mape_sum=mape_sum,
            trig_hours=c["trig_hours"] + accept.astype(f32),
            depth_sum=c["depth_sum"] + depth), None

    c, _ = jax.lax.scan(hour_step, carry0, xs)
    res = admission.finalize_day(
        c["acc"].use_flex, c["queue"], u_if, arrivals, ratio_true, queue0,
        power_fn, intensity, allowance_frac)
    diag = MPCDiag(
        recourse_frac=c["trig_hours"] / 24.0,
        recourse_depth=c["depth_sum"] / jnp.clip(c["trig_hours"], 1.0,
                                                 None))
    return res, c["vcc_real"], c["acc"], diag
