"""Plain reference of the closed-loop fleet: the day-ahead system of
``reference.py`` with the hourly MPC recourse in place of the shaped
fleet's open-loop admission.

``simulate`` copies the outer structure of ``reference.simulate`` (the
burn-in, the campus contracts, the day scan, the SLO pause, the history
rolls and the ledger), because that function takes no hook and is not
to be edited. Only the shaped fleet's day differs: ``closed_loop_day``,
written from the equations of this project's controller as its
description (``core/mpc.py``) states them, and not from its code. Each
hour h, for every cluster:

1. admit the hour's work against the current plan's curve, the gated VCC
   (u_if + (1 + delta) tau / 24) R, capped at capacity; paused or
   infeasible clusters see ten times capacity;
2. staleness signals: the inflexible forecast's mean absolute
   percentage error over the elapsed hours, the last hour's ratio of
   realized to forecast intensity, and the arrivals so far beyond the
   forecast's pro-rata share of the day; a trigger fires where one
   passes its threshold;
3. nowcast the remaining hours: the last intensity and inflexible-load
   error ratios, clipped, decayed as decay ** (hours ahead), and the
   budget tau grown by the surplus arrivals;
4. warm start: elapsed hours pinned at the realized deviations in the
   new budget's units, remaining hours keeping the planned usage;
5. re-solve the remaining hours: ``reference._ascent`` with the elapsed
   hours pinned lo == ub at the realized deviations, ``outer`` x
   ``inner`` steps from the carried campus duals, the exact breakpoint
   projection; a cluster whose realized prefix no box can conserve
   keeps lo == ub at the warm start;
6. accept the re-solved suffix (deviations and budget) where the
   cluster is gated, a trigger fired and the suffix is feasible; the
   duals are carried on either way.

The day's ledger and history come from the realized hourly flexible
use; the SLO detector sees the curve each hour enforced; the unshaped
counterfactual stays the open-loop ``reference.admit``. Thresholds,
decays and the schedule come from the configuration's ``mpc`` block.

It departs from the program as ``reference.py`` does: the projection is
exact rather than 50 bisection passes, sums are plain, and nothing is
pinned for batch invariance. The simulator runs in float32 (the PD fit's
normal equations at ``Precision.HIGHEST``, in ``reference.power_model``);
the day-ahead and suffix solves run in ``solve_dt``, float32 for the
reference and bfloat16 for the control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.chip.reference import (H, _ascent, _roll, actual_load,
                                       admit, day_bounds, forecasts,
                                       grid_day, hsum, power_model,
                                       ratio_of, solve_day, spatial_shift)


def _cast(p, dt):
    return {k: v.astype(dt) if jnp.issubdtype(v.dtype, jnp.floating)
            else v for k, v in p.items()}


def suffix_solve(p, d_warm, mu, rest, solver, outer, inner, solve_dt):
    """Re-solve the remaining hours (``rest``, (24,) bool) from the warm
    start: (delta, mu, feasible)."""
    ps = _cast(p, solve_dt)
    d0 = d_warm.astype(solve_dt)
    lo, ub, ok = day_bounds(dict(ps, drop_limit=0.8))
    lo = jnp.where(rest[None], lo, d0)
    ub = jnp.where(rest[None], ub, d0)
    ok = ok & (hsum(lo) <= 1e-6) & (hsum(ub) >= -1e-6)
    lo = jnp.where(ok[:, None], lo, d0)
    ub = jnp.where(ok[:, None], ub, d0)
    d, mu = _ascent(ps, d0, mu.astype(solve_dt), lo, ub, outer, inner,
                    solver)
    return d.astype(jnp.float32), mu.astype(jnp.float32), ok


def closed_loop_day(p, delta, mu, tuf, gate, cap_day, u_if, arr, ratio,
                    queue0, power, intensity, allowance, solver, mpc,
                    solve_dt):
    """One closed-loop day of the shaped fleet from the day-ahead plan
    (``delta``, ``mu``) of problem ``p`` (float32). Returns (the day's
    result as ``reference.admit`` gives it, the enforced curve (n, 24),
    the accepted re-plans (n,))."""
    dt = jnp.float32
    n = p["tau"].shape[0]
    tau0 = p["tau"]
    hours = jnp.arange(H, dtype=dt)

    def curve(delta, tau):
        shaped = (p["u_if"] + (1 + delta) * tau[:, None] / 24) * p["ratio"]
        return jnp.where(gate[:, None],
                         jnp.minimum(shaped, p["capacity"][:, None]),
                         cap_day[:, None] * 10)

    def hour(c, x):
        h, u, a, r, e = x
        v = curve(c["delta"], c["tau"])[:, h]
        room = jnp.minimum(jnp.clip(v - u * r, 0, None) / jnp.maximum(r, 1),
                           jnp.clip(cap_day - u, 0, None))
        run = jnp.minimum(c["q"] + a, room)
        flex = c["flex"].at[:, h].set(run)
        # staleness signals and triggers
        elapsed = (h + 1).astype(dt)
        arr_sum = c["arr_sum"] + a
        ape_sum = c["ape_sum"] + jnp.abs(p["u_if"][:, h] - u) \
            / jnp.clip(jnp.abs(u), 1e-6, None)
        r_eta = e / jnp.clip(p["eta"][:, h], 1e-6, None)
        r_uif = u / jnp.clip(p["u_if"][:, h], 1e-6, None)
        surplus = jnp.clip(arr_sum - elapsed / 24 * tuf, 0, None)
        fire = (ape_sum / elapsed > mpc["mape_trigger"]) \
            | (jnp.abs(r_eta - 1) > mpc["eta_trigger"]) \
            | (surplus > mpc["surge_trigger"] * jnp.clip(tau0, 1e-6, None))
        # nowcast of the remaining hours and the grown budget
        rest = hours >= elapsed
        ahead = jnp.clip(hours - elapsed, 0, None)
        k_eta = 1 + (jnp.clip(r_eta, 0.25, 4.0) - 1)[:, None] \
            * mpc["eta_decay"] ** ahead
        k_uif = 1 + (jnp.clip(r_uif, 0.5, 2.0) - 1)[:, None] \
            * mpc["uif_decay"] ** ahead
        tau_new = tau0 + surplus
        now = dict(p, eta=jnp.where(rest, p["eta"] * k_eta, p["eta"]),
                   u_if=jnp.where(rest, p["u_if"] * k_uif, p["u_if"]),
                   u_if_q=jnp.where(rest, p["u_if_q"] * k_uif,
                                    p["u_if_q"]),
                   tau=tau_new)
        # warm start in the new budget's units
        per_hour = jnp.clip(tau_new[:, None] / 24, 1e-9, None)
        scale = (c["tau"] / jnp.clip(tau_new, 1e-9, None))[:, None]
        d_warm = jnp.where(rest, (1 + c["delta"]) * scale - 1,
                           flex / per_hour - 1)
        d_new, mu, ok = suffix_solve(now, d_warm, c["mu"], rest, solver,
                                     mpc["outer_iters"], mpc["inner_iters"],
                                     solve_dt)
        accept = gate & fire & ok
        return dict(q=c["q"] + a - run, flex=flex, arr_sum=arr_sum,
                    ape_sum=ape_sum, mu=mu,
                    delta=jnp.where(accept[:, None], d_new, c["delta"]),
                    tau=jnp.where(accept, tau_new, c["tau"]),
                    count=c["count"] + accept.astype(dt)), v

    zeros = jnp.zeros((n,), dt)
    c0 = dict(q=queue0, flex=jnp.zeros_like(u_if), arr_sum=zeros,
              ape_sum=zeros, mu=mu, delta=delta, tau=tau0, count=zeros)
    c, v = jax.lax.scan(hour, c0, (jnp.arange(H), u_if.T, arr.T, ratio.T,
                                   intensity.T))
    usage = u_if + c["flex"]
    pw = jax.vmap(power, 1, 1)(usage)
    arrived = hsum(arr)
    res = {"usage": usage, "res": usage * ratio, "power": pw,
           "carbon": pw * intensity, "served": hsum(c["flex"]),
           "arrived": arrived, "queue_end": c["q"],
           "unmet": jnp.clip(c["q"] - queue0 - allowance * arrived, 0,
                             None)}
    return res, v.T, c["count"]


def simulate(row, fleet, solver, mpc, days, solve_dt="float32"):
    """One closed-loop rollout: ``hist_days`` unshaped burn-in days,
    campus contracts at 97% of the burned-in fitted campus peaks, then
    ``days`` days, each with one day-ahead solve and the hourly loop, the
    solves in ``solve_dt``. Returns the ledger's per-cluster totals, the
    burned-in flexible backlog, the last ``days`` of usage and the
    accepted re-plans (cluster-hours)."""
    dt = jnp.float32
    n, m, z = fleet["n_clusters"], fleet["n_campuses"], fleet["n_zones"]
    hd = fleet["hist_days"]
    cast = (lambda a: a.astype(dt)
            if jnp.issubdtype(a.dtype, jnp.floating) else a)
    row = jax.tree.map(cast, row)
    truth, zone, key = row["truth"], row["zone"], row["key"]
    cap = truth["capacity"]
    campus = jnp.arange(n) % m
    zmap = campus % z
    allowance = fleet["slo_allowance"]
    zeros = jnp.zeros((n, hd, H), dt)
    s = {"day": jnp.zeros((), jnp.int32), "hist_uif": zeros,
         "hist_flex": jnp.zeros((n, hd), dt),
         "hist_resd": jnp.zeros((n, hd), dt), "hist_usage": zeros,
         "hist_res": zeros, "carbon": jnp.zeros((z, hd, H), dt),
         "queue": jnp.zeros((n,), dt), "cf_queue": jnp.zeros((n,), dt)}
    ones_z = jnp.ones((z,), dt)

    def burn(s, _):
        k = jax.random.fold_in(key, s["day"])
        act, _ = grid_day(zone, s["carbon"], jax.random.fold_in(k, 4),
                          ones_z, ones_z, dt)
        u_if, arr, ratio = actual_load(truth, s["day"], k, cap,
                                       jnp.ones_like(cap), None, dt)
        r = admit(jnp.broadcast_to(cap[:, None] * 10, (n, H)), u_if, arr,
                  ratio, cap, s["queue"], lambda u: 100 + 300 * u,
                  act[zmap], allowance)
        return dict(s, day=s["day"] + 1,
                    hist_uif=_roll(s["hist_uif"], u_if),
                    hist_flex=_roll(s["hist_flex"], r["served"]),
                    hist_resd=_roll(s["hist_resd"], hsum(r["res"])),
                    hist_usage=_roll(s["hist_usage"], r["usage"]),
                    hist_res=_roll(s["hist_res"], r["res"]),
                    carbon=_roll(s["carbon"], act),
                    queue=r["queue_end"], cf_queue=r["queue_end"]), None

    s, _ = jax.lax.scan(burn, s, None, length=hd)
    queue0 = s["queue"]
    power, _ = power_model(row, s["hist_usage"],
                           jax.random.fold_in(key, 999), dt)
    peak = jnp.max(jax.vmap(power, 1, 1)(
        s["hist_usage"][:, -7:].reshape(n, -1)), 1)
    limit = 0.97 * jax.ops.segment_sum(peak, campus, num_segments=m)
    izeros = jnp.zeros((n,), jnp.int32)
    s = dict(s, hist_tr_pred=s["hist_resd"], hist_uif_pred=s["hist_uif"],
             streak=izeros, pause=izeros, allowed=jnp.ones((n,), bool))
    names = ("carbon_kg", "kwh", "served", "arrived", "cf_carbon_kg",
             "cf_kwh", "cf_served", "recourse_hours")
    led = {k: jnp.zeros((n,), dt) for k in names}
    hour_ch = {k: row.get(k) for k in ("arrival_hour_scale",
                                       "carbon_hour_scale")}

    def day(carry, d):
        s, led = carry
        k = jax.random.fold_in(key, s["day"])
        cap_day = cap * row["cap_scale"][d]
        power, slope = power_model(row, s["hist_usage"],
                                   jax.random.fold_in(k, 1), dt)
        fc = forecasts(s, row["gamma"])
        act, eta_fc = grid_day(zone, s["carbon"], jax.random.fold_in(k, 4),
                               row["green_scale"][d], row["coal_scale"][d],
                               dt)
        if hour_ch["carbon_hour_scale"] is not None:
            act = act * hour_ch["carbon_hour_scale"][d][None]
        tau = fc["alpha"] * fc["tuf"] + s["queue"]
        u_nom = fc["uif"] + tau[:, None] / 24
        p = {"eta": eta_fc[zmap], "u_if": fc["uif"], "u_if_q": fc["uif_q"],
             "tau": tau, "pow_nom": jax.vmap(power, 1, 1)(u_nom),
             "pi": jax.vmap(slope, 1, 1)(u_nom),
             "u_pow_cap": 0.95 * cap * row["cap_scale"][d],
             "capacity": cap_day,
             "ratio": ratio_of(fc["ra"][:, None], fc["rb"][:, None], u_nom),
             "campus": campus,
             "campus_limit": limit * row["campus_scale"][d],
             "lambda_e": row["lambda_e"], "lambda_p": row["lambda_p"],
             "drop_limit": 0.8}
        p["tau"] = spatial_shift(p, row["mobility"])
        p.pop("drop_limit")
        delta, mu, _, ok = solve_day(dict(_cast(p, solve_dt),
                                          drop_limit=0.8), solver)
        gate = s["allowed"] & ok
        arr_hour = None if hour_ch["arrival_hour_scale"] is None \
            else hour_ch["arrival_hour_scale"][d]
        u_if, arr, ratio = actual_load(truth, s["day"], k, cap_day,
                                       row["arrival_scale"][d], arr_hour,
                                       dt)
        eta = act[zmap]
        r, vcc, count = closed_loop_day(
            p, delta.astype(dt), mu.astype(dt), fc["tuf"], gate, cap_day,
            u_if, arr, ratio, s["queue"], power, eta, allowance, solver,
            mpc, solve_dt)
        cf = admit(jnp.broadcast_to(cap_day[:, None] * 10, (n, H)), u_if,
                   arr, ratio, cap_day, s["cf_queue"], power, eta,
                   allowance)
        # SLO feedback: two crowded days in a row pause shaping a week
        paused = s["pause"] > 0
        crowded = hsum(r["res"]) >= fleet["slo_margin"] * hsum(vcc)
        streak = jnp.where(paused, s["streak"],
                           jnp.where(crowded, s["streak"] + 1, 0))
        trig = ~paused & (streak >= 2)
        pause = jnp.where(trig, fleet["slo_pause_days"],
                          jnp.maximum(s["pause"] - 1, 0))
        s = dict(s, day=s["day"] + 1, streak=jnp.where(trig, 0, streak),
                 pause=pause, allowed=pause == 0,
                 hist_uif=_roll(s["hist_uif"], u_if),
                 hist_flex=_roll(s["hist_flex"], r["served"]),
                 hist_resd=_roll(s["hist_resd"], hsum(r["res"])),
                 hist_usage=_roll(s["hist_usage"], r["usage"]),
                 hist_res=_roll(s["hist_res"], r["res"]),
                 hist_tr_pred=_roll(s["hist_tr_pred"], fc["tr"]),
                 hist_uif_pred=_roll(s["hist_uif_pred"], fc["uif"]),
                 carbon=_roll(s["carbon"], act),
                 queue=r["queue_end"], cf_queue=cf["queue_end"])
        led = {"carbon_kg": led["carbon_kg"] + hsum(r["carbon"]),
               "kwh": led["kwh"] + hsum(r["power"]),
               "served": led["served"] + r["served"],
               "arrived": led["arrived"] + r["arrived"],
               "cf_carbon_kg": led["cf_carbon_kg"] + hsum(cf["carbon"]),
               "cf_kwh": led["cf_kwh"] + hsum(cf["power"]),
               "cf_served": led["cf_served"] + cf["served"],
               "recourse_hours": led["recourse_hours"] + count}
        return (s, led), None

    (s, led), _ = jax.lax.scan(day, (s, led), jnp.arange(days))
    return dict(led, queue0=queue0, queue_end=s["queue"],
                usage=s["hist_usage"][:, hd - days:])
