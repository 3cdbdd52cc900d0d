"""Traffic generator of the chip benchmark: fleets and scenario schedules
from a seed, driven by a traffic file's parameters.

The benchmark keeps its own copy of what the program's libraries use to
make their inputs, so that a later change to those libraries cannot move
a cell:

* the synthetic fleet (latent per-cluster load processes, PD power
  curves, PD usage fractions, grid-zone mixes), as in
  ``repro.core.stages.synth_params`` and ``repro.core.carbon.default_zones``;
* the scenario perturbations of ``repro.sim.scenarios`` (renewable
  drought, coal retirement, cluster outage, campus derate, demand surge,
  capacity squeeze, intraday carbon spike and demand surge), named by
  ``kind`` in the traffic file;
* the day-ahead VCC problem recipe of ``repro.core.vcc.synthetic_problem``,
  with campus contracts set from the problem's own nominal peaks.

Everything random is drawn on the device in one jitted call per batch,
or on the host from numpy generators keyed on the seed. Nothing here
imports the program.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

f32 = jnp.float32
ZONE_FIELDS = ("solar_cap", "wind_cap", "baseload", "coal_share",
               "weather_vol", "demand_amp")
ZONE_RANGES = ((0.05, 0.55), (0.05, 0.45), (0.15, 0.5), (0.05, 0.8),
               (0.02, 0.45), (0.08, 0.25))


def sub_seeds(seed: int, tag: int, count: int) -> list:
    """``count`` 31-bit seeds derived from a run seed of any size."""
    ss = np.random.SeedSequence([int(seed) % 2**63, int(tag)])
    return [int(s) for s in ss.generate_state(count, np.uint32) >> 1]


# ------------------------------------------------------------ the fleet

def zone_table(n_zones: int) -> dict:
    """Grid-zone mixes, from very green and volatile to coal-heavy and
    stable: a fixed spread, the same for every seed."""
    rng = np.random.RandomState(7)
    rows = [[float(rng.uniform(lo, hi)) for lo, hi in ZONE_RANGES]
            for _ in range(n_zones)]
    return {k: np.asarray([r[i] for r in rows], np.float32)
            for i, k in enumerate(ZONE_FIELDS)}


def cluster_truth(key, n: int) -> dict:
    """Latent per-cluster load processes."""
    ks = jax.random.split(key, 10)
    u = [jax.random.uniform(k, (n,)) for k in ks[1:]]
    capacity = jnp.exp(jax.random.normal(ks[0], (n,)) * 0.4 + 2.3)
    flex_share = jnp.clip(0.08 + 0.5 * u[0], 0.05, 0.6)
    return {"capacity": capacity,
            "flex_share": flex_share,
            "base_if": capacity * (0.35 + 0.2 * u[1]),
            "diurnal_amp": 0.15 + 0.2 * u[2],
            "peak_hour": 8.0 + 10.0 * u[3],
            "weekly_amp": 0.05 + 0.1 * u[4],
            "noise": 0.02 + 0.06 * u[5],
            "arr_level": capacity * flex_share * (0.5 + 0.4 * u[6]),
            "ratio_a": 1.15 + 0.3 * u[7],
            "ratio_b": -0.05 - 0.08 * u[8]}


def _fleet(seed, n: int, npds: int) -> dict:
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 8)
    npd = n * npds
    return {"key": jax.random.fold_in(key, 17),
            "truth": cluster_truth(ks[0], n),
            "pd_idle": 60.0 + 40.0 * jax.random.uniform(ks[1], (npd,)),
            "pd_slope": 250.0 + 150.0 * jax.random.uniform(ks[2], (npd,)),
            "pd_curve": 0.8 + 0.5 * jax.random.uniform(ks[3], (npd,)),
            "lam": jax.nn.softmax(jax.random.normal(ks[4], (n, npds)),
                                  axis=1)}


# ------------------------------------------------------ the perturbations

def _window(p: dict, days: int) -> slice:
    start, length = p.get("start", 0), p.get("length", -1)
    end = days if length < 0 else min(start + length, days)
    return slice(min(start, days), end)


def _hour_block(p, sched, key, days, rng):
    ch = sched.setdefault(key, np.ones((days, 24)))
    w = _window(p, days)
    for d in range(w.start, w.stop):
        h0 = p.get("hour_start")
        if h0 is None:
            h0 = int(rng.integers(5, 24 - p["hour_len"]))
        ch[d, h0:min(h0 + p["hour_len"], 24)] *= p["scale"]


def apply_perturbation(p: dict, sched: dict, rng, fleet: dict) -> None:
    """Edit the multiplier schedules in place, as ``p['kind']`` says."""
    kind = p["kind"]
    days = sched["cap_scale"].shape[0]
    w = _window(p, days)
    if kind == "RenewableDrought":
        zs = p.get("zones") or list(range(fleet["n_zones"]))
        sched["green_scale"][w, zs] *= 1.0 - p["depth"]
    elif kind == "CoalRetirement":
        t = np.arange(w.stop - w.start, dtype=np.float64)
        ramp = np.clip(1.0 - p["rate_per_week"] * t / 7.0, 0.0, None)
        sched["coal_scale"][w] *= ramp[:, None]
    elif kind == "ClusterOutage":
        n = fleet["n_clusters"]
        k = max(1, int(round(p["frac"] * n)))
        hit = np.sort(rng.choice(n, size=k, replace=False))
        sched["cap_scale"][w, hit] *= p["derate"]
    elif kind == "CampusDerate":
        cs = p.get("campuses") or list(range(fleet["n_campuses"]))
        sched["campus_scale"][w, cs] *= p["scale"]
    elif kind == "DemandSurge":
        sched["arrival_scale"][w] *= p["scale"]
    elif kind == "CapacitySqueeze":
        sched["cap_scale"][w] *= p["scale"]
    elif kind == "IntradayCarbonSpike":
        _hour_block(p, sched, "carbon_hour_scale", days, rng)
    elif kind == "IntradayDemandSurge":
        _hour_block(p, sched, "arrival_hour_scale", days, rng)
    else:
        raise ValueError(f"unknown perturbation kind {kind!r}")


SCENARIO_SCALARS = {"lambda_e": 0.5, "lambda_p": 0.05, "gamma": 0.05,
                    "mobility": 0.0, "risk_beta": 1.0}


def schedules(fleet: dict, scenario: dict, seed: int, days: int) -> dict:
    """One rollout's multiplier schedules (numpy, one row per day)."""
    n, m, z = fleet["n_clusters"], fleet["n_campuses"], fleet["n_zones"]
    sched = {"green_scale": np.ones((days, z)),
             "coal_scale": np.ones((days, z)),
             "cap_scale": np.ones((days, n)),
             "arrival_scale": np.ones((days, n)),
             "campus_scale": np.ones((days, m))}
    tag = zlib.crc32(scenario["name"].encode("utf-8"))
    rng = np.random.default_rng((int(seed) << 32) ^ tag)
    for p in scenario.get("perturbations", ()):
        apply_perturbation(p, sched, rng, fleet)
    return sched


def rollout_batch(fleet: dict, scenarios: list, seeds: list, days: int
                  ) -> dict:
    """The (scenario x seed) batch as a dict of stacked device arrays,
    scenario major: row b = i_scenario * len(seeds) + i_seed. Every
    rollout carries the intraday hour channels if any of them does
    (the others get all-ones)."""
    n, npds = fleet["n_clusters"], fleet["pds_per_cluster"]
    rows = [(sc, s) for sc in scenarios for s in seeds]
    scheds = [schedules(fleet, sc, s, days) for sc, s in rows]
    keys = set().union(*scheds)
    for k in ("arrival_hour_scale", "carbon_hour_scale"):
        if k in keys:
            for sd in scheds:
                sd.setdefault(k, np.ones((days, 24)))
    synth = jax.jit(jax.vmap(lambda s: _fleet(s, n, npds)))
    out = synth(jnp.asarray([s for _, s in rows], jnp.int32))
    zones = zone_table(fleet["n_zones"])
    b = len(rows)
    out["zone"] = {k: jnp.asarray(np.broadcast_to(v, (b,) + v.shape))
                   for k, v in zones.items()}
    for k, v in SCENARIO_SCALARS.items():
        out[k] = jnp.asarray([sc.get(k, v) for sc, _ in rows], f32)
    for k in sorted(scheds[0]):
        out[k] = jnp.asarray(np.stack([sd[k] for sd in scheds]), f32)
    return out


# ------------------------------------------------- the day-ahead problem

def vcc_problem(seed: int, n: int, n_campuses: int, recipe: dict) -> dict:
    """A fleetwide day-ahead VCC problem: a diurnal intensity curve and
    noisy inflexible load (the recipe of ``vcc.synthetic_problem``), with
    each campus contracted to ``recipe['campus_limit_frac']`` of its
    clusters' summed nominal peaks (None leaves the contracts
    uncontended)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    H = 24
    eta = jnp.abs(0.3 + 0.25 * jnp.sin(jnp.linspace(0, 2 * jnp.pi, H))[None]
                  + 0.05 * jax.random.normal(ks[0], (n, H)))
    u_if = 0.4 + 0.05 * jax.random.normal(ks[1], (n, H))
    tau = 2.0 + 3.0 * jax.random.uniform(ks[2], (n,))
    pow_nom = 500.0 + 20.0 * jax.random.normal(ks[3], (n, H))
    campus = np.arange(n) % n_campuses
    frac = recipe.get("campus_limit_frac")
    if frac is None:
        limit = jnp.full((n_campuses,), 1e9, f32)
    else:
        limit = frac * jax.ops.segment_sum(pow_nom.max(axis=1),
                                           jnp.asarray(campus),
                                           num_segments=n_campuses)
    return {"eta": eta, "u_if": u_if, "u_if_q": u_if * 1.1, "tau": tau,
            "pow_nom": pow_nom, "pi": jnp.full((n, H), 300.0, f32),
            "u_pow_cap": jnp.full((n,), 0.95, f32),
            "capacity": jnp.full((n,), 1.3, f32),
            "ratio": jnp.full((n, H), 1.3, f32),
            "campus": jnp.asarray(campus, jnp.int32),
            "campus_limit": limit.astype(f32),
            "lambda_e": jnp.asarray(recipe.get("lambda_e", 0.1), f32),
            "lambda_p": jnp.asarray(recipe.get("lambda_p", 0.05), f32)}
