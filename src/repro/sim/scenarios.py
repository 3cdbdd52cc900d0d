"""Declarative scenario perturbations composable onto the synthetic fleet.

A Scenario = a name + scalar overrides (carbon price, risk, mobility) + a
tuple of Perturbation objects, each of which edits the multiplier
*schedules* (numpy arrays, one row per rollout day) that the engine
consumes. Composition is pure: `build_params(cfg, scenario, seed, days)`
always returns the identical SimParams pytree for identical inputs —
per-scenario randomness (e.g. which clusters an outage hits) is drawn from
a generator keyed on (seed, crc32(scenario.name)).

Scenario axes follow the related literature: renewable droughts and grid
mix shifts ("Let's Wait Awhile"), price/risk sweeps ("The War of the
Efficiencies"), plus operational events (outages, campus derates, demand
surges) from the paper's production narrative.
"""
from __future__ import annotations

import zlib
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import stages
from repro.sim.engine import SimConfig, SimParams

f32 = jnp.float32


# ------------------------------------------------------------ perturbations

@dataclass(frozen=True)
class Perturbation:
    """Base: edits the schedule dict in place. start/length in rollout
    days; length < 0 means 'until the end of the horizon'."""
    start: int = 0
    length: int = -1

    def window(self, days: int) -> slice:
        end = days if self.length < 0 else min(self.start + self.length,
                                               days)
        return slice(min(self.start, days), end)

    def apply(self, sched: Dict[str, np.ndarray], rng: np.random.Generator,
              cfg: SimConfig) -> None:
        raise NotImplementedError


@dataclass(frozen=True)
class RenewableDrought(Perturbation):
    """Dunkelflaute: solar+wind capacity drops by `depth` in some zones."""
    depth: float = 0.7
    zones: Optional[Tuple[int, ...]] = None      # None = all zones

    def apply(self, sched, rng, cfg):
        w = self.window(sched["green_scale"].shape[0])
        zs = list(self.zones) if self.zones is not None \
            else list(range(cfg.n_zones))
        sched["green_scale"][w, zs] *= (1.0 - self.depth)


@dataclass(frozen=True)
class CoalRetirement(Perturbation):
    """Linear ramp-down of the thermal coal share, `rate` per week."""
    rate_per_week: float = 0.05

    def apply(self, sched, rng, cfg):
        days = sched["coal_scale"].shape[0]
        w = self.window(days)
        t = np.arange(w.stop - w.start, dtype=np.float64)
        ramp = np.clip(1.0 - self.rate_per_week * t / 7.0, 0.0, None)
        sched["coal_scale"][w] *= ramp[:, None]


@dataclass(frozen=True)
class ClusterOutage(Perturbation):
    """A fraction of clusters loses most capacity for a window."""
    frac: float = 0.25
    derate: float = 0.1          # remaining capacity fraction

    def apply(self, sched, rng, cfg):
        w = self.window(sched["cap_scale"].shape[0])
        k = max(1, int(round(self.frac * cfg.n_clusters)))
        hit = np.sort(rng.choice(cfg.n_clusters, size=k, replace=False))
        sched["cap_scale"][w, hit] *= self.derate


@dataclass(frozen=True)
class CampusDerate(Perturbation):
    """Contracted campus power limit drops (grid event / demand response)."""
    scale: float = 0.85
    campuses: Optional[Tuple[int, ...]] = None

    def apply(self, sched, rng, cfg):
        w = self.window(sched["campus_scale"].shape[0])
        cs = list(self.campuses) if self.campuses is not None \
            else list(range(cfg.n_campuses))
        sched["campus_scale"][w, cs] *= self.scale


@dataclass(frozen=True)
class DemandSurge(Perturbation):
    """Flexible-demand arrivals scale up fleetwide for a window."""
    scale: float = 1.5

    def apply(self, sched, rng, cfg):
        w = self.window(sched["arrival_scale"].shape[0])
        sched["arrival_scale"][w] *= self.scale


@dataclass(frozen=True)
class CapacitySqueeze(Perturbation):
    """Fleetwide machine-capacity derate (tight-supply regime: temporal
    shaping bounds bind, so spatially exporting work matters)."""
    scale: float = 0.75

    def apply(self, sched, rng, cfg):
        w = self.window(sched["cap_scale"].shape[0])
        sched["cap_scale"][w] *= self.scale


def _hour_channel(sched: Dict[str, np.ndarray], key: str,
                  days: int) -> np.ndarray:
    """Lazily materialize an intraday (days, 24) multiplier channel. Kept
    out of the base schedule so scenarios without intraday perturbations
    build SimParams with the channel leaves = None (byte-identical
    compiled day-step graph — stages.SimParams)."""
    if key not in sched:
        sched[key] = np.ones((days, 24))
    return sched[key]


@dataclass(frozen=True)
class IntradayCarbonSpike(Perturbation):
    """Forecast-busting intra-day carbon spike: the ACTUAL zone intensity
    is scaled by ``scale`` for a contiguous ``hour_len``-hour block each
    day of the window, applied after the day-ahead forecast is drawn — the
    planner never sees it coming. ``hour_start=None`` randomizes the block
    per day (scenario rng), so the persistence-based carbon forecaster
    cannot lock onto a recurring pattern across days."""
    scale: float = 1.8
    hour_len: int = 8
    hour_start: Optional[int] = None

    def apply(self, sched, rng, cfg):
        days = sched["cap_scale"].shape[0]
        ch = _hour_channel(sched, "carbon_hour_scale", days)
        w = self.window(days)
        for d in range(w.start, w.stop):
            h0 = self.hour_start if self.hour_start is not None \
                else int(rng.integers(5, 24 - self.hour_len))
            ch[d, h0:min(h0 + self.hour_len, 24)] *= self.scale


@dataclass(frozen=True)
class IntradayDemandSurge(Perturbation):
    """Forecast-busting intra-day arrival surge: ACTUAL flexible arrivals
    scale by ``scale`` for a ``hour_len``-hour block each day of the
    window (random block per day when ``hour_start=None``). The load
    forecasters saw none of it when the day's tau was budgeted."""
    scale: float = 1.7
    hour_len: int = 6
    hour_start: Optional[int] = None

    def apply(self, sched, rng, cfg):
        days = sched["cap_scale"].shape[0]
        ch = _hour_channel(sched, "arrival_hour_scale", days)
        w = self.window(days)
        for d in range(w.start, w.stop):
            h0 = self.hour_start if self.hour_start is not None \
                else int(rng.integers(5, 24 - self.hour_len))
            ch[d, h0:min(h0 + self.hour_len, 24)] *= self.scale


# ----------------------------------------------------------------- scenario

@dataclass(frozen=True)
class Scenario:
    name: str
    description: str = ""
    perturbations: Tuple[Perturbation, ...] = ()
    lambda_e: float = 0.5        # carbon price (paper-style sweep axis)
    lambda_p: float = 0.05
    gamma: float = 0.05          # power-capping violation probability
    mobility: float = 0.0        # spatial-shift mobility (0 = paper mode)
    risk_beta: float = 1.0       # CVaR tail fraction (1.0 = risk-neutral;
    #                              only acts when SimConfig.n_members > 1)


def _scenario_rng(scenario: Scenario, seed: int) -> np.random.Generator:
    tag = zlib.crc32(scenario.name.encode("utf-8"))
    return np.random.default_rng((int(seed) << 32) ^ tag)


def build_params(cfg: SimConfig, scenario: Scenario, seed: int, days: int
                 ) -> SimParams:
    """Compose a scenario onto the synthetic fleet -> array-only SimParams.

    Pure: identical (cfg, scenario, seed, days) -> identical arrays.
    """
    n, m, z = cfg.n_clusters, cfg.n_campuses, cfg.n_zones
    # the same synthesis leaves the legacy fleet path uses (stage core)
    sp = stages.synth_params(seed, n, cfg.pds_per_cluster, z)

    sched = {
        "green_scale": np.ones((days, z)),
        "coal_scale": np.ones((days, z)),
        "cap_scale": np.ones((days, n)),
        "arrival_scale": np.ones((days, n)),
        "campus_scale": np.ones((days, m)),
    }
    rng = _scenario_rng(scenario, seed)
    for p in scenario.perturbations:
        p.apply(sched, rng, cfg)

    return SimParams(
        key=sp["key"],
        truth=sp["truth"], pd_idle=sp["pd_idle"], pd_slope=sp["pd_slope"],
        pd_curve=sp["pd_curve"], lam=sp["lam"], zone=sp["zone"],
        lambda_e=jnp.asarray(scenario.lambda_e, f32),
        lambda_p=jnp.asarray(scenario.lambda_p, f32),
        gamma=jnp.asarray(scenario.gamma, f32),
        mobility=jnp.asarray(scenario.mobility, f32),
        risk_beta=jnp.asarray(scenario.risk_beta, f32),
        green_scale=jnp.asarray(sched["green_scale"], f32),
        coal_scale=jnp.asarray(sched["coal_scale"], f32),
        cap_scale=jnp.asarray(sched["cap_scale"], f32),
        arrival_scale=jnp.asarray(sched["arrival_scale"], f32),
        campus_scale=jnp.asarray(sched["campus_scale"], f32),
        arrival_hour_scale=(
            jnp.asarray(sched["arrival_hour_scale"], f32)
            if "arrival_hour_scale" in sched else None),
        carbon_hour_scale=(
            jnp.asarray(sched["carbon_hour_scale"], f32)
            if "carbon_hour_scale" in sched else None),
    )


def build_batch(cfg: SimConfig, scenarios: Sequence[Scenario],
                seeds: Sequence[int], days: int) -> SimParams:
    """Stack (scenario x seed) SimParams along a new leading axis, scenario
    major: batch index b = i_scenario * len(seeds) + i_seed.

    Stacking needs a homogeneous pytree: if ANY rollout carries an
    intraday hour channel, the rollouts without it get the neutral
    all-ones channel (multiplying actuals by exactly 1.0 — identical
    results; an all-None column stays None and the batch keeps the
    channel-free graph)."""
    all_params = [build_params(cfg, sc, seed, days)
                  for sc in scenarios for seed in seeds]
    ones = jnp.ones((days, 24), f32)
    for field in ("arrival_hour_scale", "carbon_hour_scale"):
        if any(getattr(p, field) is not None for p in all_params):
            all_params = [
                p._replace(**{field: ones}) if getattr(p, field) is None
                else p for p in all_params]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *all_params)


# ------------------------------------------------------------------ library

def default_library(days: int = 14) -> List[Scenario]:
    """The standing scenario sweep (>= 8 scenarios)."""
    half = max(days // 2, 1)
    return [
        Scenario("baseline",
                 "nominal grid, nominal fleet"),
        Scenario("renewable_drought",
                 "70% solar+wind drop across all zones, second half",
                 (RenewableDrought(start=half, depth=0.7),)),
        Scenario("coal_retirement",
                 "coal share ramps down 10%/week from day 0",
                 (CoalRetirement(rate_per_week=0.10),)),
        Scenario("cluster_outage",
                 "25% of clusters derated to 10% capacity mid-horizon",
                 (ClusterOutage(start=half, length=max(days // 4, 1),
                                frac=0.25),)),
        Scenario("campus_derate",
                 "all campus power contracts cut 15%",
                 (CampusDerate(scale=0.85),)),
        Scenario("demand_surge",
                 "flexible arrivals x1.6 in the second half",
                 (DemandSurge(start=half, scale=1.6),)),
        Scenario("high_carbon_price",
                 "lambda_e x4: aggressive shaping",
                 lambda_e=2.0),
        Scenario("low_risk_tolerance",
                 "gamma 0.01: conservative power capping",
                 gamma=0.01),
        Scenario("spatial_mobility",
                 "30% of flexible work location-flexible (beyond-paper)",
                 mobility=0.3),
        Scenario("peak_shaver",
                 "peak-power-optimal pricing (lambda_p >> lambda_e): the "
                 "'War of the Efficiencies' counterpoint",
                 lambda_e=0.02, lambda_p=0.5),
        Scenario("perfect_storm",
                 "drought + outage + surge, compounded",
                 (RenewableDrought(start=half, depth=0.6),
                  ClusterOutage(start=half, length=max(days // 4, 1),
                                frac=0.2),
                  DemandSurge(start=half, scale=1.4))),
    ]


MOBILITY_SWEEP = (0.0, 0.1, 0.3, 0.6)


def mobility_sweep_library(days: int = 14,
                           mobilities: Sequence[float] = MOBILITY_SWEEP
                           ) -> List[Scenario]:
    """The spatial-mobility sweep family (joint spatio-temporal path).

    Mobility is swept as a data leaf (one batched rollout) under a
    geographically skewed, supply-tight grid: a deep renewable drought
    pinned to zone 0 for the whole horizon, a fleetwide demand surge, and
    a capacity squeeze — so the dirty zone's clusters saturate their
    shaping bounds and EXPORTING work (not just delaying it) is what
    saves carbon; this is the regime where the joint optimizer can beat
    the greedy pre-shift. mobility=0 is the temporal-only control row
    (the shift is pinned to zero; the joint path may still refine delta,
    so its realized rollouts match the sequential path only to float
    tolerance). Run with ``SimConfig(joint_spatial=True)`` and compare
    against the same batch under ``joint_spatial=False`` for the
    joint-vs-sequential carbon delta (``report.mobility_sweep_rows``;
    tests/test_joint.py holds every row to >= -0.5%).
    """
    return [
        Scenario(f"mobility{int(round(100 * m)):03d}",
                 f"{m:.0%} of flexible work location-flexible under a "
                 "zone-0 drought + surge + capacity squeeze",
                 (RenewableDrought(depth=0.8, zones=(0,)),
                  DemandSurge(scale=1.3),
                  CapacitySqueeze(scale=0.75)),
                 lambda_e=1.0, lambda_p=0.02, mobility=m)
        for m in mobilities
    ]


def forecast_bust_library(days: int = 6) -> List[Scenario]:
    """Forecast-busting scenarios for the intra-day MPC recourse gate
    (``SimConfig.mpc``): the day-ahead plan is issued against clean
    forecasts, then the ACTUAL intensity / arrivals are hit by
    randomly-placed intra-day blocks the planner never saw. These are the
    rows where the closed loop must beat (or match) the open loop on
    carbon or within-24h flex service — ``report.mpc_recourse_rows``
    rows, each checked by ``test_mpc_recourse_beats_open_loop``."""
    return [
        Scenario("intraday_carbon_spike",
                 "unforecasted x1.8 intensity block, 8h/day, random hours",
                 (IntradayCarbonSpike(scale=1.8, hour_len=8),),
                 lambda_e=1.0),
        Scenario("intraday_demand_surge",
                 "unforecasted x1.7 arrival block, 6h/day, random hours",
                 (IntradayDemandSurge(scale=1.7, hour_len=6),),
                 lambda_e=1.0),
        Scenario("intraday_perfect_storm",
                 "carbon spike + arrival surge, independently placed",
                 (IntradayCarbonSpike(scale=1.6, hour_len=8),
                  IntradayDemandSurge(scale=1.5, hour_len=6)),
                 lambda_e=1.0),
    ]


RISK_BETAS = (0.5, 0.9, 0.99)
RISK_MEMBERS = (1, 8, 32)


def risk_sweep_library(days: int = 14,
                       betas: Sequence[float] = RISK_BETAS
                       ) -> List[Scenario]:
    """The risk-sweep scenario family: CVaR tail fraction beta swept under
    a forecast-hostile backdrop (drought + demand surge — the regimes
    'Let's Wait Awhile' shows are most forecast-error sensitive).

    beta is a data leaf, so the whole sweep batches in ONE rollout; the
    ensemble size K is a static shape, so pair this library with
    ``SimConfig(n_members=K)`` for each K in ``RISK_MEMBERS`` (K=1 makes
    every beta collapse to the identical point-forecast path — the
    degenerate control row).
    """
    half = max(days // 2, 1)
    backdrop = (RenewableDrought(start=half, depth=0.6),
                DemandSurge(start=half, scale=1.4))
    return [
        Scenario(f"risk_beta{int(round(100 * b)):02d}",
                 f"CVaR beta={b}: optimize the worst {b:.0%} of forecast "
                 "members under drought + surge",
                 backdrop, lambda_e=1.0, risk_beta=b)
        for b in betas
    ]
