"""Grid simulation + day-ahead carbon-intensity forecasting (paper §III-B3).

The paper consumes hourly average carbon-intensity forecasts from Tomorrow
(electricityMap) per grid zone. Offline, we build the substrate: a
multi-zone grid simulator whose hourly average carbon intensity is driven by
a generation mix (solar/wind/hydro/nuclear/gas/coal) with diurnal structure
and AR(1) weather, plus a forecaster whose day-ahead MAPE lands in the
paper's reported 0.4%-26% band depending on zone volatility.

All series are shaped (days, 24) or (zones, days, 24); hours are UTC.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

f32 = jnp.float32

# kgCO2e / kWh by source (lifecycle-ish averages)
CI_BY_SOURCE = {
    "coal": 0.95, "gas": 0.45, "solar": 0.0, "wind": 0.0,
    "hydro": 0.0, "nuclear": 0.0,
}


@dataclass(frozen=True)
class ZoneConfig:
    """A grid zone's structural mix. Fractions are of mean demand."""
    name: str = "zone"
    solar_cap: float = 0.35        # midday solar peak as fraction of demand
    wind_cap: float = 0.25
    baseload: float = 0.30         # hydro+nuclear, carbon-free
    coal_share: float = 0.4        # of the thermal residual
    weather_vol: float = 0.2       # AR(1) innovation scale (forecastability)
    demand_amp: float = 0.15       # diurnal demand swing


ZONE_FIELDS = ("solar_cap", "wind_cap", "baseload", "coal_share",
               "weather_vol", "demand_amp")


def zone_params(zone: ZoneConfig) -> dict:
    """ZoneConfig -> dict of f32 scalars (the array-native scenario hook:
    sim scenarios perturb these before simulation)."""
    return {k: jnp.asarray(getattr(zone, k), f32) for k in ZONE_FIELDS}


def stack_zone_params(zones) -> dict:
    """Tuple of ZoneConfig -> dict of (n_zones,) arrays for vmapping."""
    return {k: jnp.asarray([getattr(z, k) for z in zones], f32)
            for k in ZONE_FIELDS}


def _diurnal(hours, peak_hour, width):
    d = jnp.minimum(jnp.abs(hours - peak_hour), 24 - jnp.abs(hours - peak_hour))
    return jnp.exp(-0.5 * (d / width) ** 2)


def simulate_zone_from(key, zp: dict, days: int) -> jnp.ndarray:
    """Hourly average carbon intensity from a zone-parameter dict (scalars
    or traced scalars). Shape (days, 24), kgCO2e/kWh."""
    hours = jnp.arange(24, dtype=f32)
    k1, k2, k3 = jax.random.split(key, 3)
    # AR(1) daily weather states for solar clearness and wind strength
    def ar1(key, n, rho=0.7, vol=1.0):
        eps = jax.random.normal(key, (n,)) * vol
        def step(x, e):
            x = rho * x + jnp.sqrt(1 - rho ** 2) * e
            return x, x
        _, xs = jax.lax.scan(step, jnp.zeros_like(eps[0]), eps)
        return xs
    clear = jax.nn.sigmoid(1.0 + ar1(k1, days, vol=zp["weather_vol"] * 5))
    windy = jax.nn.sigmoid(0.5 + ar1(k2, days, vol=zp["weather_vol"] * 6))
    demand = 1.0 + zp["demand_amp"] * (
        0.6 * _diurnal(hours, 19.0, 3.5) + 0.4 * _diurnal(hours, 9.0, 2.5))
    solar_shape = _diurnal(hours, 12.5, 2.8)
    wind_noise = 1.0 + 0.15 * jax.random.normal(k3, (days, 24))
    solar = zp["solar_cap"] * clear[:, None] * solar_shape[None, :]
    wind = zp["wind_cap"] * windy[:, None] * jnp.clip(wind_noise, 0.3, 1.7)
    green = solar + wind + zp["baseload"]
    thermal = jnp.maximum(demand[None, :] - green, 0.02)
    coal = jnp.clip(zp["coal_share"], 0.0, 1.0)
    ci_thermal = (coal * CI_BY_SOURCE["coal"]
                  + (1 - coal) * CI_BY_SOURCE["gas"])
    intensity = thermal * ci_thermal / demand[None, :]
    return intensity.astype(f32)


def simulate_zone(key, zone: ZoneConfig, days: int) -> jnp.ndarray:
    """Hourly average carbon intensity, shape (days, 24), kgCO2e/kWh."""
    return simulate_zone_from(key, zone_params(zone), days)


def simulate_zones_from(keys, zps: dict, days: int) -> jnp.ndarray:
    """Batched over zones: keys (z, 2), zps dict of (z,) -> (z, days, 24)."""
    return jax.vmap(lambda k, p: simulate_zone_from(k, p, days))(keys, zps)


def forecast_day_ahead(key, history: jnp.ndarray, actual_next: jnp.ndarray,
                       vol: float) -> jnp.ndarray:
    """Day-ahead hourly forecast for the next day.

    Blend of climatology (trailing 7-day hourly mean) and persistence
    (yesterday), plus a forecast-error term scaled by zone volatility so the
    realized MAPE spans the paper's 0.4-26% band across zones/horizons.
    history: (d, 24) past actuals; actual_next: (24,) tomorrow's truth.
    """
    clim = history[-7:].mean(axis=0)
    persist = history[-1]
    base = 0.6 * clim + 0.4 * persist
    # weather-forecast skill: forecasters see most of tomorrow's deviation
    dev = actual_next - base
    err = jax.random.normal(key, (24,)) * vol * jnp.abs(actual_next)
    return jnp.clip(base + 0.8 * dev + err, 1e-3, None).astype(f32)


def mape(forecast: jnp.ndarray, actual: jnp.ndarray) -> jnp.ndarray:
    return jnp.mean(jnp.abs(forecast - actual)
                    / jnp.clip(jnp.abs(actual), 1e-6, None))


def default_zones(n: int) -> Tuple[ZoneConfig, ...]:
    """A spread of zones from very green/volatile to coal-heavy/stable."""
    rng = np.random.RandomState(7)
    zones = []
    for i in range(n):
        zones.append(ZoneConfig(
            name=f"zone_{i}",
            solar_cap=float(rng.uniform(0.05, 0.55)),
            wind_cap=float(rng.uniform(0.05, 0.45)),
            baseload=float(rng.uniform(0.15, 0.5)),
            coal_share=float(rng.uniform(0.05, 0.8)),
            weather_vol=float(rng.uniform(0.02, 0.45)),
            demand_amp=float(rng.uniform(0.08, 0.25)),
        ))
    return tuple(zones)
