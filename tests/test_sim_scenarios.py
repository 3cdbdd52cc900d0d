"""Sim subsystem: scenario purity, ledger accounting, batched parity."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.sim import (Scenario, SimConfig, build_batch, build_params,
                       default_library, init_ledger, ledger_update,
                       make_init, make_rollout, rollout_batch,
                       rollout_batch_sharded, rollout_sequential, summarize)
from repro.launch.mesh import make_batch_mesh
from repro.sim.ledger import DayMetrics
from repro.sim.scenarios import ClusterOutage, DemandSurge, RenewableDrought

CFG = SimConfig(n_clusters=2, n_campuses=2, n_zones=2, pds_per_cluster=2,
                hist_days=14)
DAYS = 2


def test_scenario_composition_deterministic():
    """build_params is pure: same (cfg, scenario, seed, days) -> identical
    arrays, including perturbations with internal randomness."""
    sc = Scenario("combo", "drought+outage+surge",
                  (RenewableDrought(start=1, depth=0.5),
                   ClusterOutage(start=0, length=1, frac=0.5),
                   DemandSurge(start=1, scale=1.5)))
    a = build_params(CFG, sc, seed=3, days=4)
    b = build_params(CFG, sc, seed=3, days=4)
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    # a different seed must change the fleet (and the outage draw)
    c = build_params(CFG, sc, seed=4, days=4)
    assert not np.allclose(np.asarray(a.truth["capacity"]),
                           np.asarray(c.truth["capacity"]))


def test_scenario_schedules_shapes_and_effects():
    sc = Scenario("drought", "", (RenewableDrought(start=1, depth=0.7),))
    p = build_params(CFG, sc, seed=0, days=3)
    g = np.asarray(p.green_scale)
    assert g.shape == (3, CFG.n_zones)
    np.testing.assert_allclose(g[0], 1.0)
    np.testing.assert_allclose(g[1:], 0.3, rtol=1e-6)


def test_ledger_matches_hand_computed_2cluster_2day():
    """Feed a hand-written 2-cluster / 2-day rollout through the ledger and
    check every cumulative total against numpy arithmetic."""
    n = 2
    led = init_ledger(n)
    days = []
    for d in range(2):
        power = np.array([[1.0 + d, 2.0], [3.0, 4.0 + d]])    # (n, hours=2)
        intensity = np.array([[0.5, 1.0], [1.0, 0.25]])
        carbon = power * intensity
        m = DayMetrics(
            carbon_kg=jnp.asarray(carbon.sum(1), jnp.float32),
            kwh=jnp.asarray(power.sum(1), jnp.float32),
            peak_kw=jnp.asarray(power.max(1), jnp.float32),
            served=jnp.asarray([1.0, 2.0 + d], jnp.float32),
            arrived=jnp.asarray([2.0, 2.0 + d], jnp.float32),
            unmet=jnp.asarray([0.5, 0.0], jnp.float32),
            queue_end=jnp.asarray([1.0, 0.0 + d], jnp.float32),
            cf_carbon_kg=jnp.asarray(carbon.sum(1) * 1.25, jnp.float32),
            cf_kwh=jnp.asarray(power.sum(1) * 1.1, jnp.float32),
            cf_peak_kw=jnp.asarray(power.max(1) * 0.9, jnp.float32),
            cf_served=jnp.asarray([2.0, 2.0 + d], jnp.float32),
            cf_queue_end=jnp.asarray([0.0, 0.0], jnp.float32),
        )
        days.append(m)
        led = ledger_update(led, m)
    assert float(led.days) == 2.0
    np.testing.assert_allclose(
        np.asarray(led.carbon_kg),
        sum(np.asarray(m.carbon_kg) for m in days), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(led.kwh), sum(np.asarray(m.kwh) for m in days),
        rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(led.peak_kw),
        np.maximum(*[np.asarray(m.peak_kw) for m in days]), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(led.delayed_cpu_h),
        sum(np.asarray(m.queue_end) for m in days), rtol=1e-6)
    # summary math
    s = summarize(led)
    carbon = float(led.carbon_kg.sum())
    cf_carbon = float(led.cf_carbon_kg.sum())
    assert abs(float(s["carbon_saved_pct"])
               - 100.0 * (cf_carbon - carbon) / cf_carbon) < 1e-4
    # cf = shaped * 1.25 => exactly 20% saved
    assert abs(float(s["carbon_saved_pct"]) - 20.0) < 1e-3
    unmet = sum(float(np.asarray(m.unmet).sum()) for m in days)
    arrived = sum(float(np.asarray(m.arrived).sum()) for m in days)
    assert abs(float(s["flex_within_24h_pct"])
               - 100.0 * (1 - unmet / arrived)) < 1e-4


def test_flex_completion_capped_with_initial_backlog():
    """Regression: when a burned-in backlog drains during the rollout,
    served work exceeds in-horizon arrivals. Completion must be reported
    as served-of-(arrived + initial backlog) and never exceed 100%."""
    n = 2
    led = init_ledger(n)
    z = jnp.zeros((n,), jnp.float32)
    m = DayMetrics(
        carbon_kg=jnp.ones((n,)), kwh=jnp.ones((n,)),
        peak_kw=jnp.ones((n,)),
        served=jnp.asarray([15.0, 12.0]),    # > arrived: backlog drained
        arrived=jnp.asarray([10.0, 10.0]),
        unmet=z, queue_end=z,
        cf_carbon_kg=jnp.ones((n,)), cf_kwh=jnp.ones((n,)),
        cf_peak_kw=jnp.ones((n,)),
        cf_served=jnp.asarray([15.0, 12.0]), cf_queue_end=z)
    led = ledger_update(led, m)
    # without the backlog term the ratio is 27/20 -> clipped to 100
    assert float(summarize(led)["flex_completion_pct"]) == 100.0
    # with the true initial backlog (7 CPU-h) it is exactly 100
    s = summarize(led, initial_backlog=7.0)
    np.testing.assert_allclose(float(s["flex_completion_pct"]), 100.0,
                               rtol=1e-6)
    # an over-estimated backlog yields a true fraction below 100
    s = summarize(led, initial_backlog=13.0)
    np.testing.assert_allclose(float(s["flex_completion_pct"]),
                               100.0 * 27.0 / 33.0, rtol=1e-6)


def test_vmap_batch_matches_sequential_runs():
    """A vmap'd batch of 4 scenarios must reproduce 4 separate
    (non-batched, day-sequential) rollouts BITWISE — the engine's parity
    contract. The Python-loop driver of the same jitted day step agrees to
    float tolerance (standalone-vs-scan-body compilation differs in
    FMA/fusion choices, which bitwise equality cannot survive)."""
    scens = default_library(DAYS)[:4]
    batch = build_batch(CFG, scens, [0], DAYS)
    run = rollout_batch(CFG, DAYS)
    stB, ledB, trajB = run(batch)
    init = jax.jit(make_init(CFG))
    roll = jax.jit(make_rollout(CFG, DAYS))
    for i, sc in enumerate(scens):
        p = build_params(CFG, sc, 0, DAYS)
        st, led, traj = roll(p, init(p))
        for a, b in zip(jax.tree.leaves((stB, ledB, trajB)),
                        jax.tree.leaves((st, led, traj))):
            np.testing.assert_array_equal(np.asarray(a[i]), np.asarray(b),
                                          err_msg=sc.name)
    # single-element batch must also match (batch-size invariance)
    b1 = build_batch(CFG, [scens[0]], [0], DAYS)
    _, led1, _ = run(b1)
    for a, b in zip(jax.tree.leaves(led1), jax.tree.leaves(ledB)):
        np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    # python-loop sequential driver ~= scan rollout
    p = build_params(CFG, scens[0], 0, DAYS)
    st0 = init(p)
    _, led_scan, _ = roll(p, st0)
    _, led_seq = rollout_sequential(CFG, DAYS, p, st0)
    for a, b in zip(jax.tree.leaves(led_scan), jax.tree.leaves(led_seq)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_sharded_batch_matches_unsharded():
    """rollout_batch_sharded (shard_map over the 1-D device mesh) must
    reproduce rollout_batch BITWISE on a one-device mesh. Across devices
    each device compiles a program for its slice of the batch, which may
    round differently (on 4 v5e chips the fleet totals agree to 1%;
    ``chip_smoke.py --four-chips`` checks that). Also: a batch that does
    not divide across the mesh is rejected loudly."""
    scens = default_library(DAYS)[:3]
    # size the batch to divide whatever mesh the host offers
    batch = build_batch(CFG, scens, list(range(len(jax.devices()))), DAYS)
    _, led, traj = rollout_batch(CFG, DAYS)(batch)
    _, led_s, traj_s = rollout_batch_sharded(
        CFG, DAYS, make_batch_mesh(1))(batch)
    for a, b in zip(jax.tree.leaves((led, traj)),
                    jax.tree.leaves((led_s, traj_s))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    n_dev = len(jax.devices())
    if n_dev > 1:                              # pragma: no cover
        bad = build_batch(CFG, scens[:1], list(range(n_dev + 1)), DAYS)
        with pytest.raises(ValueError, match="divide"):
            rollout_batch_sharded(CFG, DAYS)(bad)


SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# the sharded rollout over 4 forced host devices; a subprocess keeps the
# forced device count out of the other tests
SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np
from repro.sim import (SimConfig, build_batch, default_library,
                       rollout_batch, rollout_batch_sharded)
assert len(jax.devices()) == 4, jax.devices()
cfg = SimConfig(n_clusters=2, n_campuses=2, n_zones=2, pds_per_cluster=2,
                hist_days=14)
days = 2
scens = default_library(days)[:3]
batch = build_batch(cfg, scens, [0, 1, 2, 3], days)
run = rollout_batch(cfg, days)
full = run(batch)
sharded = rollout_batch_sharded(cfg, days)(batch)
# each device's slice run alone on one device equals its rows of the
# whole batch bitwise: the batch extent does not change the numerics
per = [run(jax.tree.map(lambda a: a[i:i + 3], batch)) for i in range(0, 12, 3)]
for a, *ps in zip(jax.tree.leaves(full), *map(jax.tree.leaves, per)):
    np.testing.assert_array_equal(
        np.asarray(a), np.concatenate([np.asarray(p) for p in ps]))
for leaf in jax.tree.leaves(sharded):
    assert np.isfinite(np.asarray(leaf, np.float64)).all()
gaps = {}
for field in ("carbon_kg", "kwh", "served", "cf_carbon_kg"):
    u = np.asarray(getattr(full[1], field), np.float64).sum(axis=-1)
    v = np.asarray(getattr(sharded[1], field), np.float64).sum(axis=-1)
    gaps[field] = float((np.abs(v - u) / np.abs(u)).max())
    assert gaps[field] <= 1e-2, (field, gaps[field])
try:
    rollout_batch_sharded(cfg, days)(
        build_batch(cfg, scens[:1], [0, 1, 2, 3, 4], days))
except ValueError as e:
    assert "divide" in str(e), e
else:
    raise AssertionError("a batch of 5 over 4 devices was not refused")
print("SHARDED_OK", gaps)
"""


def test_sharded_four_devices_keep_fleet_totals():
    """Across devices the contract is each rollout's fleet totals (carbon,
    kWh, served work, counterfactual carbon) within 1% of the unsharded
    batch, the README's parity contract: each device compiles the
    shard_map body as its own program, and XLA may round it differently
    (on CPU here: up to 3.0e-6 in fleet totals at this config; bitwise at
    8 clusters). Run over 4 forced host devices, it also checks that a
    device's slice run alone equals its rows of the whole batch bitwise,
    and that a batch the mesh does not divide is refused."""
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", SHARDED_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "SHARDED_OK" in r.stdout


def test_counterfactual_serves_no_less():
    """The unshaped counterfactual admits flexible work at least as fast
    as the shaped run (VCC only ever restricts admission)."""
    p = build_params(CFG, default_library(DAYS)[0], 0, DAYS)
    init = jax.jit(make_init(CFG))
    roll = jax.jit(make_rollout(CFG, DAYS))
    _, led, _ = roll(p, init(p))
    assert float(led.cf_delayed_cpu_h.sum()) <= \
        float(led.delayed_cpu_h.sum()) + 1e-3
