"""Fleet telemetry layer tests (ISSUE 6 tentpole + satellites).

Four contracts:

* **Collapse** — ``StageConfig.telemetry=False`` (the default) compiles
  the day step to HLO byte-identical to the graph traced with the
  verbatim pre-telemetry ``solver.dual_ascent`` (so the golden trace and
  every parity test keep pinning the same executable), and the default
  ``StageConfig()`` equals an explicit ``telemetry=False``.
* **Parity** — batched telemetry == per-rollout sequential telemetry
  BITWISE (the DayTelemetry record rides the same batch-invariant
  numerics contract as the ledger; mirrors tests/test_stages_parity.py).
* **Export** — solve_vcc telemetry channels are sane, trace records
  round-trip through JSONL, ``report.telemetry_rows`` aggregates them,
  and ``report.scenario_rows`` uses the sample std (ddof=1; n=1 pins
  0.0, never NaN).

The hypothesis property tests for the calibration metric primitives
(coverage in [0, 1], MAPE >= 0, zero-error forecast => zero bias) live
in tests/test_telemetry_properties.py — a module-level importorskip
would otherwise skip THIS whole file where hypothesis is absent.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import solver, stages, vcc
from repro.sim import (SimConfig, build_batch, build_params,
                       default_library, init_ledger, ledger_update,
                       make_init, make_rollout, rollout_batch,
                       scenario_rows, telemetry_records, telemetry_rows,
                       write_jsonl, read_jsonl, DayTelemetry,
                       TELEMETRY_COLUMNS, TRACE_FIELDS, format_table)
from repro.sim.engine import _day_xs
from repro.sim.ledger import DayMetrics

CFG_KW = dict(n_clusters=4, n_campuses=2, n_zones=2, pds_per_cluster=2,
              hist_days=14)
DAYS = 2

f32 = jnp.float32


def _legacy_dual_ascent(inner, dual_update, x0, mu0, outer_iters):
    """Verbatim pre-telemetry ``solver.dual_ascent`` — the reference the
    collapse contract is certified against."""
    def outer(carry, _):
        x, mu = carry
        x = inner(x, mu)
        mu = dual_update(x, mu)
        return (x, mu), None

    (x, mu), _ = jax.lax.scan(outer, (x0, mu0), None, length=outer_iters)
    return x, mu


# ------------------------------------------------------- collapse contract

def test_default_stage_config_is_telemetry_off():
    assert stages.StageConfig().telemetry is False
    assert stages.StageConfig() == stages.StageConfig(telemetry=False)
    assert stages.StageConfig() != stages.StageConfig(telemetry=True)


def test_telemetry_off_day_step_hlo_byte_identical_to_legacy():
    """The telemetry=False day step must compile to EXACTLY the HLO of
    the graph traced with the pre-telemetry two-value dual-ascent scan —
    byte-equal text, not just numerics (the repo's collapse contract)."""
    cfg = SimConfig(**CFG_KW)
    sc = default_library(DAYS)[0]
    p = build_params(cfg, sc, 0, DAYS)
    s = jax.jit(make_init(cfg))(p)
    xs = _day_xs(p, 0)
    scfg = cfg.stage_config()
    step = jax.jit(stages.make_day_step(scfg))
    hlo_now = step.lower(p, s, xs).as_text()
    orig = solver.dual_ascent
    solver.dual_ascent = _legacy_dual_ascent
    try:
        hlo_legacy = jax.jit(stages.make_day_step(scfg)).lower(
            p, s, xs).as_text()
    finally:
        solver.dual_ascent = orig
    assert hlo_now == hlo_legacy


def test_solve_vcc_telemetry_off_hlo_identical():
    """Same contract one layer down: solve_vcc(telemetry=False) compiles
    byte-identical to the legacy solver graph."""
    p = vcc.synthetic_problem(6, seed=2)
    f = jax.jit(lambda q: vcc.solve_vcc(q, use_pallas=False))
    hlo_now = f.lower(p).as_text()
    orig = solver.dual_ascent
    solver.dual_ascent = _legacy_dual_ascent
    try:
        hlo_legacy = jax.jit(
            lambda q: vcc.solve_vcc(q, use_pallas=False)).lower(p).as_text()
    finally:
        solver.dual_ascent = orig
    assert hlo_now == hlo_legacy


def test_telemetry_off_traj_keys_unchanged():
    """telemetry=False must not grow the rollout traj (golden-trace key
    set); telemetry=True stacks DayTelemetry leaves under 'telemetry'."""
    cfg = SimConfig(**CFG_KW)
    sc = default_library(DAYS)[:1]
    batch = build_batch(cfg, sc, [0], DAYS)
    _, _, traj = rollout_batch(cfg, DAYS)(batch)
    assert "telemetry" not in traj
    cfg_on = SimConfig(**CFG_KW, telemetry=True)
    _, _, traj_on = rollout_batch(cfg_on, DAYS)(batch)
    tel = traj_on["telemetry"]
    assert isinstance(tel, DayTelemetry)
    assert tel.uif_mape.shape == (1, DAYS, CFG_KW["n_clusters"])


# ----------------------------------------------------------- bitwise parity

def test_batched_telemetry_matches_sequential_bitwise():
    """A vmap'd batch's DayTelemetry must reproduce each scenario's
    non-batched sequential rollout telemetry BITWISE — same contract,
    same idiom as tests/test_stages_parity.py for the ledger.

    One channel is held to one float32 ulp instead: ``obj_cluster_traj``
    (``vcc.cluster_objective`` per outer round) is a sum of products,
    which XLA's CPU backend fuses into multiply-adds in one program and
    not in the other; an ``optimization_barrier`` does not stop that
    fusion. Measured on jax 0.9 CPU: 1 ulp (6.5e-8 relative)."""
    cfg = SimConfig(**CFG_KW, telemetry=True)
    scens = default_library(DAYS)[:3]
    batch = build_batch(cfg, scens, [0], DAYS)
    _, _, trajB = rollout_batch(cfg, DAYS)(batch)
    init = jax.jit(make_init(cfg))
    roll = jax.jit(make_rollout(cfg, DAYS))
    for i, sc in enumerate(scens):
        p = build_params(cfg, sc, 0, DAYS)
        _, _, traj = roll(p, init(p))
        for name in DayTelemetry._fields:
            a = np.asarray(getattr(trajB["telemetry"], name)[i])
            b = np.asarray(getattr(traj["telemetry"], name))
            if name == "obj_cluster_traj":
                np.testing.assert_allclose(a, b, rtol=2.0 ** -23, atol=0,
                                           err_msg=f"{sc.name} {name}")
            else:
                np.testing.assert_array_equal(a, b,
                                              err_msg=f"{sc.name} {name}")


# ----------------------------------------------------- solver-channel sanity

def test_solve_vcc_telemetry_channels():
    """telemetry=True returns (sol, diag) with converging trajectories
    and near-zero residuals; the solution itself is bitwise the
    telemetry=False solution (the diagnostics only OBSERVE the scan)."""
    p = vcc.synthetic_problem(8, seed=5)
    sol0 = vcc.solve_vcc(p, use_pallas=False)
    sol, diag = vcc.solve_vcc(p, use_pallas=False, telemetry=True)
    np.testing.assert_array_equal(np.asarray(sol.delta),
                                  np.asarray(sol0.delta))
    n = p.tau.shape[0]
    assert diag["obj_cluster_traj"].shape == (20, n)
    assert diag["step_max_traj"].shape == (20, n)
    # PGD converges: the final step is much smaller than the first
    steps = np.asarray(diag["step_max_traj"]).max(axis=1)
    assert steps[-1] < steps[0]
    # conservation holds to projection tolerance at the solution
    assert float(np.max(np.asarray(diag["conservation_resid"]))) < 1e-3
    assert np.all(np.asarray(diag["proj_nu_tol"]) >= 0.0)
    # uncontended campus limits -> zero dual residual
    assert float(np.max(np.asarray(diag["dual_resid"]))) == 0.0
    # point-forecast problem -> degenerate tail mass 1.0
    np.testing.assert_array_equal(np.asarray(diag["cvar_tail_mass"]),
                                  np.ones(n, np.float32))


def test_day_step_telemetry_record_sane():
    """In-graph DayTelemetry gauges stay in range through a real rollout."""
    cfg = SimConfig(**CFG_KW, telemetry=True)
    sc = default_library(DAYS)[:1]
    batch = build_batch(cfg, sc, [0, 1], DAYS)
    _, _, traj = rollout_batch(cfg, DAYS)(batch)
    t = jax.tree.map(np.asarray, traj["telemetry"])
    for leaf in (t.uifq_coverage, t.vcc_binding_frac, t.theta_covered,
                 t.paused, t.shaped):
        assert np.all(leaf >= 0.0) and np.all(leaf <= 1.0)
    for leaf in (t.uif_mape, t.tuf_mape, t.tr_mape, t.queue_age_days,
                 t.fc_level_drift, t.proj_nu_tol, t.dual_resid,
                 t.cvar_tail_mass):
        assert np.all(leaf >= 0.0)
    assert np.all((t.joint_winner == 0.0) | (t.joint_winner == 1.0))


# ------------------------------------------------------------ trace export

def test_trace_records_roundtrip_jsonl(tmp_path):
    cfg = SimConfig(**CFG_KW, telemetry=True)
    scens = default_library(DAYS)[:2]
    batch = build_batch(cfg, scens, [0, 1], DAYS)
    _, _, traj = rollout_batch(cfg, DAYS)(batch)
    recs = telemetry_records(traj["telemetry"], [s.name for s in scens], 2)
    assert len(recs) == 2 * 2 * DAYS
    assert all(set(r) == set(TRACE_FIELDS) for r in recs)
    path = tmp_path / "trace.jsonl"
    write_jsonl(path, recs)
    back = read_jsonl(path)
    assert back == json.loads(json.dumps(recs))  # exact round-trip
    rows = telemetry_rows(back)
    assert [r["scenario"] for r in rows] == [s.name for s in scens]
    table = format_table(rows, TELEMETRY_COLUMNS)
    assert "thetaCov" in table and "vccBind" in table
    # wrong batch geometry is rejected loudly
    with pytest.raises(ValueError):
        telemetry_records(traj["telemetry"], [scens[0].name], 2)


# ------------------------------------------------------- report std fixes

def _ledger_batch(vals):
    """A batched one-cluster Ledger whose carbon_kg sums differ per seed."""
    leds = []
    for v in vals:
        led = init_ledger(1)
        m = DayMetrics(
            carbon_kg=jnp.asarray([v], f32), kwh=jnp.asarray([v], f32),
            peak_kw=jnp.asarray([1.0], f32), served=jnp.asarray([1.0], f32),
            arrived=jnp.asarray([1.0], f32), unmet=jnp.asarray([0.0], f32),
            queue_end=jnp.asarray([0.0], f32),
            cf_carbon_kg=jnp.asarray([2 * v], f32),
            cf_kwh=jnp.asarray([2 * v], f32),
            cf_peak_kw=jnp.asarray([2.0], f32),
            cf_served=jnp.asarray([1.0], f32),
            cf_queue_end=jnp.asarray([0.0], f32))
        leds.append(ledger_update(led, m))
    return jax.tree.map(lambda *xs: jnp.stack(xs), *leds)


def test_scenario_rows_std_is_sample_std():
    """Seeds are a sample: std must be Bessel-corrected (ddof=1) for
    n_seeds > 1, and the n_seeds=1 path pins 0.0 — never NaN (np.std of
    one value with ddof=1 is NaN)."""
    led = _ledger_batch([10.0, 14.0])
    rows = scenario_rows(led, ["s"], n_seeds=2)
    vals = np.array([10.0, 14.0])
    assert rows[0]["carbon_kg"] == pytest.approx(vals.mean())
    assert rows[0]["carbon_kg_std"] == pytest.approx(vals.std(ddof=1))
    led1 = _ledger_batch([10.0])
    rows1 = scenario_rows(led1, ["s"], n_seeds=1)
    assert rows1[0]["carbon_kg_std"] == 0.0
    for k, v in rows1[0].items():
        if isinstance(v, float):
            assert not np.isnan(v), k
