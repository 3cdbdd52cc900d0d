"""Fleet telemetry layer: in-scan diagnostics + host-side trace export.

The measurement plane of the reproduction — two surfaces:

* **In-graph** (`DayTelemetry`, `day_telemetry`): a pytree record built
  inside the jitted day step when ``StageConfig.telemetry=True``. Solver
  convergence channels come from ``core.vcc.solve_vcc(telemetry=True)``
  (PGD objective/step trajectories through the dual-ascent scan,
  conservation/dual residuals, certified bisection tolerance, CVaR tail
  mass, joint-vs-sequential winner); forecast calibration (MAPE / bias /
  coverage of the day-ahead U_IF, T_UF, T_R and Theta forecasts against
  the realized day, plus a streaming-vs-rescan drift gauge against the
  trailing week) and SLO/headroom gauges (hourly VCC binding fraction,
  queue age) are computed here from the observe/SLO stage products. Every
  channel uses elementwise ops + ordered trailing-axis reductions
  (``admission.hour_sum``) and keeps the cluster axis unreduced, so the
  record rides ``lax.scan`` / ``vmap`` / ``shard_map`` without breaking
  the engine's bitwise batched==sequential parity contract. With the flag
  off the StepOut leaf stays ``None`` (an EMPTY pytree subtree): the
  legacy compiled graph is byte-identical (HLO-tested collapse contract).

* **Trace export** (`telemetry_records`, `write_jsonl`, `read_jsonl`):
  flatten a batched rollout's stacked DayTelemetry into one JSON record
  per scenario x seed x day (cluster axes reduced host-side), the schema
  consumed by ``report.telemetry_rows`` and the CI trace artifact.

Device time per stage is not measured here: the day step names its
stages with ``jax.named_scope`` and a profiler trace of the fused program
is read by those names (``benchmarks/chip/scopes.py``).
"""
from __future__ import annotations

import json
from typing import Dict, List, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.admission import hour_sum

f32 = jnp.float32


# -------------------------------------------------------- metric primitives

def mape(pred, actual, eps: float = 1e-6):
    """Mean absolute percentage error |pred - actual| / |actual| over the
    trailing axis (ordered ``hour_sum`` mean — batch-invariant); 1-D
    inputs return the per-element APE. Always >= 0."""
    e = jnp.abs(pred - actual) / jnp.clip(jnp.abs(actual), eps, None)
    if e.ndim > 1:
        return hour_sum(e) / e.shape[-1]
    return e


def bias(pred, actual, eps: float = 1e-6):
    """Signed relative error (pred - actual) / |actual|, trailing-axis
    mean for >=2-D inputs. A zero-error forecast gives exactly 0.0."""
    e = (pred - actual) / jnp.clip(jnp.abs(actual), eps, None)
    if e.ndim > 1:
        return hour_sum(e) / e.shape[-1]
    return e


def coverage(bound, actual):
    """Empirical coverage: fraction of trailing-axis entries with
    ``actual <= bound`` (in [0, 1] by construction); 1-D inputs return
    the 0/1 indicator."""
    ok = (actual <= bound).astype(f32)
    if ok.ndim > 1:
        return hour_sum(ok) / ok.shape[-1]
    return ok


def level_drift(fc_level, trailing, eps: float = 1e-6):
    """|forecast daily level - trailing-window mean| / mean: the gauge
    that catches a streaming predictor drifting away from what a rescan
    over the same window would forecast. fc_level (n,); trailing (n, W)."""
    m = hour_sum(trailing) / trailing.shape[-1]
    return jnp.abs(fc_level - m) / jnp.clip(jnp.abs(m), eps, None)


# ------------------------------------------------------- the in-graph record

class DayTelemetry(NamedTuple):
    """One day's diagnostics, per rollout. n = clusters, m = campuses,
    T = solver outer rounds. The cluster/campus axes are NOT reduced
    in-graph (host-side consumers reduce them — same convention as the
    Ledger), so stacking under scan/vmap yields (days, ...) and
    (batch, days, ...) leaves."""
    # --- solver convergence (core.vcc / core.spatial channels)
    obj_cluster_traj: jnp.ndarray     # (T, n) nominal cost per outer round
    step_max_traj: jnp.ndarray        # (T, n) max |delta step| per round
    conservation_resid: jnp.ndarray   # (n,)  |sum_h delta| at the solution
    proj_nu_tol: jnp.ndarray          # (n,)  certified bisection tolerance
    dual_resid: jnp.ndarray           # (m,)  relative campus overshoot
    cvar_tail_mass: jnp.ndarray       # (n,)  max CVaR member weight
    joint_winner: jnp.ndarray         # ()    1.0 = joint refinement kept
    # --- forecast calibration (vs the realized day)
    uif_mape: jnp.ndarray             # (n,) hourly U_IF forecast MAPE
    uif_bias: jnp.ndarray             # (n,) hourly U_IF signed rel. error
    tuf_mape: jnp.ndarray             # (n,) daily flexible-total MAPE
    tuf_bias: jnp.ndarray             # (n,)
    tr_mape: jnp.ndarray              # (n,) daily reservation-total MAPE
    tr_bias: jnp.ndarray              # (n,)
    theta_covered: jnp.ndarray        # (n,) 1.0 if realized T_R <= Theta
    uifq_coverage: jnp.ndarray        # (n,) frac hours U_IF <= (1-g) quant
    fc_level_drift: jnp.ndarray       # (n,) forecast-vs-trailing-week drift
    # --- SLO / headroom gauges
    vcc_binding_frac: jnp.ndarray     # (n,) frac hours reservations at VCC
    queue_age_days: jnp.ndarray       # (n,) backlog / daily service rate
    paused: jnp.ndarray               # (n,) 1.0 = SLO pause active
    shaped: jnp.ndarray               # (n,) 1.0 = cluster actively shaped
    # --- intra-day MPC recourse (core.mpc; zeros when StageConfig.mpc
    # is off so the telemetry pytree stays config-independent)
    mpc_recourse_frac: jnp.ndarray    # (n,) frac hours re-planned
    mpc_recourse_depth: jnp.ndarray   # (n,) mean |delta change| if re-planned


def day_telemetry(sdiag: Dict[str, jnp.ndarray], fc, res, u_if, vcc_curve,
                  *, pause_left, shaped, trail,
                  recourse=None) -> DayTelemetry:
    """Assemble the day's DayTelemetry inside the jitted step.

    ``sdiag``: the optimize_stage solver-diagnostics dict; ``fc``: the
    forecast dict the day optimized against; ``res``: the shaped
    admission DayResult; ``u_if``: realized inflexible load (n, 24);
    ``trail``: dict of trailing-week daily levels {uif, tuf, tr} (n, 7)
    — the pred rings in streaming mode, the hist window tails in rescan
    mode; ``recourse``: the ``core.mpc.MPCDiag`` of the day when
    StageConfig.mpc (None = open loop, recorded as zeros).
    Barrier-pinned: telemetry must never change how the channels it taps
    re-fuse. Note ``vcc_curve`` is the curve admission actually enforced
    (under mpc the realized hour-by-hour curve), so ``vcc_binding_frac``
    gauges the closed loop, not the stale 00:00 plan."""
    daily_res = hour_sum(res.reservations)
    if recourse is None:
        rec_frac = jnp.zeros_like(daily_res)
        rec_depth = jnp.zeros_like(daily_res)
    else:
        rec_frac = recourse.recourse_frac
        rec_depth = recourse.recourse_depth
    drift = jnp.maximum(
        jnp.maximum(level_drift(hour_sum(fc["uif"]), trail["uif"]),
                    level_drift(fc["tuf"], trail["tuf"])),
        level_drift(fc["tr"], trail["tr"]))
    rec = DayTelemetry(
        obj_cluster_traj=sdiag["obj_cluster_traj"],
        step_max_traj=sdiag["step_max_traj"],
        conservation_resid=sdiag["conservation_resid"],
        proj_nu_tol=sdiag["proj_nu_tol"],
        dual_resid=sdiag["dual_resid"],
        cvar_tail_mass=sdiag["cvar_tail_mass"],
        joint_winner=sdiag["joint_winner"],
        uif_mape=mape(fc["uif"], u_if),
        uif_bias=bias(fc["uif"], u_if),
        tuf_mape=mape(fc["tuf"], res.served),
        tuf_bias=bias(fc["tuf"], res.served),
        tr_mape=mape(fc["tr"], daily_res),
        tr_bias=bias(fc["tr"], daily_res),
        theta_covered=(daily_res <= fc["theta"]).astype(f32),
        uifq_coverage=coverage(fc["uif_q"], u_if),
        fc_level_drift=drift,
        # an hour is "binding" when reservations reach the VCC (within
        # 0.1% — admission saturates at the curve, never above it)
        vcc_binding_frac=coverage(res.reservations, 0.999 * vcc_curve),
        queue_age_days=res.queue_end / jnp.clip(res.served, 1e-6, None),
        paused=(pause_left > 0).astype(f32),
        shaped=shaped.astype(f32),
        mpc_recourse_frac=rec_frac,
        mpc_recourse_depth=rec_depth)
    return jax.lax.optimization_barrier(rec)


# ---------------------------------------------------------- trace exporting

# one JSON record per scenario x seed x day; cluster/campus axes reduced
# host-side (fleet mean for calibration rates, max for residuals/ages)
TRACE_FIELDS = (
    "scenario", "seed", "day",
    "obj_first", "obj_final", "obj_decrease_pct", "step_final",
    "conservation_max", "proj_tol_max", "dual_max", "cvar_tail_max",
    "joint_winner",
    "uif_mape", "uif_bias", "tuf_mape", "tuf_bias", "tr_mape", "tr_bias",
    "theta_coverage", "uifq_coverage", "fc_level_drift",
    "vcc_binding_frac", "queue_age_max", "paused_frac", "shaped_frac",
    "mpc_recourse_frac", "mpc_recourse_depth",
)


def telemetry_records(tel: DayTelemetry, scenario_names: Sequence[str],
                      n_seeds: int) -> List[Dict[str, object]]:
    """Flatten a batched rollout's stacked telemetry — leaves shaped
    (scenario x seed, days, ...), scenario-major seed-minor (the
    ``scenarios.build_batch`` layout) — into TRACE_FIELDS records."""
    t = jax.tree.map(lambda a: np.asarray(a, dtype=np.float64), tel)
    batch, days = t.uif_mape.shape[:2]
    if batch != len(scenario_names) * n_seeds:
        raise ValueError(
            f"telemetry batch of {batch} rollouts != {len(scenario_names)} "
            f"scenarios x {n_seeds} seeds")
    records = []
    for b in range(batch):
        scen = scenario_names[b // n_seeds]
        seed = b % n_seeds
        for d in range(days):
            obj_first = float(t.obj_cluster_traj[b, d, 0].sum())
            obj_final = float(t.obj_cluster_traj[b, d, -1].sum())
            records.append({
                "scenario": scen, "seed": seed, "day": d,
                "obj_first": obj_first, "obj_final": obj_final,
                "obj_decrease_pct": 100.0 * (obj_first - obj_final)
                / max(abs(obj_first), 1e-9),
                "step_final": float(t.step_max_traj[b, d, -1].max()),
                "conservation_max": float(t.conservation_resid[b, d].max()),
                "proj_tol_max": float(t.proj_nu_tol[b, d].max()),
                "dual_max": float(t.dual_resid[b, d].max()),
                "cvar_tail_max": float(t.cvar_tail_mass[b, d].max()),
                "joint_winner": float(t.joint_winner[b, d]),
                "uif_mape": float(t.uif_mape[b, d].mean()),
                "uif_bias": float(t.uif_bias[b, d].mean()),
                "tuf_mape": float(t.tuf_mape[b, d].mean()),
                "tuf_bias": float(t.tuf_bias[b, d].mean()),
                "tr_mape": float(t.tr_mape[b, d].mean()),
                "tr_bias": float(t.tr_bias[b, d].mean()),
                "theta_coverage": float(t.theta_covered[b, d].mean()),
                "uifq_coverage": float(t.uifq_coverage[b, d].mean()),
                "fc_level_drift": float(t.fc_level_drift[b, d].max()),
                "vcc_binding_frac": float(t.vcc_binding_frac[b, d].mean()),
                "queue_age_max": float(t.queue_age_days[b, d].max()),
                "paused_frac": float(t.paused[b, d].mean()),
                "shaped_frac": float(t.shaped[b, d].mean()),
                "mpc_recourse_frac": float(
                    t.mpc_recourse_frac[b, d].mean()),
                "mpc_recourse_depth": float(
                    t.mpc_recourse_depth[b, d].mean()),
            })
    return records


def write_jsonl(path, records: Sequence[Dict[str, object]]) -> None:
    """One JSON object per line (the CI trace-artifact format)."""
    with open(path, "w") as f:
        for rec in records:
            f.write(json.dumps(rec, sort_keys=True) + "\n")


def read_jsonl(path) -> List[Dict[str, object]]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


__all__ = [
    "DayTelemetry", "day_telemetry", "mape", "bias", "coverage",
    "level_drift", "telemetry_records", "write_jsonl", "read_jsonl",
    "TRACE_FIELDS",
]
