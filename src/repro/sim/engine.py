"""Batched fleet rollout engine: scan/vmap/shard_map over the staged core.

The CICS day cycle itself lives in ``repro.core.stages`` (pure stage
functions composed by ``stages.make_day_step``); this module owns only the
ROLLOUT machinery around it:

  * `SimConfig`            — static shapes + solver knobs. Everything
    dynamic (prices, risk, weather, outages) lives in `SimParams` arrays.
  * `make_day_step(cfg)`   — the staged day, returning (state', StepOut).
  * `make_init(cfg)`       — `lax.scan` burn-in -> SimState (jit/vmap-safe).
  * `make_rollout(cfg, d)` — `lax.scan` of the day step over days, carrying
    an emissions ledger and advancing an UNSHAPED counterfactual fleet
    (identical arrivals, VCC = machine capacity) in the same trace.
  * `rollout_batch`        — `jax.vmap` of (init + rollout) across a
    leading (scenario x seed) axis of stacked SimParams.
  * `rollout_batch_sharded`— the same batch `shard_map`'d over a 1-D
    device mesh (`launch.mesh.make_batch_mesh`): scenario batches scale
    across every accelerator on the host/pod, one shard per device group.

Parity contract (tested on CPU): a vmap'd batch reproduces each
scenario's non-batched sequential rollout BITWISE, for any batch size,
and the sharded batch reproduces the unsharded batch bitwise on a
one-device mesh. This needs batch-invariant numerics — ordered
reductions for daily totals (`admission.hour_sum`), the pairwise-summed
normal equations of `power.fit_pd_window`, the elementwise
`power._solve_spd` / `power.pd_power`, and the `optimization_barrier`
pins at every stage boundary in `stages` so XLA cannot re-fuse (and
re-round) a producer when its consumers change.

On a TPU it holds only in part. The burn-in is bitwise across batch
extents (11 against 44 on a v5e), but the day step's forecasts (demand,
reservation ratio, grid intensity: `jnp.sum`/`mean` over history
windows) round by the extent of the batch, so a rollout in a batch of 11
differs from the same rollout in a batch of 44, and the SLO and
admission decisions carry that to percent gaps in single clusters.
Sharding adds no rounding there: over 4 v5e chips the sharded batch
equals its per-device slices run on one chip as batches of their own,
bitwise. Across batch extents, and so across device counts, the
contract is each rollout's fleet totals within 1%, which
``chip_smoke.py`` checks for batch 11 against 44 on one chip and
``--four-chips`` for 4 chips against one. On CPU, across 4 host devices,
the sharded program may also round differently from the unsharded one
(tests/test_sim_scenarios.py). `rollout_sequential` additionally drives
the same jitted day step from a Python loop — a debugging reference that
agrees to float tolerance (standalone-vs-scan-body compilation may
differ in FMA choices).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax

from repro.core import stages
from repro.core.stages import (SimParams, SimState,    # noqa: F401
                               StepOut, hour_sum as _hsum)
from repro.sim.ledger import DayMetrics, init_ledger, ledger_update


@dataclass(frozen=True)
class SimConfig:
    """Static structure (shapes + solver knobs). Everything dynamic —
    prices, risk, weather, outages — lives in SimParams as arrays."""
    n_clusters: int = 16
    n_campuses: int = 4
    n_zones: int = 4
    pds_per_cluster: int = 2
    hist_days: int = 35           # rolling-history window (weeks * 7)
    slo_margin: float = 1.0
    slo_pause_days: int = 7
    joint_spatial: bool = False   # True = joint spatio-temporal optimize
    #                               (static graph selection; each
    #                               scenario's mobility stays a data leaf)
    n_members: int = 1            # forecast-ensemble size K (static shape;
    #                               K > 1 turns on the CVaR risk objective
    #                               at each scenario's risk_beta)
    streaming: bool = False       # True = O(1) streaming prediction layer
    #                               (stats.PredictorState carry; state and
    #                               day-step cost independent of
    #                               hist_days — year-scale rollouts);
    #                               False = the legacy rescan graph
    #                               (golden-trace pinned)
    telemetry: bool = False       # True = stack a sim.telemetry
    #                               DayTelemetry record per day into the
    #                               rollout traj (solver convergence +
    #                               forecast calibration + SLO gauges);
    #                               False = the legacy graph, byte-
    #                               identical compiled HLO (tested)
    mpc: bool = False             # True = intra-day MPC recourse (hourly
    #                               warm-started suffix re-solves,
    #                               core.mpc); False = open-loop day-ahead
    #                               plan, byte-identical compiled HLO
    #                               (tested, same contract as telemetry)
    slo_allowance: float = 0.25   # late-arrival fraction not counted as
    #                               unmet (admission.finalize_day)

    def stage_config(self) -> stages.StageConfig:
        return stages.StageConfig(slo_margin=self.slo_margin,
                                  slo_pause_days=self.slo_pause_days,
                                  joint_spatial=self.joint_spatial,
                                  n_members=self.n_members,
                                  streaming=self.streaming,
                                  telemetry=self.telemetry,
                                  mpc=self.mpc,
                                  slo_allowance=self.slo_allowance)


def _metrics(res, cf) -> DayMetrics:
    return DayMetrics(
        carbon_kg=_hsum(res.carbon), kwh=_hsum(res.power),
        peak_kw=res.power.max(axis=-1), served=res.served,
        arrived=res.arrived, unmet=res.unmet, queue_end=res.queue_end,
        cf_carbon_kg=_hsum(cf.carbon), cf_kwh=_hsum(cf.power),
        cf_peak_kw=cf.power.max(axis=-1), cf_served=cf.served,
        cf_queue_end=cf.queue_end)


def make_day_step(cfg: SimConfig):
    """The staged CICS day (see stages.make_day_step):
    step(params, state, xs) -> (state', StepOut)."""
    return stages.make_day_step(cfg.stage_config())


def make_init(cfg: SimConfig):
    """init(params) -> burned-in SimState. jit- and vmap-compatible."""
    return stages.make_init(cfg.n_clusters, cfg.n_campuses, cfg.n_zones,
                            cfg.hist_days, streaming=cfg.streaming)


def _day_xs(params: SimParams, d=None):
    """Scenario-schedule slices. With d=None returns scan xs (leading day
    axis); with an int d returns that single day's slices.

    The intraday forecast-busting channels are included only when the
    SimParams carry them (non-None): absent keys keep the traced day-step
    graph — and its compiled HLO — exactly the legacy one."""
    sched = {"green_scale": params.green_scale,
             "coal_scale": params.coal_scale,
             "cap_scale": params.cap_scale,
             "arrival_scale": params.arrival_scale,
             "campus_scale": params.campus_scale}
    if params.arrival_hour_scale is not None:
        sched["arrival_hour_scale"] = params.arrival_hour_scale
    if params.carbon_hour_scale is not None:
        sched["carbon_hour_scale"] = params.carbon_hour_scale
    if d is None:
        return sched
    return {k: v[d] for k, v in sched.items()}


def make_rollout(cfg: SimConfig, days: int):
    """rollout(params, state) -> (state', Ledger, traj dict of (days,))."""
    step = make_day_step(cfg)

    def rollout(params: SimParams, state: SimState):
        horizon = params.cap_scale.shape[-2]
        if horizon < days:
            raise ValueError(
                f"params schedules cover {horizon} days but the rollout "
                f"asks for {days}; rebuild with build_params(..., "
                f"days>={days})")
        ledger = stages.vary_like(init_ledger(cfg.n_clusters),
                                  params.truth["capacity"])

        def body(carry, xs):
            s, led = carry
            s, out = step(params, s, xs)
            with jax.named_scope("engine.ledger"):
                metrics = _metrics(out.res, out.cf)
                led = ledger_update(led, metrics)
                traj = {"carbon_kg": _hsum(metrics.carbon_kg),
                        "cf_carbon_kg": _hsum(metrics.cf_carbon_kg),
                        "kwh": _hsum(metrics.kwh),
                        "peak_kw": _hsum(metrics.peak_kw),
                        "queue": _hsum(metrics.queue_end)}
            if cfg.telemetry:
                # stacked by the scan -> (days, ...) DayTelemetry leaves
                # (telemetry=False keeps the traj keys — and graph —
                # exactly the legacy ones)
                traj["telemetry"] = out.telemetry
            if cfg.mpc:
                # (days,) accepted re-plans, beside the telemetry record
                # and with telemetry off too
                traj["recourse_hours"] = out.recourse_hours
            return (s, led), traj

        xs = jax.tree.map(lambda a: a[:days], _day_xs(params))
        (state, ledger), traj = jax.lax.scan(body, (state, ledger), xs)
        return state, ledger, traj

    return rollout


def rollout_batch(cfg: SimConfig, days: int):
    """vmap'd (init + rollout) over a leading (scenario x seed) axis."""
    init = make_init(cfg)
    roll = make_rollout(cfg, days)

    @jax.jit
    def run(params: SimParams):
        def one(p):
            return roll(p, init(p))
        return jax.vmap(one)(params)

    return run


def rollout_batch_sharded(cfg: SimConfig, days: int, mesh=None):
    """`rollout_batch` with the (scenario x seed) batch axis sharded over
    a 1-D device mesh (`launch.mesh.make_batch_mesh()` over all local
    devices by default). Each device runs its vmap'd slice of the batch;
    there is no cross-rollout communication. On a one-device mesh the
    result is bitwise identical to the unsharded `rollout_batch`
    (parity-tested). Across devices a rollout runs in a smaller batch,
    and where the backend rounds by batch extent (a TPU) each rollout's
    fleet totals agree within 1% (see the module docstring).

    The leading batch extent must divide by the mesh size — pad the batch
    (e.g. repeat a seed) or pass a smaller mesh otherwise.
    """
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_batch_mesh

    if mesh is None:
        mesh = make_batch_mesh()
    n_dev = mesh.devices.size
    init = make_init(cfg)
    roll = make_rollout(cfg, days)

    def one(p):
        return roll(p, init(p))

    # P("batch") as a prefix spec: shard the leading axis of every leaf
    mapped = jax.shard_map(jax.vmap(one), mesh=mesh, in_specs=P("batch"),
                           out_specs=P("batch"))
    mapped = jax.jit(mapped)

    def run(params: SimParams):
        b = jax.tree_util.tree_leaves(params)[0].shape[0]
        if b % n_dev:
            raise ValueError(
                f"batch of {b} rollouts does not divide across the "
                f"{n_dev}-device mesh; pad the (scenario x seed) batch or "
                "pass a smaller mesh")
        return mapped(params)

    return run


def rollout_sequential(cfg: SimConfig, days: int, params: SimParams,
                       state: SimState):
    """Debugging reference: drive the SAME jitted day step from a Python
    loop. Agrees with the scan engine to float tolerance (XLA may compile
    the standalone step with different FMA/fusion choices than the scan
    body); the bitwise guarantee is batched-vs-unbatched `make_rollout`."""
    step = stages.jitted_day_step(cfg.stage_config())
    ledger = init_ledger(cfg.n_clusters)
    for d in range(days):
        state, out = step(params, state, _day_xs(params, d))
        ledger = ledger_update(ledger, _metrics(out.res, out.cf))
    return state, ledger
