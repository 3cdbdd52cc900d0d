"""Scenario engine + batched fleet simulation (beyond-paper subsystem).

The paper validates CICS with fleet-wide rollouts under real operational
variation; this package supplies the reproduction's counterpart: a library
of declarative scenario perturbations (`scenarios`), a jit/vmap-batched
rollout engine over a (scenario x seed) axis (`engine`), a per-cluster
emissions ledger with an unshaped counterfactual run in the same batch
(`ledger`), and per-scenario summary reporting (`report`).
"""
from repro.sim.engine import (SimConfig, SimParams, SimState, make_init,
                              make_day_step, make_rollout, rollout_batch,
                              rollout_batch_sharded, rollout_sequential)
from repro.sim.ledger import Ledger, init_ledger, ledger_update, summarize
from repro.sim.scenarios import (Scenario, build_params, build_batch,
                                 default_library, forecast_bust_library,
                                 mobility_sweep_library,
                                 risk_sweep_library, MOBILITY_SWEEP,
                                 RISK_BETAS, RISK_MEMBERS)
from repro.sim.report import (scenario_rows, format_table,
                              mobility_sweep_rows, mpc_recourse_rows,
                              risk_sweep_rows, state_nbytes,
                              telemetry_rows, MOBILITY_COLUMNS,
                              MPC_COLUMNS, RISK_COLUMNS,
                              TELEMETRY_COLUMNS)
from repro.sim.telemetry import (DayTelemetry, day_telemetry,
                                 telemetry_records, write_jsonl, read_jsonl,
                                 TRACE_FIELDS)

__all__ = [
    "SimConfig", "SimParams", "SimState", "make_init", "make_day_step",
    "make_rollout", "rollout_batch", "rollout_batch_sharded",
    "rollout_sequential",
    "Ledger", "init_ledger", "ledger_update", "summarize",
    "Scenario", "build_params", "build_batch", "default_library",
    "forecast_bust_library", "mobility_sweep_library",
    "risk_sweep_library", "MOBILITY_SWEEP", "RISK_BETAS", "RISK_MEMBERS",
    "scenario_rows", "format_table", "mobility_sweep_rows",
    "mpc_recourse_rows", "risk_sweep_rows", "state_nbytes",
    "telemetry_rows", "MOBILITY_COLUMNS", "MPC_COLUMNS", "RISK_COLUMNS",
    "TELEMETRY_COLUMNS",
    "DayTelemetry", "day_telemetry", "telemetry_records", "write_jsonl",
    "read_jsonl", "TRACE_FIELDS",
]
