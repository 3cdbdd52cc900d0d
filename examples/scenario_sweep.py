"""Scenario sweep: the whole library x seeds in ONE vmap'd batch.

Runs >= 8 scenarios x 4 seeds of multi-week CICS rollouts in a single
batched call (burn-in + rollout compiled once, scanned over days, vmapped
over the scenario-seed axis), then prints the per-scenario table of carbon
saved vs. the unshaped counterfactual, peak-power reduction, and
flexible-work completion within 24h.

    PYTHONPATH=src python examples/scenario_sweep.py [--days 14] [--seeds 4]
                                                     [--sharded]

``--sharded`` runs the same batch through `rollout_batch_sharded`: the
(scenario x seed) axis is shard_map'd over every local device. On one
device the table is bitwise the same; across devices each device's
program may round differently, which moves single clusters but keeps
fleet totals within 1% (the engine's parity contract, see README).

Reading the table: carbon-priced scenarios trade peak power for carbon
(negative peakRed% — the 'War of the Efficiencies'); `peak_shaver` flips
the prices and the sign.

``--risk`` swaps in the risk-sweep family (`risk_sweep_library`): CVaR
tail fraction beta in {0.5, 0.9, 0.99} under drought + surge, run once
per ensemble size K in RISK_MEMBERS = {1, 8, 32} (K is a static shape —
one compile each; beta is a data leaf — the sweep batches). K=1 is the
degenerate control: every beta row is identical to the point-forecast
path.

``--spatial`` swaps in the mobility-sweep family
(`mobility_sweep_library`): spatial mobility in {0, 10, 30, 60}% under a
zone-0 renewable drought + demand surge, run TWICE over the same batch —
once with the joint spatio-temporal optimizer
(`SimConfig(joint_spatial=True)`: delta and the budget shift descended
together, bounds recomputed from the shifted budgets in the fused step)
and once with the sequential greedy pre-shift. The vsSeq% column is the
carbon the joint optimizer saves over the sequential two-phase baseline;
mobility=0 is the temporal-only control row (the shift is pinned to
zero; the joint path may still refine delta, so the rows agree to float
tolerance, not bitwise).

``--telemetry`` reruns the default library with the in-graph
DayTelemetry record stacked into the rollout (`SimConfig(telemetry=
True)`) and prints a second table of solver convergence and forecast
calibration per scenario (see README "Observability"); ``--trace PATH``
additionally exports the raw per scenario x seed x day records as JSONL.
"""
import argparse
import time

import jax

from repro.launch.cache import enable_compile_cache
from repro.sim import (MOBILITY_COLUMNS, RISK_COLUMNS, RISK_MEMBERS,
                       SimConfig, TELEMETRY_COLUMNS, build_batch,
                       default_library, format_table,
                       mobility_sweep_library, mobility_sweep_rows,
                       risk_sweep_library, risk_sweep_rows, rollout_batch,
                       rollout_batch_sharded, scenario_rows,
                       telemetry_records, telemetry_rows, write_jsonl)


def run_risk_sweep(args):
    scenarios = risk_sweep_library(args.days)
    seeds = list(range(args.seeds))
    engine = rollout_batch_sharded if args.sharded else rollout_batch
    ledgers_by_k = {}
    for k in RISK_MEMBERS:
        cfg = SimConfig(n_clusters=args.clusters, n_campuses=4, n_zones=4,
                        pds_per_cluster=2, hist_days=args.hist,
                        n_members=k)
        batch = build_batch(cfg, scenarios, seeds, args.days)
        t0 = time.time()
        _, led, _ = engine(cfg, args.days)(batch)
        jax.block_until_ready(led)
        print(f"K={k}: {len(scenarios) * len(seeds)} rollouts in "
              f"{time.time() - t0:.1f}s incl. compile")
        ledgers_by_k[k] = led
    rows = risk_sweep_rows(ledgers_by_k, [s.name for s in scenarios],
                           len(seeds))
    for r in rows:
        r["scenario"] = f"K={r['n_members']:<3d} {r['scenario']}"
    print()
    print(format_table(rows, RISK_COLUMNS))
    print("\n(risk_beta = averaged worst-tail fraction: smaller = more "
          "risk-averse; K=1 rows are the degenerate point-forecast "
          "control)")


def run_mobility_sweep(args):
    scenarios = mobility_sweep_library(args.days)
    seeds = list(range(args.seeds))
    engine = rollout_batch_sharded if args.sharded else rollout_batch
    ledgers = {}
    for joint in (True, False):
        cfg = SimConfig(n_clusters=args.clusters, n_campuses=4, n_zones=4,
                        pds_per_cluster=2, hist_days=args.hist,
                        joint_spatial=joint)
        batch = build_batch(cfg, scenarios, seeds, args.days)
        t0 = time.time()
        _, led, _ = engine(cfg, args.days)(batch)
        jax.block_until_ready(led)
        mode = "joint" if joint else "sequential"
        print(f"{mode}: {len(scenarios) * len(seeds)} rollouts in "
              f"{time.time() - t0:.1f}s incl. compile")
        ledgers[joint] = led
    rows = mobility_sweep_rows(ledgers[True], ledgers[False],
                               [s.name for s in scenarios], len(seeds))
    print()
    print(format_table(rows, MOBILITY_COLUMNS))
    print("\n(vsSeq% = carbon the joint spatio-temporal optimizer saves "
          "over the sequential greedy pre-shift on the same rollouts; "
          "mobility000 is the temporal-only control)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--days", type=int, default=14)
    ap.add_argument("--seeds", type=int, default=4)
    ap.add_argument("--clusters", type=int, default=8)
    ap.add_argument("--hist", type=int, default=28)
    ap.add_argument("--sharded", action="store_true",
                    help="shard the (scenario x seed) batch over all "
                         "local devices")
    ap.add_argument("--risk", action="store_true",
                    help="run the CVaR risk-sweep family (beta x K) "
                         "instead of the default library")
    ap.add_argument("--spatial", action="store_true",
                    help="run the mobility-sweep family through the joint "
                         "spatio-temporal optimizer vs the sequential "
                         "pre-shift")
    ap.add_argument("--telemetry", action="store_true",
                    help="stack the in-graph DayTelemetry record per day "
                         "(SimConfig(telemetry=True)) and print the "
                         "per-scenario solver/forecast diagnostics table")
    ap.add_argument("--trace", type=str, default=None, metavar="PATH",
                    help="with --telemetry: also write the per scenario x "
                         "seed x day trace records to PATH as JSONL")
    args = ap.parse_args()
    enable_compile_cache()
    if args.days < 1 or args.seeds < 1:
        ap.error("--days and --seeds must be >= 1")
    if args.risk and args.spatial:
        ap.error("--risk and --spatial are mutually exclusive")
    if args.trace and not args.telemetry:
        ap.error("--trace requires --telemetry")
    if args.telemetry and (args.risk or args.spatial):
        ap.error("--telemetry applies to the default scenario library")
    if args.risk:
        run_risk_sweep(args)
        return
    if args.spatial:
        run_mobility_sweep(args)
        return

    cfg = SimConfig(n_clusters=args.clusters, n_campuses=4, n_zones=4,
                    pds_per_cluster=2, hist_days=args.hist,
                    telemetry=args.telemetry)
    scenarios = default_library(args.days)
    seeds = list(range(args.seeds))
    mode = (f"shard_map'd over {len(jax.devices())} device(s)"
            if args.sharded else "one vmap'd batch")
    print(f"{len(scenarios)} scenarios x {len(seeds)} seeds x "
          f"{args.days} days ({cfg.n_clusters} clusters, "
          f"{cfg.hist_days}-day burn-in) in {mode}...")

    batch = build_batch(cfg, scenarios, seeds, args.days)
    run = (rollout_batch_sharded if args.sharded
           else rollout_batch)(cfg, args.days)
    t0 = time.time()
    _, ledgers, traj = run(batch)
    jax.block_until_ready(ledgers)
    wall = time.time() - t0
    n_rollouts = len(scenarios) * len(seeds)
    print(f"{n_rollouts} rollouts ({n_rollouts * args.days} fleet-days) "
          f"in {wall:.1f}s incl. compile\n")

    rows = scenario_rows(ledgers, [s.name for s in scenarios], len(seeds))
    print(format_table(rows))
    print("\n(+carbonSaved% = shaped fleet emitted less than the unshaped "
          "counterfactual; flex<24h% = flexible work completed within a "
          "day, paper SLO)")

    if args.telemetry:
        records = telemetry_records(traj["telemetry"],
                                    [s.name for s in scenarios], len(seeds))
        print()
        print(format_table(telemetry_rows(records), TELEMETRY_COLUMNS))
        print("\n(objDec% = PGD objective decrease across the dual-ascent "
              "rounds; thetaCov/uifQCov = forecast-bound coverage of the "
              "realized day; vccBind = fraction of hours admission is "
              "pinned at the VCC; queueAge = backlog in days of service)")
        if args.trace:
            write_jsonl(args.trace, records)
            print(f"\n{len(records)} trace records "
                  f"(scenario x seed x day) -> {args.trace}")


if __name__ == "__main__":
    main()
