"""Quickstart: one CICS day on a small synthetic fleet.

Shows the paper's full pipeline end-to-end — carbon forecast, power-model
fit, load forecasts, risk-aware VCC optimization, Borg-like admission — and
prints the cluster-level result: VCC dips where carbon peaks, flexible work
shifts to green hours, daily totals conserved.

    PYTHONPATH=src python examples/quickstart.py
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.sim import (Scenario, SimConfig, build_params,  # noqa: E402
                       make_day_step, make_init)
from repro.sim.engine import _day_xs  # noqa: E402


def main():
    print("== CICS quickstart: init fleet (incl. 91-day telemetry burn-in)")
    cfg = SimConfig(n_clusters=8, n_campuses=2, n_zones=2,
                    pds_per_cluster=4, hist_days=91)
    sc = Scenario("quickstart", lambda_e=0.6, lambda_p=0.05, gamma=0.05)
    params = build_params(cfg, sc, seed=0, days=1)
    st = jax.jit(make_init(cfg))(params)
    st, out = jax.jit(make_day_step(cfg))(params, st, _day_xs(params, 0))
    sol, res, eta = out.sol, out.res, out.eta_act
    shaped = np.asarray(sol.shaped & st.shaping_allowed)
    print(f"shaped clusters: {shaped.sum()}/{cfg.n_clusters}")
    c = int(np.nonzero(shaped)[0][0])
    print(f"\ncluster {c} — hourly view (paper Fig 3):")
    print(f"{'h':>3} {'carbon':>7} {'VCC':>7} {'flex':>6} {'inflex':>7}")
    vcc = np.asarray(out.vcc_curve[c])
    flex = np.asarray(res.usage_flex[c])
    uif = np.asarray(res.usage_total[c] - res.usage_flex[c])
    for h in range(24):
        bar = "#" * int(np.asarray(eta[c])[h] * 40)
        print(f"{h:3d} {np.asarray(eta[c])[h]:7.3f} {vcc[h]:7.2f} "
              f"{flex[h]:6.2f} {uif[h]:7.2f}  {bar}")
    corr = np.corrcoef(np.asarray(sol.delta[c]), np.asarray(eta[c]))[0, 1]
    print(f"\ncorr(delta, carbon) = {corr:.2f}  (negative = load shifted "
          "away from dirty hours)")
    print(f"flexible served / arrived: {float(res.served[c]):.1f} / "
          f"{float(res.arrived[c]):.1f} CPU-h (daily total conserved)")


if __name__ == "__main__":
    main()
