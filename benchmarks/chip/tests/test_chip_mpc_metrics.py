"""The closed-loop cell's per-layer readers, on a recorded TPU trace of a
tiny closed-loop rollout (``data/trace_tiny_mpc.json``: one simulated
day of 4 rollouts x 8 clusters, its day-ahead solve, 24 admission ticks
and 24 suffix re-solves, with the compiled program's scope map)."""
import json
import math
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[3]))

from benchmarks.chip import reduce, roofline, run, scopes  # noqa: E402

DATA = Path(__file__).with_name("data")
BUST = ["mpc_resolve_ms.bust", "mpc_tick_ms.bust", "pgd_kernel_ms.bust",
        "pgd_roofline_pct.bust", "device_idle_pct.bust"]
SCOPED = ["mpc_resolve_ms.bust", "mpc_tick_ms.bust"]
# the slice is one simulated day of 4 rollouts; an epoch covers all 4
# rollouts' 8 clusters
FLEET_DAYS, EPOCH_ROWS = 4, 32


class Trace:
    def __init__(self, name, work):
        data = json.loads((DATA / name).read_text())
        lo, hi = data["window"]
        ev = [[data["device"], reduce.OPS, n, s, d]
              for n, s, d in data["ops"]]
        ev.append([reduce.HOST, "python", "bench.window", lo, hi - lo])
        self.events, self.window_ns = ev, hi - lo
        self.devices = [reduce.Device(ev, data["device"], lo, hi)]
        self.scopes = data["scopes"]
        self.work = dict(work)
        if "scopes" in self.work:
            self.work["scopes"] = self.scopes
        self.peaks = roofline.peaks("TPU v5 lite")


def mpc_trace(*drop):
    work = {"fleet_days": FLEET_DAYS, "epoch_rows": EPOCH_ROWS,
            "scopes": None}
    return Trace("trace_tiny_mpc.json",
                 {k: v for k, v in work.items() if k not in drop})


@pytest.mark.parametrize("name", BUST)
def test_bust_readers_on_the_recorded_trace(name):
    v = run.reader(name)(mpc_trace())
    assert v is not None and math.isfinite(v) and v > 0, v


def test_recorded_day_runs_its_solves_through_the_kernel():
    """20 day-ahead epochs and 2 suffix epochs an hour, every one under
    ``solver.pgd_epoch``; 48 of them inside ``mpc.resolve``."""
    tr = mpc_trace()
    pallas = tr.devices[0].pallas()
    paths = [tr.scopes[scopes.instr(e[2])] for e in pallas]
    assert len(pallas) == 68
    assert all(scopes.holds(p, "solver.pgd_epoch") for p in paths)
    assert sum(scopes.holds(p, "mpc.resolve") for p in paths) == 48
    kernel_ms = sum(e[4] for e in pallas) / 1e6
    assert run.reader("pgd_kernel_ms.bust")(tr) == pytest.approx(
        kernel_ms / FLEET_DAYS)
    want = 100 * 68 * roofline.pgd_epoch_bytes(EPOCH_ROWS) / 819e9 \
        / (kernel_ms / 1e3)
    assert run.reader("pgd_roofline_pct.bust")(tr) == pytest.approx(want)


def test_resolve_and_tick_within_the_hour_loop():
    tr = mpc_trace()
    resolve = run.reader("mpc_resolve_ms.bust")(tr)
    tick = run.reader("mpc_tick_ms.bust")(tr)
    hour = scopes.ms_per_unit(tr.devices, tr.scopes, FLEET_DAYS, "mpc.hour")
    assert resolve + tick <= hour * (1 + 1e-12)
    # the re-solves' kernel events are counted in the re-solve
    kernel = sum(e[4] for e in tr.devices[0].pallas()
                 if scopes.holds(tr.scopes[scopes.instr(e[2])],
                                 "mpc.resolve")) / 1e6 / FLEET_DAYS
    assert kernel <= resolve


@pytest.mark.parametrize("name", SCOPED)
def test_scope_readers_find_nothing_in_the_sweep(name):
    """The open-loop sweep's program has no ``mpc.*`` scope."""
    tr = Trace("trace_tiny_sweep.json",
               {"fleet_days": 1, "epoch_rows": 32, "scopes": None})
    assert run.reader(name)(tr) is None


@pytest.mark.parametrize("name", SCOPED)
def test_scope_readers_find_nothing_without_a_scope_map(name):
    assert run.reader(name)(mpc_trace("scopes")) is None


@pytest.mark.parametrize("name", BUST)
def test_bust_readers_find_nothing_without_fleet_days(name):
    assert run.reader(name)(mpc_trace("fleet_days")) is None
