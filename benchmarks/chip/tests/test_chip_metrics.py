"""Each per-layer metric reader of the chip benchmark."""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[3]))

from benchmarks.chip import reduce, roofline, run  # noqa: E402
from chip_trace_cases import DEV, handmade  # noqa: E402

V5E = roofline.peaks("TPU v5 lite")


class Trace:
    def __init__(self, work, events=None):
        ev = events if events is not None else handmade()
        lo, hi = reduce.window(ev)
        self.events, self.window_ns = ev, hi - lo
        self.devices = [reduce.Device(ev, DEV, lo, hi)]
        self.work, self.peaks = work, V5E


SWEEP = {"fleet_days": 10, "epoch_rows": 256}
# the work of a cell that simulates no fleet-days, such as a controller
# cell timing single solves
OTHER = {"full_solves": 1}


def test_sweep_readers():
    tr = Trace(SWEEP)
    assert run.reader("device_idle_pct.sweep")(tr) == pytest.approx(50.0)
    assert run.reader("pgd_kernel_ms.sweep")(tr) == pytest.approx(15e-6
                                                                 / 10)
    assert run.reader("other_device_ms.sweep")(tr) == pytest.approx(
        35e-6 / 10)
    want = 100 * 2 * roofline.pgd_epoch_bytes(256) / 819e9 / 15e-9
    assert run.reader("pgd_roofline_pct.sweep")(tr) == pytest.approx(want)


@pytest.mark.parametrize("name", ["device_idle_pct.sweep",
                                  "pgd_kernel_ms.sweep",
                                  "pgd_roofline_pct.sweep",
                                  "other_device_ms.sweep"])
def test_sweep_readers_find_nothing_in_controller_cells(name):
    assert run.reader(name)(Trace(OTHER)) is None


def test_kernel_readers_without_kernel_events_read_nothing():
    ev = [e for e in handmade() if reduce.PALLAS not in e[2]]
    for name in ("pgd_kernel_ms.sweep", "pgd_roofline_pct.sweep"):
        assert run.reader(name)(Trace(SWEEP, ev)) is None


def test_every_declared_metric_has_a_reader():
    bench = json.loads((Path(__file__).resolve().parents[3]
                        / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        assert callable(run.reader(m["name"]))
