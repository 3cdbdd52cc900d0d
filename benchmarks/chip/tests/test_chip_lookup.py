"""The chip benchmark finds its cells, configurations, traffic, limits,
drivers and readers by name, refuses unknown names, and keeps to the
shape BENCHMARK.json promises."""
import importlib
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import roofline, run  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_lookup_by_name(cell):
    spec = run.load_cell(cell)
    assert spec["cell"]["name"] == cell
    assert spec["config"]["name"] == spec["cell"]["config"]
    assert spec["limits"], "every cell states its limits"
    drv = importlib.import_module(
        f"benchmarks.chip.drivers.{spec['traffic']['driver']}")
    assert hasattr(drv, "Driver")


@pytest.mark.parametrize("name", ["no_such.cell", "fleet256.sweep15"])
def test_unknown_cell_is_refused(name):
    with pytest.raises(run.Refused):
        run.load_cell(name)


@pytest.mark.parametrize("name", ["../run", "a/b", "", ".hidden"])
def test_names_that_would_leave_the_benchmark_are_refused(name):
    with pytest.raises(run.Refused):
        run.checked_name(name)


def test_unknown_metric_has_no_reader():
    with pytest.raises(FileNotFoundError):
        run.reader("no_such_metric")


def test_unknown_perturbation_is_refused():
    from benchmarks.chip import traffic
    fleet = {"n_clusters": 4, "n_campuses": 2, "n_zones": 2}
    with pytest.raises(ValueError):
        traffic.schedules(fleet, {"name": "x", "perturbations": [
            {"kind": "Meteor"}]}, 0, 3)


def test_benchmark_json_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in BENCH["configs"]] + CELLS \
        + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        run.checked_name(n)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    assert all(0 < m["bound"] <= 0.25 for m in e2e.values())
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            assert w in CELLS
            assert w in e2e[m["moves"]].get("workloads", [w])


def test_peaks_by_device_kind():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_epoch_bytes_from_shapes():
    # 7 (n, 24) operands and 5 per-cluster scalars, float32
    assert roofline.pgd_epoch_bytes(1) == (7 * 24 + 5) * 4
    assert roofline.pgd_epoch_bytes(44 * 256) == 44 * 256 * 692
    assert roofline.pgd_epoch_bytes(10, hours=1) == 10 * 12 * 4
    assert roofline.roofline_pct(819e9, 1.0, 819e9) == pytest.approx(100.0)
    assert roofline.roofline_pct(1.0, 0.0, 819e9) is None
