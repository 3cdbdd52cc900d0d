"""Cells of the chip benchmark cut to a size a CPU test can hold, and a
way to run one through the harness with the chip look skipped."""
import io
import json
import sys
from contextlib import redirect_stdout
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import run  # noqa: E402

SEED = 2**33 + 5
HERE = ROOT / "benchmarks" / "chip"


def spec_of(name, config, traffic, chips=1):
    """A cell's spec read from its own files (not from BENCHMARK.json),
    so the tests hold a driver whether or not a cell of it is listed."""
    def load(path):
        return json.loads(path.read_text())
    return {"bench": load(ROOT / "BENCHMARK.json"),
            "cell": {"name": name, "config": config, "traffic": traffic,
                     "chips": chips},
            "config": load(HERE / "configs" / f"{config}.json"),
            "traffic": load(HERE / "traffic" / f"{traffic}.json"),
            "limits": load(HERE / "limits" / f"{name}.json")}


def rollout_spec():
    spec = spec_of("fleet256.sweep14", "fleet256", "sweep14")
    spec["config"]["fleet"].update(n_clusters=8, n_campuses=2, n_zones=2,
                                   hist_days=14)
    baseline, high_price = (spec["traffic"]["scenarios"][i] for i in (0, 6))
    spec["traffic"].update(days=6, seeds_per_scenario=2,
                           scenarios=[baseline, high_price])
    return spec


def run_cell(spec, *extra, seconds=0.5):
    """The harness's result for the cell, without the chip."""
    buf = io.StringIO()
    argv = ["--workload", spec["cell"]["name"], "--seed", str(SEED),
            "--seconds", str(seconds), "--trace", "0", *extra]
    with redirect_stdout(buf):
        rc = run.main(argv, require_chip=False, spec=spec)
    assert rc == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])
