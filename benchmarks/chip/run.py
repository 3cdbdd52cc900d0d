"""Chip benchmark of the carbon-aware fleet system.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration file and a traffic
file; the traffic file names its driver (``drivers/<name>.py``). A run
loads them, turns on the persistent compile cache, builds every input on
the device from the seed, compiles the cell's own programs ahead of time
(each must call the configuration's Pallas kernel), warms up, and then
measures for ``--seconds``. With ``--trace 1`` the window runs under the
profiler and the run prints the cell's per-layer metrics, each read by
``metrics/<metric name>.py`` from the trace reduction (``reduce.py``),
and a breakdown of device time and idle gaps.

After the window the run checks what the timed calls produced against
the plain reference (``reference.py``) on a sample drawn from the seed,
and prints each compared number beside its limit (``limits/<cell>.json``)
as its last lines on stderr and under ``checks`` in the result.
``--control 1`` puts the reference, computed in bfloat16, in the
program's place instead, with no window; it has to come out not correct.

The last line of stdout is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and ``checks``. A run
exits non-zero, printing no result, without a TPU or with fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from contextlib import contextmanager, nullcontext  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
NAME_OK = set("abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ"
              "0123456789_.-")


class Refused(Exception):
    """The run cannot proceed; no result is printed."""


def checked_name(name: str) -> str:
    if not name or not set(name) <= NAME_OK or name[0] in ".-":
        raise Refused(f"not a benchmark name: {name!r}")
    return name


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell's entry, configuration, traffic and limits, by name."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no cell {name!r} in BENCHMARK.json")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads((HERE / "traffic"
                          / f"{checked_name(cell['traffic'])}.json")
                         .read_text())
    limits = json.loads((HERE / "limits" / f"{checked_name(name)}.json")
                        .read_text())
    return {"bench": bench, "cell": cell, "config": config,
            "traffic": traffic, "limits": limits}


def reader(metric: str):
    """The per-layer metric's reader, ``metrics/<metric>.py``."""
    path = HERE / "metrics" / f"{checked_name(metric)}.py"
    spec = importlib.util.spec_from_file_location(
        "chip_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Harness:
    """What a driver gets: the cell's files, the seed, the chips, and
    the ahead-of-time compile that checks for the Pallas kernel."""

    def __init__(self, spec, seed, require_kernel=True):
        self.cell, self.config = spec["cell"], spec["config"]
        self.traffic, self.limits = spec["traffic"], spec["limits"]
        self.seed, self.chips = int(seed), int(self.cell["chips"])
        self.require_kernel = require_kernel

    def compile(self, jitted, *args):
        """Lower and compile; the lowering must call the configuration's
        Pallas kernel and no other, so that every Pallas event of the
        trace is that kernel and the jnp oracle is never timed."""
        lowered = jitted.lower(*args)
        kernel = self.config["kernel"]
        found = set(re.findall(r'kernel_name = "(\w+)"', lowered.as_text()))
        if self.require_kernel and found != {kernel}:
            raise Refused(f"the program calls {sorted(found)}, not just "
                          f"{kernel}")
        return lowered.compile()


@contextmanager
def span(name):
    import jax
    with jax.profiler.TraceAnnotation(f"bench.{name}"):
        yield


def per_layer(spec, events, work, chips, device_kind):
    from benchmarks.chip import reduce, roofline

    class Trace:
        pass

    tr = Trace()
    tr.events = events
    lo, hi = reduce.window(events)
    tr.window_ns = hi - lo
    tr.devices = [reduce.Device(events, p, lo, hi)
                  for p in reduce.devices(events)[:chips]]
    tr.work = work
    tr.peaks = roofline.peaks(device_kind)
    cell = spec["cell"]["name"]
    out = {}
    for m in spec["bench"]["per_layer"]:
        if cell not in m.get("workloads", [cell]):
            continue
        v = reader(m["name"])(tr)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    busy = sum(d.busy_ns for d in tr.devices) / len(tr.devices) / 1e9
    worst = max(tr.devices, key=lambda d: d.idle_share)
    breakdown = {"device_ops": reduce.top_ops(tr.devices[0]),
                 "idle_gaps": reduce.idle_gaps(events, worst)}
    return out, {"busy_s": busy, "window_s": tr.window_ns / 1e9}, breakdown


def judge(numbers: dict, limits: dict) -> tuple:
    checks = {}
    ok = True
    for k, v in numbers.items():
        lim = limits[k]["limit"]
        checks[k] = {"value": v, "limit": lim}
        ok = ok and math.isfinite(v) and v <= lim
    return ok, checks


def run(args, require_chip=True, spec=None) -> dict:
    spec = spec or load_cell(args.workload)
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise Refused(f"no program under {src}")
    sys.path[:0] = [str(src)]
    import jax
    import_s = time.perf_counter() - T0
    devs = jax.devices()
    chips = int(spec["cell"]["chips"])
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise Refused(f"the cell needs {chips} TPU chip(s); JAX found "
                      f"{len(devs)} {devs[0].platform} device(s)")
    devs = devs[:chips]
    if require_chip:
        from repro.launch.cache import enable_compile_cache
        enable_compile_cache()
    compiles = [0]
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _s, **_kw: compiles.__setitem__(
            0, compiles[0] + (event == "/jax/core/compile/backend_compile"
                              "_duration")))
    h = Harness(spec, args.seed, require_kernel=require_chip)
    drv = importlib.import_module(
        f"benchmarks.chip.drivers.{checked_name(h.traffic['driver'])}"
    ).Driver(h)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if args.control:
        numbers = drv.control()
        ok, checks = judge(numbers, spec["limits"])
        return {"correct": ok, "attempted": 0, "failed": 0, "metrics": {},
                "device": device, "checks": checks}
    drv.setup()
    setup_s = time.perf_counter() - T0
    before = compiles[0]
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-") if args.trace \
        else None
    try:
        with (jax.profiler.trace(tdir) if tdir else nullcontext()):
            with span("window"):
                res = drv.window(args.seconds, span)
        if tdir:
            from benchmarks.chip import reduce
            events = reduce.load_events(tdir)
    finally:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)
    res["summary"]["compiles_in_window"] = compiles[0] - before
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    device["memory_peak_bytes"] = peak
    failed = drv.failed()
    numbers = drv.check()
    ok, checks = judge(numbers, spec["limits"])
    cell = spec["cell"]["name"]
    units = {m["name"]: m["unit"] for m in spec["bench"]["end_to_end"]
             if cell in m.get("workloads", [cell])}
    out = {"correct": ok and failed == 0 and res["attempted"] > 0,
           "attempted": res["attempted"], "failed": failed}
    if args.trace:
        metrics, busy, breakdown = per_layer(spec, events, res["work"],
                                             chips, device["kind"])
        device.update(busy)
        out.update(metrics=metrics, device=device, breakdown=breakdown)
    else:
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in res["metrics"].items() if k in units}
        metrics["setup_s"] = {"value": setup_s, "unit": units["setup_s"]}
        out.update(metrics=metrics, device=device)
    out["summary"] = dict(res["summary"], window_s=res["elapsed"],
                          import_s=import_s, **drv.setup_parts,
                          **getattr(drv, "info", {}))
    out["checks"] = checks
    return out


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def emit(out: dict) -> None:
    """Summary on an earlier line, each compared number beside its limit
    as the last lines of stderr, and the result as the last stdout line
    (``checks`` its last key)."""
    summary = out.pop("summary", None)
    if summary is not None:
        print("summary " + json.dumps(summary), flush=True)
    for k, c in out["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


def main(argv=None, require_chip=True, spec=None) -> int:
    args = parse(argv)
    sys.path[:0] = [str(ROOT)]
    try:
        out = run(args, require_chip=require_chip, spec=spec)
    except (Refused, FileNotFoundError) as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    emit(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
