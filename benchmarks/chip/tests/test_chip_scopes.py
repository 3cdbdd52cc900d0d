"""Device time by the program's named scopes (``scopes.py``): on a
hand-made trace and HLO with known answers, on a recorded TPU trace of
the tiny sweep, and on the tiny sweep's program compiled here."""
import json
import re
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[3]))

from benchmarks.chip import reduce, scopes  # noqa: E402
from chip_scope_cases import DEV, HLO, handmade  # noqa: E402

DAY = "jit(run)/vmap()/while/body"
# the layer selections the per-layer readers are to make:
# (element, excluded elements, Pallas events counted)
LAYERS = {"burnin": ("engine.burnin", (), True),
          "power_fit": ("stage.power", ("engine.burnin",), True),
          "observe": ("stage.observe", ("engine.burnin",), True),
          "solver_other": ("stage.optimize", (), False)}


def device(events):
    return reduce.Device(events, DEV, *reduce.window(events))


def test_scope_map_on_handmade_hlo():
    m = scopes.scope_map(HLO)
    assert m["mul.2"] == f"{DAY}/stage.power/mul"
    assert m["add.6"] == f"{DAY}/stage.optimize/add"
    assert m["while.13"] == "jit(run)/vmap(engine.burnin)/while"
    assert m["x.12"] == "x"
    # a fusion without metadata: its fused computation's root
    assert m["fusion.5"] == f"{DAY}/stage.power/mul"
    # a copy without metadata: the scope its computation's ops share
    assert m["copy.7"] == f"{DAY}/stage.optimize"
    assert m["param_0.1"] == f"{DAY}/stage.power"
    assert m["c.10"] == ""
    assert "FileNames" not in m and "1" not in m


def test_instr_keeps_the_instance_number():
    assert scopes.instr('%closed_call.14 = f32[44] custom-call(...)') \
        == "closed_call.14"
    assert scopes.instr("%solver.pgd_epoch.4 = f32[4,8,24] custom-call()") \
        == "solver.pgd_epoch.4"
    assert scopes.instr("fusion.12") == "fusion.12"
    assert reduce.short("%fusion.12 = f32[8] fusion(...)") == "fusion"


@pytest.mark.parametrize("path,element,held", [
    (f"{DAY}/stage.power/sort", "stage.power", True),
    ("stage.power", "stage.power", True),
    (f"{DAY}/stage.power_fit/sort", "stage.power", False),
    (f"{DAY}/xstage.power/sort", "stage.power", False),
    ("jit(run)/vmap(engine.burnin)/while", "engine.burnin", True),
    ("jit(run)/vmap(engine.burnin_x)/while", "engine.burnin", False),
])
def test_whole_path_elements(path, element, held):
    assert scopes.holds(path, element) is held


def test_elements_of_a_path():
    assert scopes.elements("jit(run)/vmap(engine.burnin)/while/body/"
                           "stage.observe/jit(_normal)/erf") == [
        "engine.burnin", "stage.observe"]
    assert scopes.elements(f"{DAY}/closed_call/add") == []


def test_scoped_union_burnin_against_horizon():
    events, m = handmade()
    d = device(events)
    assert d.busy_ns == 90
    assert scopes.intervals(d, m, "engine.burnin") == [[2, 16]]
    assert scopes.scoped_ns(d, m, "stage.observe") == 14
    assert scopes.scoped_ns(d, m, "stage.observe", ("engine.burnin",)) == 8
    assert scopes.scoped_ns(d, m, "stage.power", ("engine.burnin",)) == 8


def test_scoped_union_leaves_kernel_and_containers_out():
    events, m = handmade()
    d = device(events)
    # the loops' own paths hold engine.burnin, but they are containers
    assert scopes.scoped_ns(d, m, "engine.burnin") == 14
    assert scopes.scoped_ns(d, m, "stage.optimize") == 35
    assert scopes.scoped_ns(d, m, "stage.optimize", pallas=False) == 5
    assert scopes.intervals(d, m, "solver.pgd_epoch") == [[33, 63]]
    assert [scopes.instr(e[2]) for e in scopes.leaves(d)
            if e[2].startswith("%while")] == []


def test_layer_selections_per_fleet_day():
    events, m = handmade()
    d = device(events)
    got = {k: scopes.ms_per_unit([d], m, 10, el, ex, pal)
           for k, (el, ex, pal) in LAYERS.items()}
    assert got == pytest.approx({"burnin": 14e-7, "power_fit": 8e-7,
                                 "observe": 8e-7, "solver_other": 5e-7})
    kernel = sum(e[4] for e in d.pallas())
    assert sum(got.values()) <= (d.busy_ns - kernel) / 1e6 / 10


@pytest.mark.parametrize("layer", sorted(LAYERS))
def test_layer_selections_find_nothing_without_fleet_days(layer):
    events, m = handmade()
    el, ex, pal = LAYERS[layer]
    assert scopes.ms_per_unit([device(events)], m, None, el, ex, pal) \
        is None


@pytest.mark.parametrize("layer", sorted(LAYERS))
def test_layer_selections_find_nothing_in_a_program_without_scopes(layer):
    """A program without scopes (the parent of the scopes) reads None,
    and does not raise."""
    events, m = handmade()
    bare = {k: re.sub(r"(engine|stage|solver)\.\w+/?", "", p)
            for k, p in m.items()}
    el, ex, pal = LAYERS[layer]
    assert scopes.ms_per_unit([device(events)], bare, 10, el, ex, pal) \
        is None


def test_ms_by_scope_with_remainders():
    events, m = handmade()
    d = device(events)
    t = {k: v * 1e6 for k, v in scopes.ms_by_scope([d], m, 1).items()}
    assert t == pytest.approx({
        "engine.burnin": 14, "stage.carbon": 4, "stage.observe": 14,
        "stage.power": 8, "stage.forecast": 3, "stage.optimize": 35,
        "solver.problem": 2, "solver.pgd_epoch": 30,
        "solver.dual_update": 2, "engine.ledger": 2,
        "unscoped": 2, "unresolved": 3})
    scoped = reduce.union([iv for k in t if "." in k
                           for iv in scopes.intervals(d, m, k)])
    covered = sum(e - s for s, e in scoped) + t["unscoped"] \
        + t["unresolved"]
    # the rest of busy time is the loops' own, between their leaves
    assert d.busy_ns - covered == 15


def recorded():
    path = Path(__file__).with_name("data") / "trace_tiny_sweep.json"
    data = json.loads(path.read_text())
    lo, hi = data["window"]
    events = [[data["device"], "XLA Ops", n, s, d] for n, s, d in
              data["ops"]]
    events.append([reduce.HOST, "python", "bench.window", lo, hi - lo])
    return events, data["scopes"]


def test_recorded_tiny_sweep_resolves_to_scopes():
    """One simulated day (and the burn-in) of the tiny sweep on a v5e."""
    events, m = recorded()
    d = reduce.Device(events, reduce.devices(events)[0],
                      *reduce.window(events))
    pallas = d.pallas()
    assert len(pallas) == 20                    # 20 outer rounds, one day
    assert all(scopes.holds(m[scopes.instr(e[2])], "solver.pgd_epoch")
               for e in pallas)
    t = scopes.ms_by_scope([d], m, 1)
    assert t["unresolved"] == 0
    assert t["engine.burnin"] > 0 and t["stage.power"] > 0
    names = [k for k in t if k not in ("unscoped", "unresolved")]
    scoped = reduce.union([iv for k in names
                           for iv in scopes.intervals(d, m, k)])
    scoped_ns = sum(e - s for s, e in scoped)
    assert abs(scoped_ns + t["unscoped"] * 1e6 - d.busy_ns) \
        < 0.01 * d.busy_ns
    kernel = sum(e[4] for e in pallas)
    # the epoch's scope also holds the kernel's operand layout and copies
    assert kernel <= t["solver.pgd_epoch"] * 1e6 < 1.01 * kernel
    layers = [scopes.ms_per_unit([d], m, 1, *LAYERS[k]) for k in LAYERS]
    assert all(v > 0 for v in layers)
    assert sum(layers) * 1e6 <= d.busy_ns - kernel


def test_tiny_sweep_program_holds_the_scopes():
    """The tiny sweep's rollout compiled here: the metadata holds every
    element a layer selection names, the solve's PGD epochs (the loop of
    80 steps the jnp oracle runs here) sit under solver.pgd_epoch, and
    the map resolves every instruction."""
    import jax

    from chip_tiny import rollout_spec

    from benchmarks.chip import traffic
    from repro.sim import SimConfig, SimParams, rollout_batch

    spec = rollout_spec()
    fleet, t = spec["config"]["fleet"], spec["traffic"]
    batch = traffic.rollout_batch(fleet, t["scenarios"],
                                  traffic.sub_seeds(7, 0, 1), t["days"])
    params = SimParams(**{k: batch[k] for k in SimParams._fields
                          if k in batch})
    run = jax.jit(rollout_batch(SimConfig(**fleet), t["days"]))
    text = run.lower(params).compile().as_text()
    m = scopes.scope_map(text)
    found = {x for p in m.values() for x in scopes.elements(p)}
    assert {el for el, ex, _ in LAYERS.values()} | {
        x for _, ex, _ in LAYERS.values() for x in ex} <= found
    assert {"engine.ledger", "stage.forecast", "stage.carbon", "stage.slo",
            "stage.history", "solver.problem", "solver.spatial",
            "solver.pgd_epoch", "solver.dual_update"} <= found
    epochs = [ln for ln in text.splitlines()
              if " while(" in ln and '"known_trip_count":{"n":"80"}' in ln]
    assert epochs
    assert all(scopes.holds(m[re.search(r"%(\S+) = ", ln).group(1)],
                            "solver.pgd_epoch") for ln in epochs)
    named = re.findall(r"^\s+(?:ROOT\s+)?%(\S+) = ", text, re.M)
    assert set(named) <= set(m)
