"""The program names its layers with ``jax.named_scope``, and the names
cost nothing.

* Every configuration's day step carries the stage, solver and (with MPC)
  controller scopes it runs, nested as the vocabulary says, and none it
  does not run; the rollout carries the burn-in and the ledger.
* Scopes are metadata: the compiled rollout runs the same instructions
  with them as without them, and the lowered text without debug info,
  which the collapse tests byte-compare, holds none of them.

The paths are read from the compiled program's metadata
(``metadata={op_name="jit(step)/.../stage.power/..."}``), where the
compiler has joined the paths of nested loop bodies; that is what a
trace's ops are matched to.
"""
import contextlib
import re

import jax
import pytest

from repro.core import stages
from repro.sim import (SimConfig, build_batch, build_params,
                       default_library, make_init, rollout_batch)
from repro.sim.engine import _day_xs

CFG_KW = dict(n_clusters=4, n_campuses=2, n_zones=2, pds_per_cluster=2,
              hist_days=14)
DAYS = 2
ELEMENT = re.compile(r"(?:^|[/(])((?:engine|stage|solver|mpc)\.\w+)(?=$|[/)])")
OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
DAY_STEP = {"stage.power", "stage.forecast", "stage.carbon",
            "stage.optimize", "stage.observe", "stage.slo", "stage.history",
            "solver.problem", "solver.spatial", "solver.pgd_epoch",
            "solver.dual_update"}


def scope_paths(lowered) -> list:
    """The op_name paths of the compiled program's instructions."""
    return OP_NAME.findall(lowered.compile().as_text())


def elements(paths) -> set:
    return {x for p in paths for x in ELEMENT.findall(p)}


def day_step_paths(**kw):
    cfg = SimConfig(**CFG_KW, **kw)
    p = build_params(cfg, default_library(DAYS)[0], 0, DAYS)
    s = jax.eval_shape(make_init(cfg), p)
    step = jax.jit(stages.make_day_step(cfg.stage_config()))
    return scope_paths(step.lower(p, s, _day_xs(p, 0)))


@pytest.mark.parametrize("kw,more", [
    ({}, set()),
    ({"telemetry": True}, {"stage.telemetry", "solver.diagnostics"}),
    ({"n_members": 2}, {"stage.ensembles"}),
    ({"mpc": True, "streaming": True}, {"mpc.hour", "mpc.resolve"}),
    ({"joint_spatial": True}, set()),
], ids=["paper", "telemetry", "ensembles", "mpc", "joint"])
def test_day_step_scopes(kw, more):
    paths = day_step_paths(**kw)
    found = elements(paths)
    assert found == DAY_STEP | more
    # the solve's parts sit inside the optimize stage, or (MPC) inside
    # the hourly re-solve of the observe stage (paths that do not start
    # at the program are those of reductions' and sorts' regions, which
    # run inside their op, never as ops of their own)
    for p in paths:
        if p.startswith("jit(") and "/solver." in p:
            assert ("/stage.optimize/" in p
                    or "/stage.observe/" in p and "/mpc.resolve/" in p), p
    if kw.get("joint_spatial"):
        assert any("/solver.spatial/" in p and "/solver.pgd_epoch/" in p
                   for p in paths)
    if kw.get("mpc"):
        assert any("/mpc.hour/" in p and "/mpc.resolve/" in p
                   and "/solver.pgd_epoch/" in p for p in paths)


def test_rollout_scopes_burnin_and_ledger():
    cfg = SimConfig(**CFG_KW)
    batch = build_batch(cfg, default_library(DAYS)[:2], [0], DAYS)
    paths = scope_paths(jax.jit(rollout_batch(cfg, DAYS)).lower(batch))
    found = elements(paths)
    assert found == DAY_STEP | {"engine.burnin", "engine.ledger"}
    burn = elements(p for p in paths if "engine.burnin" in p)
    # burn-in days draw the grid and admit load; the contract fit runs
    # in the burn-in but outside the power stage
    assert burn == {"engine.burnin", "stage.carbon", "stage.observe"}
    assert not any("engine.ledger" in p and "stage." in p for p in paths)


def test_scopes_are_not_in_the_compared_text():
    cfg = SimConfig(**CFG_KW)
    batch = build_batch(cfg, default_library(DAYS)[:1], [0], DAYS)
    text = jax.jit(rollout_batch(cfg, DAYS)).lower(batch).as_text()
    assert "stage.power" not in text and "engine.burnin" not in text


class _NoScope(contextlib.ContextDecorator):
    def __init__(self, _name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def instructions(text) -> list:
    """The compiled program's computations with every name, parameter
    name and metadata taken out: what runs, in order."""
    lines = text.splitlines()
    first = next(i for i, ln in enumerate(lines)
                 if re.match(r"^(%|ENTRY)", ln))
    out = []
    for ln in lines[first:]:
        ln = re.sub(r",? ?metadata=\{[^{}]*\}", "", ln)
        ln = re.sub(r"\bparam_[\d.]+", "p", ln)
        out.append(re.sub(r"%[\w.\-]+", "%_", ln))
    return out


def test_scopes_change_no_instruction(monkeypatch):
    cfg = SimConfig(**CFG_KW)
    batch = build_batch(cfg, default_library(DAYS)[:2], [0], DAYS)

    def compiled():
        return jax.jit(rollout_batch(cfg, DAYS)).lower(
            batch).compile().as_text()

    scoped = compiled()
    monkeypatch.setattr(jax, "named_scope", _NoScope)
    bare = compiled()
    assert "stage.power" in scoped and "stage.power" not in bare
    assert instructions(scoped) == instructions(bare)
