"""What decides ``correct`` in the closed-loop cell, at a size a CPU test
can hold: a sound run passes; the reference with its solves in bfloat16
in the program's place fails, and so do the open-loop program and a
ledger that never advances. The reference's hour loop with every trigger
off is the trusted open-loop reference's day."""
import io
import json
import math
from contextlib import redirect_stdout

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip_tiny import SEED, spec_of

import repro.sim  # noqa: E402  (chip_tiny puts the program on the path)
from benchmarks.chip import (reference, reference_mpc, run,  # noqa: E402
                             traffic)


def mpc_spec(**fleet):
    """``fleet256_mpc.bust7`` cut to 8 clusters, 2 campuses, 2 zones, a
    14-day burn-in, 2 scenarios x 2 seeds x 4 days."""
    spec = spec_of("fleet256_mpc.bust7", "fleet256_mpc", "bust7")
    spec["config"]["fleet"].update(n_clusters=8, n_campuses=2, n_zones=2,
                                   hist_days=14, **fleet)
    spec["traffic"].update(days=4, seeds_per_scenario=2,
                           scenarios=spec["traffic"]["scenarios"][:2])
    return spec


def run_mpc(spec, *extra):
    """(result, summary) of the cell through the harness, without the
    chip."""
    buf = io.StringIO()
    argv = ["--workload", spec["cell"]["name"], "--seed", str(SEED),
            "--seconds", "0.5", "--trace", "0", *extra]
    with redirect_stdout(buf):
        assert run.main(argv, require_chip=False, spec=spec) == 0
    lines = buf.getvalue().strip().splitlines()
    summary = [json.loads(ln[len("summary "):]) for ln in lines
               if ln.startswith("summary ")]
    return json.loads(lines[-1]), (summary or [{}])[0]


def test_sound_run_is_correct():
    out, summary = run_mpc(mpc_spec())
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    # both sides re-plan; triggers flip on rounding, so only roughly alike
    share = summary["recourse_share_program"]
    assert 0 < share < 1
    assert math.isclose(share, summary["recourse_share_reference"],
                        rel_tol=0.1)


def test_bfloat16_control_is_not_correct():
    out, _ = run_mpc(mpc_spec(), "--control", "1")
    assert not out["correct"], out["checks"]


def test_open_loop_program_is_not_correct():
    """The fleet256 program (mpc=False) against the closed-loop
    reference: the check covers the hour loop."""
    out, summary = run_mpc(mpc_spec(mpc=False))
    assert not out["correct"], out["checks"]
    assert "recourse_share_program" not in summary


def test_ledger_left_at_zero_is_not_correct(monkeypatch):
    real = repro.sim.rollout_batch

    def broken(cfg, days):
        roll = real(cfg, days)

        def run(p):
            state, led, traj = roll(p)
            return state, jax.tree.map(jnp.zeros_like, led), traj
        return run

    monkeypatch.setattr(repro.sim, "rollout_batch", broken)
    out, _ = run_mpc(mpc_spec())
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("days,rtol", [(1, 0.0), (2, 1e-4)])
def test_hour_loop_without_triggers_is_the_open_loop(days, rtol):
    """No trigger can fire: every re-solve is discarded and each day is
    the open-loop reference's day. The first day is bitwise the same;
    after it the two histories drift by float32 rounding (1e-5 at the
    second day's backlog, CPU)."""
    spec = mpc_spec()
    fleet, t = spec["config"]["fleet"], spec["traffic"]
    off = dict(spec["config"]["mpc"], mape_trigger=math.inf,
               eta_trigger=math.inf, surge_trigger=math.inf)
    batch = traffic.rollout_batch(fleet, t["scenarios"],
                                  traffic.sub_seeds(SEED, 0, 1), days)
    row = jax.tree.map(lambda a: a[0], batch)
    solver = spec["config"]["solver"]
    want = jax.jit(lambda r: reference.simulate(
        r, fleet, solver, days))(row)
    got = jax.jit(lambda r: reference_mpc.simulate(
        r, fleet, solver, off, days))(row)
    assert float(got["recourse_hours"].sum()) == 0.0
    for k, v in want.items():
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(v),
                                   rtol=rtol, atol=0, err_msg=k)
