"""What decides ``correct`` in the rollout cells, at a size a CPU test can
hold: a sound run passes; the reference with its solves in bfloat16 in
the program's place fails; and so does the timed path broken underneath
in each way the cell can break."""
import jax
import jax.numpy as jnp
import pytest

from chip_tiny import rollout_spec, run_cell

import repro.sim  # noqa: E402  (chip_tiny puts the program on the path)


def test_sound_run_is_correct():
    out = run_cell(rollout_spec())
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"


def test_bfloat16_control_is_not_correct():
    out = run_cell(rollout_spec(), "--control", "1")
    assert not out["correct"], out["checks"]


def _unchanged(out):
    state, led, traj = out
    return state, jax.tree.map(jnp.zeros_like, led), traj


def _half(out):
    def dup(a):
        h = a.shape[0] // 2
        return jnp.concatenate([a[:h], a[:h]])
    return jax.tree.map(dup, out)


def _altered(out):
    state, led, traj = out
    return state, led._replace(carbon_kg=led.carbon_kg * 1.05), traj


@pytest.mark.parametrize("fault", [_unchanged, _half, _altered],
                         ids=["state_unchanged", "half_batch",
                              "answer_altered"])
def test_broken_rollout_is_not_correct(monkeypatch, fault):
    real = repro.sim.rollout_batch

    def broken(cfg, days):
        run = real(cfg, days)
        return jax.jit(lambda p: fault(run(p)))

    monkeypatch.setattr(repro.sim, "rollout_batch", broken)
    out = run_cell(rollout_spec())
    assert not out["correct"], out["checks"]
