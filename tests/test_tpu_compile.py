"""Compile the ``vcc_pgd`` kernels for a described TPU v5e chip.

No chip is needed: the TPU compiler compiles for a topology that is
described and not attached, and refuses what Mosaic cannot lower (the
interpret-mode tests cannot see that). Each kernel is compiled at the
fleet size the chip smoke run uses, 256 clusters, and its HLO must call
the kernel (``tpu_custom_call``). The sharded rollout is compiled over
the described chip's 4 devices with the kernels on. The topology is
described inside a
module fixture, never at import: only one process at a time may load the
TPU library, and under pytest-xdist each worker imports every test file.
Keep every chip compile in this one file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels.vcc_pgd import kernel as K

N, H = 256, 24
f32 = jnp.float32


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e):
    return SingleDeviceSharding(v5e.devices[0])


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, f32, sharding=sharding) for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_pgd_epoch_compiles(one_chip):
    wide, slim = (N, H), (N, 1)
    hlo = _compile(lambda *a: K.pgd_epoch_pallas(
        *a, temp=0.5, lambda_e=1.0, iters=80),
        [wide, wide, wide, wide, slim, slim, wide, wide, slim], one_chip)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("batch,n", [(44, N), (None, 300)],
                         ids=["cell-vmap44", "padded-lanes"])
def test_pgd_epoch_lowers_to_its_one_kernel(one_chip, batch, n):
    """The plain epoch at the cell's shape, 44 rollouts x (256, 24) under
    ``jax.vmap``, and at 300 clusters, whose last 128-lane tile holds 84
    dead lanes. Its lowering calls ``_pgd_kernel`` and no other Pallas
    kernel: the wrappers' transposes stay XLA ops (the chip benchmark
    refuses a program with any other kernel)."""
    import re

    lead = () if batch is None else (batch,)
    wide, slim = lead + (n, H), lead + (n, 1)

    def epoch(*a):
        return K.pgd_epoch_pallas(*a, temp=0.5, lambda_e=1.0, iters=80)

    fn = epoch if batch is None else jax.vmap(epoch)
    args = [jax.ShapeDtypeStruct(s, f32, sharding=one_chip)
            for s in [wide, wide, wide, wide, slim, slim, wide, wide, slim]]
    lowered = jax.jit(fn).lower(*args)
    assert set(re.findall(r'kernel_name = "(\w+)"', lowered.as_text())) \
        == {"_pgd_kernel"}
    assert "tpu_custom_call" in lowered.compile().as_text()


def test_joint_step_compiles(one_chip):
    wide, slim = (N, H), (N, 1)
    hlo = _compile(lambda *a: K.joint_step_pallas(
        *a, temp=0.5, lambda_e=1.0, drop_limit=0.5),
        [wide, slim, wide, wide, wide, slim, wide, wide, wide, slim, slim,
         slim, slim], one_chip)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("members", [8, 32])
def test_pgd_epoch_ens_compiles(one_chip, members):
    """The CVaR ensemble epoch at the risk sweep's K (sim.RISK_MEMBERS)."""
    wide, slim, ens = (N, H), (N, 1), (members, N, H)
    hlo = _compile(lambda *a: K.pgd_epoch_ens_pallas(
        *a, temp=0.5, lambda_e=1.0, risk_s=2.0, iters=80),
        [wide, ens, wide, ens, slim, slim, wide, wide, slim], one_chip)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("flags", [{}, {"joint_spatial": True},
                                   {"n_members": 8}],
                         ids=["plain", "joint", "ensemble"])
def test_sharded_rollout_compiles(v5e, monkeypatch, flags):
    """``rollout_batch_sharded`` over the chip's 4 devices, with each
    kernel on. ``shard_map`` types every value by the mesh axes it
    varies over, and the kernel calls and the loops inside them must
    keep that typing; the CPU tests run the jnp oracle and cannot see
    it."""
    from repro.kernels.vcc_pgd import ops
    from repro.sim import (SimConfig, build_batch, default_library,
                           mobility_sweep_library, risk_sweep_library,
                           rollout_batch_sharded)
    monkeypatch.setattr(ops, "tpu_available", lambda: True)
    days = 2
    cfg = SimConfig(n_clusters=8, n_campuses=2, n_zones=2, hist_days=14,
                    **flags)
    lib = (mobility_sweep_library if cfg.joint_spatial else
           risk_sweep_library if cfg.n_members > 1 else default_library)
    batch = build_batch(cfg, lib(days)[:2], [0, 1], days)
    mesh = Mesh(np.array(v5e.devices), ("batch",))
    spec = NamedSharding(mesh, P("batch"))
    sds = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=spec),
        batch)
    hlo = jax.jit(rollout_batch_sharded(cfg, days, mesh)).lower(
        sds).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_rollout_kernel_keeps_its_name_under_the_epoch_scope(
        one_chip, monkeypatch):
    """The one-chip rollout with the kernel on: the named scopes leave
    the Pallas kernel's name as it was (the chip benchmark checks it),
    and every kernel call of the compiled program sits under
    ``solver.pgd_epoch``, the scope a trace's kernel events are read
    by."""
    import re

    from repro.kernels.vcc_pgd import ops
    from repro.sim import (SimConfig, build_batch, default_library,
                           rollout_batch)
    monkeypatch.setattr(ops, "tpu_available", lambda: True)
    days = 2
    cfg = SimConfig(n_clusters=8, n_campuses=2, n_zones=2, hist_days=14)
    batch = build_batch(cfg, default_library(days)[:2], [0], days)
    sds = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        batch)
    lowered = jax.jit(rollout_batch(cfg, days)).lower(sds)
    assert set(re.findall(r'kernel_name = "(\w+)"', lowered.as_text())) \
        == {"_pgd_kernel"}
    calls = [ln for ln in lowered.compile().as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls
    for ln in calls:
        path = re.search(r'op_name="([^"]*)"', ln).group(1)
        assert "/solver.pgd_epoch/" in path, path


def test_mpc_rollout_calls_only_its_kernel(one_chip, monkeypatch):
    """The closed-loop rollout as the chip benchmark's MPC cell runs it
    (MPC on, rescan predictor) with the kernel on: the day-ahead epochs
    and the hourly suffix epochs both lower to ``_pgd_kernel`` and no
    other kernel, and each kernel call sits under ``solver.pgd_epoch``,
    the suffix ones under ``mpc.resolve`` too."""
    import re

    from repro.kernels.vcc_pgd import ops
    from repro.sim import (SimConfig, build_batch, forecast_bust_library,
                           rollout_batch)
    monkeypatch.setattr(ops, "tpu_available", lambda: True)
    days = 2
    cfg = SimConfig(n_clusters=8, n_campuses=2, n_zones=2, hist_days=14,
                    mpc=True)
    batch = build_batch(cfg, forecast_bust_library(days)[:2], [0], days)
    sds = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        batch)
    lowered = jax.jit(rollout_batch(cfg, days)).lower(sds)
    assert set(re.findall(r'kernel_name = "(\w+)"', lowered.as_text())) \
        == {"_pgd_kernel"}
    paths = [re.search(r'op_name="([^"]*)"', ln).group(1)
             for ln in lowered.compile().as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert all("/solver.pgd_epoch/" in p for p in paths), paths
    assert sum("/mpc.resolve/" in p for p in paths) == 1
    assert sum("/stage.optimize/" in p for p in paths) == 1
