"""Mesh factories. Functions, not module-level constants, so importing this
module never touches jax device state."""
from __future__ import annotations

import numpy as np

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e production mesh: 16x16 (256 chips) per pod; 2 pods = 512.

    Axes: (data, model) single pod; (pod, data, model) multi-pod. The dry-run
    forces 512 host platform devices; single-pod uses the first 256.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)}; the "
            "dry-run entrypoint must set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "any jax import")
    return jax.make_mesh(shape, axes, devices=devices)


def make_batch_mesh(n_devices=None):
    """1-D mesh over local devices with a single "batch" axis.

    The sim engine shards its (scenario x seed) rollout batch over this
    axis (`engine.rollout_batch_sharded`): rollouts are embarrassingly
    parallel, so a flat device line is the right topology. With one device
    (CPU tests) this degenerates to a 1-mesh — same code path, no-op
    sharding.
    """
    devices = jax.devices()
    n = len(devices) if n_devices is None else int(n_devices)
    if n < 1 or n > len(devices):
        raise ValueError(f"need 1..{len(devices)} devices, asked for {n}")
    return jax.make_mesh((n,), ("batch",), devices=devices[:n])


def make_local_mesh(model_parallel: int = 1):
    """Tiny mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    dp = n // model_parallel
    return jax.make_mesh((dp, model_parallel), ("data", "model"),
                         devices=jax.devices()[:dp * model_parallel])
