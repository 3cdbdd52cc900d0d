"""Activation-sharding constraints that models can apply without knowing the
mesh.

XLA's sharding propagation through ``while`` loops (scan over layers, query
chunks, loss chunks) can drop activation shardings and silently replicate the
batch across the model axis. The fix is explicit anchors inside scan bodies.
Models call ``constrain(x, 'batch', None, 'model', None)``; the launcher
activates a context mapping 'batch'/'model' to concrete mesh axes. Without an
active context (unit tests, single-device runs) it is a no-op.
"""
from __future__ import annotations

import contextlib
import contextvars

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

_CTX: contextvars.ContextVar = contextvars.ContextVar("act_sharding",
                                                      default=None)


@contextlib.contextmanager
def activation_sharding(mesh):
    """Activate activation constraints for a mesh with a 'model' axis and
    'data' (+ optional 'pod') batch axes."""
    axes = tuple(mesh.axis_names)
    batch = ("pod", "data") if "pod" in axes else ("data",)
    ctx = {
        "batch": batch,
        "batch_size": int(__import__("numpy").prod(
            [mesh.shape[a] for a in batch])),
        "model": "model",
        "model_size": int(mesh.shape["model"]),
        "mesh": mesh,
    }
    token = _CTX.set(ctx)
    try:
        yield
    finally:
        _CTX.reset(token)


def active() -> bool:
    return _CTX.get() is not None


def current_mesh():
    c = _CTX.get()
    return c["mesh"] if c else None


def batch_shards() -> int:
    """Number of ways the batch axes shard the leading dim (1 if inactive)."""
    c = _CTX.get()
    return c["batch_size"] if c else 1


def _spec(c, shape, dims) -> P:
    spec = []
    for i, d in enumerate(dims):
        if d == "batch" and shape[i] % c["batch_size"] == 0:
            spec.append(c["batch"])
        elif d == "model" and shape[i] % c["model_size"] == 0:
            spec.append(c["model"])
        else:
            spec.append(None)
    return P(*spec)


def constrain(x, *dims):
    """dims entries: 'batch' | 'model' | None, one per array dim.
    Dims whose size does not divide the named axis are left unconstrained."""
    c = _CTX.get()
    if c is None or x is None or not hasattr(x, "ndim"):
        return x
    if x.ndim != len(dims):
        return x
    try:
        return jax.lax.with_sharding_constraint(x, _spec(c, x.shape, dims))
    except Exception:       # no ambient mesh (e.g. eager test) -> no-op
        return x


def out_sharding(shape, *dims):
    """The sharding ``constrain(x, *dims)`` would give an array of
    ``shape``, as an ``out_sharding`` for ops whose output sharding is
    ambiguous (a contraction over a sharded dim); None when inactive."""
    c = _CTX.get()
    if c is None:
        return None
    return NamedSharding(c["mesh"], _spec(c, shape, dims))


def constrain_tree(tree, *dims):
    return jax.tree.map(lambda x: constrain(x, *dims), tree)
