"""Pallas TPU kernels (+ jnp oracles) for the framework's compute hot-spots.

- ``flash_attention``: block-tiled online-softmax attention (workload layer).
- ``linear_scan``: chunked gated linear attention (RWKV6 / Mamba2 mixers).
- ``vcc_pgd``: fused projected-gradient step of the paper's fleetwide VCC
  optimizer (the CICS day-ahead planning hotspot, §III-C of the paper).

Each kernel package ships ``kernel.py`` (pl.pallas_call + explicit BlockSpec
VMEM tiling), ``ops.py`` (jit'd dispatching wrapper) and ``ref.py`` (pure-jnp
oracle). Kernels are validated on CPU via ``interpret=True``.
"""
import jax


def tpu_available() -> bool:
    """The kernels' auto-dispatch (``use_pallas=None``): the Pallas kernel
    where JAX's default backend is a TPU, the jnp oracle elsewhere."""
    return jax.default_backend() == "tpu"
