"""Public GLA op: Pallas TPU kernel when available, else chunked XLA path."""
from __future__ import annotations

from typing import Optional


from repro.kernels import tpu_available
from repro.kernels.linear_scan import ref as _ref

gla_step = _ref.gla_step  # decode step is O(1); no kernel needed


def gla(q, k, v, log_decay, *, bonus=None, strict: bool = False,
        chunk: int = 64, initial_state=None,
        use_pallas: Optional[bool] = None, interpret: bool = False):
    """Chunked gated linear attention. See ``ref.gla_chunked`` for shapes."""
    if use_pallas is None:
        use_pallas = tpu_available()
    if use_pallas or interpret:
        from repro.kernels.linear_scan import kernel as _kernel
        return _kernel.gla_pallas(
            q, k, v, log_decay, bonus=bonus, strict=strict, chunk=chunk,
            initial_state=initial_state, interpret=interpret)
    return _ref.gla_chunked(q, k, v, log_decay, bonus=bonus, strict=strict,
                            chunk=chunk, initial_state=initial_state)
