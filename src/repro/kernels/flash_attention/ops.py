"""Public attention op: dispatches to the Pallas TPU kernel when available,
else the bounded-memory XLA path (``ref.attention_chunked``).
"""
from __future__ import annotations

from typing import Optional


from repro.kernels import tpu_available
from repro.kernels.flash_attention import ref as _ref


def attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
              softcap: Optional[float] = None, q_offset=0, length=None,
              scale: Optional[float] = None, q_chunk: int = 512,
              use_pallas: Optional[bool] = None, interpret: bool = False):
    """Multi-head (GQA) attention.

    q: (B, Sq, N, H); k, v: (B, Sk, K, H) with N % K == 0.
    causal/window/softcap/q_offset/length: see ``ref.attention_reference``.
    use_pallas: None = auto (TPU only). interpret: run Pallas in interpret
    mode (CPU validation).
    """
    if use_pallas is None:
        use_pallas = tpu_available()
    if use_pallas or interpret:
        from repro.kernels.flash_attention import kernel as _kernel
        return _kernel.flash_attention(
            q, k, v, causal=causal, window=window, softcap=softcap,
            q_offset=q_offset, length=length, scale=scale,
            interpret=interpret)
    return _ref.attention_chunked(
        q, k, v, causal=causal, window=window, softcap=softcap,
        q_offset=q_offset, length=length, scale=scale, q_chunk=q_chunk)
