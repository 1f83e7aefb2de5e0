"""Jit'd wrapper for the chunkwise mLSTM kernel. The carried state
(C0, n0, m0) is a first-class kernel input, so prefill continuations are
exact with no host-side correction."""

from __future__ import annotations

from repro.kernels import interpret_default
from repro.kernels.mlstm_chunk import kernel as K


def mlstm_chunk(q, k, v, li, lf, C0, n0, m0, *, chunk: int = 128,
                interpret: bool | None = None):
    """Chunkwise mLSTM. Shapes as in models.xlstm.mlstm_chunkwise.

    Returns (h: (B,H,S,hd), (C, n, m) final state, fp32)."""
    if interpret is None:
        interpret = interpret_default()
    return K.mlstm_chunk_kernel(q, k, v, li, lf, C0, n0, m0,
                                chunk=int(chunk), interpret=bool(interpret))
