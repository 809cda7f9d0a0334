"""Jit'd wrapper: Pallas GLA scan on TPU, interpret elsewhere, jnp fallback."""
from __future__ import annotations

from repro.kernels import interpret_mode
from repro.kernels.gla_scan.gla_scan import gla_scan
from repro.models.linear_scan import gla_chunked


def gla(q, k, v, ld, *, inclusive: bool = True, chunk: int = 64,
        use_kernel: bool = True, interpret: bool | None = None):
    if not use_kernel:
        return gla_chunked(q, k, v, ld, inclusive=inclusive, chunk=chunk)
    if interpret is None:
        interpret = interpret_mode()
    return gla_scan(q, k, v, ld, inclusive=inclusive, chunk=chunk,
                    interpret=interpret)
