"""Jit'd public wrapper: kernel on TPU, interpret-mode kernel or jnp reference
elsewhere. ``make_update_fn`` plugs into ``ns_solver.ns_sample(update_fn=...)``."""
from __future__ import annotations

from repro.kernels import interpret_mode
from repro.kernels.ns_update.ns_update import ns_update_nd
from repro.kernels.ns_update.ref import ns_update_ref


def fused_ns_update(x0, u, a, w, *, use_kernel: bool = True,
                    interpret: bool | None = None):
    if not use_kernel:
        return ns_update_ref(x0, u, a, w)
    if interpret is None:
        interpret = interpret_mode()
    return ns_update_nd(x0, u, a, w, interpret=interpret)


def make_update_fn(use_kernel: bool = True, interpret: bool | None = None):
    def update_fn(x0, U, a_i, w_i):
        return fused_ns_update(x0, U, a_i, w_i, use_kernel=use_kernel,
                               interpret=interpret)
    return update_fn
