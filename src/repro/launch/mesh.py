"""Mesh construction.

Meshes are built by FUNCTIONS (not module-level constants) so that importing
this module never touches jax device state; the dry-run sets XLA_FLAGS for
512 host devices before any jax import.

Every mesh uses ``Auto`` axis types: the sharding rules in
``distributed.sharding`` and the model code are written for GSPMD
propagation, not for JAX's explicit-sharding mode (the ``jax.make_mesh``
default since JAX 0.7).

Host:       (1, n) over the n local devices, axes ("data", "model") — the
            backbone tensor-parallel over one host's chips (1x1 on a CPU).
Single pod: (16, 16) = 256 chips, axes ("data", "model").
Multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model") — "pod"
composes with "data" as the batch/FSDP axis; "model" stays intra-pod (tensor
parallelism needs the fast ICI domain, the pod axis crosses DCI).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """(1, n) mesh over every local device: the backbone is sharded on
    ``model`` across the host's chips (a degenerate 1x1 mesh on one CPU)."""
    devices = jax.local_devices()
    return _auto_mesh((1, len(devices)), ("data", "model"), devices=devices)


def batch_axes(mesh) -> tuple[str, ...]:
    """The composed batch/FSDP axis: ("pod","data") on multi-pod meshes."""
    names = mesh.axis_names
    return tuple(n for n in names if n in ("pod", "data"))
