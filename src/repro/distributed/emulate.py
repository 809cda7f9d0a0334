"""Emulated multi-host devices: the fleet tier's CI substrate.

BNS solver artifacts are tiny (<200 params), so a serving fleet replicates
the solver freely and the hard part — sharded request queues, affinity
routing, work stealing, host join/leave — is pure distribution logic. That
logic is testable on a laptop/CI runner by splitting ONE CPU into many XLA
host-platform devices (the ``--xla_force_host_platform_device_count``
trick; see bayespec's ``config.py`` in SNIPPETS.md) and giving each
emulated "host" its own single-device mesh:

    from repro.distributed import emulate_hosts, host_meshes
    emulate_hosts(8)            # BEFORE anything initializes jax
    import jax                  # now sees 8 CpuDevices
    meshes = host_meshes(4)     # 4 per-host meshes, 2 devices each

The flag is only read when jax creates its backends, so ``emulate_hosts``
must run first — and because the silent failure mode (set the env var,
nothing happens, every "multi-host" test quietly runs on one device) is a
footgun, it RAISES if jax is already initialized instead of no-opping.
CI sets ``XLA_FLAGS`` at the job level for the same reason (conftest.py
imports jax at collection time, long before any test body runs).
"""
from __future__ import annotations

import os
import sys

_FLAG = "--xla_force_host_platform_device_count"


def jax_initialized() -> bool:
    """Whether jax has created a backend yet (reading devices, running any
    computation). Merely ``import jax`` does NOT initialize — XLA_FLAGS can
    still take effect after it."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return bool(xla_bridge._backends)


def emulate_hosts(n: int) -> int:
    """Split the CPU platform into ``n`` XLA devices (one per emulated
    fleet host). Must run before jax initializes its backends; raises
    RuntimeError (never silently no-ops) when it is too late for the flag
    to matter. Any other XLA_FLAGS already set are preserved."""
    if n < 1:
        raise ValueError(f"need at least 1 emulated host, got {n}")
    if jax_initialized():
        raise RuntimeError(
            f"emulate_hosts({n}): jax backends are already initialized, so "
            f"{_FLAG} would be silently ignored. Call emulate_hosts before "
            "any jax.devices()/jit/device_put (e.g. first thing in main), "
            "or set XLA_FLAGS in the environment before the process starts "
            f"(CI does: XLA_FLAGS={_FLAG}={n}).")
    kept = [t for t in os.environ.get("XLA_FLAGS", "").split()
            if not t.startswith(f"{_FLAG}=")]
    os.environ["XLA_FLAGS"] = " ".join(kept + [f"{_FLAG}={n}"])
    return n


def host_meshes(n: int, axes: tuple = ("data", "model")):
    """Partition the visible devices into ``n`` per-host meshes (the fleet
    places each host gateway's params on its own mesh). Devices split
    evenly along the first (data) axis; the remaining axes get size 1 —
    intra-host tensor parallelism composes later via real mesh shapes.
    Raises when fewer than ``n`` devices are visible, pointing at
    ``emulate_hosts`` (the footgun this module exists to defuse)."""
    import jax
    import numpy as np
    from jax.sharding import AxisType, Mesh

    if n < 1:
        raise ValueError(f"need at least 1 host, got {n}")
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"host_meshes({n}): only {len(devices)} device(s) visible. "
            f"Call repro.distributed.emulate_hosts({n}) before jax "
            f"initializes (or set XLA_FLAGS={_FLAG}={n}).")
    per = len(devices) // n
    shape = (per,) + (1,) * (len(axes) - 1)
    return [Mesh(np.asarray(devices[i * per:(i + 1) * per]).reshape(shape),
                 axes, axis_types=(AxisType.Auto,) * len(axes))
            for i in range(n)]
