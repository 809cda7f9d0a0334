"""Tuned launch profile: XLA flag set + allocator preload for serving.

``serve.py --profile tuned`` re-executes the process once with a serving-
oriented environment before JAX initializes:

* ``TUNED_XLA_FLAGS`` — the XLA GPU flags production serving stacks ship
  with (triton gemm autotuning, the latency-hiding scheduler, highest-
  priority async stream). No-ops on CPU/TPU backends, which parse and
  ignore flags of another backend; a flag the installed XLA does not
  know at all aborts the process, so the set holds only known flags.
* tcmalloc — host-side allocator preload (``LD_PRELOAD``), applied only
  when one of the known shared-object paths exists on this machine. The
  large-alloc report threshold is raised so steady-state serving does not
  spam warnings for big host buffers.

Everything except the ``os.execv`` is pure and unit-testable:
``merge_xla_flags`` / ``apply_profile`` build the target environment
mapping without touching the process. ``maybe_reexec`` performs the
actual re-exec, guarded by the ``REPRO_TUNED_REEXEC`` sentinel so the
re-launched process runs straight through.
"""
from __future__ import annotations

import os
import sys

TUNED_XLA_FLAGS = (
    "--xla_gpu_triton_gemm_any=True",
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
)

TCMALLOC_CANDIDATES = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
)

_SENTINEL = "REPRO_TUNED_REEXEC"


def merge_xla_flags(existing: str, extra) -> str:
    """Merge ``extra`` flags into an ``XLA_FLAGS`` string, deduplicating by
    flag NAME (the text before ``=``) — a flag the user already set wins
    over the profile's default for it."""
    merged = []
    seen = set()
    for flag in list(existing.split()) + list(extra):
        name = flag.split("=", 1)[0]
        if name in seen:
            continue
        seen.add(name)
        merged.append(flag)
    return " ".join(merged)


def apply_profile(name: str, env=None) -> dict:
    """Return a COPY of ``env`` (default ``os.environ``) with the named
    profile applied. ``default`` returns the environment untouched;
    ``tuned`` merges ``TUNED_XLA_FLAGS`` into ``XLA_FLAGS`` and preloads
    tcmalloc when one of the candidate paths exists."""
    base = dict(os.environ if env is None else env)
    if name == "default":
        return base
    if name != "tuned":
        raise ValueError(f"unknown launch profile {name!r}")
    base["XLA_FLAGS"] = merge_xla_flags(base.get("XLA_FLAGS", ""),
                                        TUNED_XLA_FLAGS)
    lib = next((p for p in TCMALLOC_CANDIDATES if os.path.exists(p)), None)
    if lib is not None:
        preload = base.get("LD_PRELOAD", "")
        if lib not in preload.split(":"):
            base["LD_PRELOAD"] = f"{preload}:{lib}".strip(":")
        base.setdefault("TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD",
                        "60000000000")
    return base


def maybe_reexec(profile: str, argv=None, log=print) -> None:
    """Re-exec the current interpreter once under the tuned environment.

    No-op for the default profile, and for the re-launched child (the
    ``REPRO_TUNED_REEXEC`` sentinel breaks the loop). ``LD_PRELOAD`` and
    ``XLA_FLAGS`` must be set BEFORE the dynamic loader / XLA parse them,
    which for an already-running process means replacing it."""
    if profile == "default" or os.environ.get(_SENTINEL):
        return
    env = apply_profile(profile)
    env[_SENTINEL] = "1"
    argv = list(sys.argv if argv is None else argv)
    log(f"re-exec under '{profile}' profile: "
        f"XLA_FLAGS={env.get('XLA_FLAGS', '')!r}"
        + (f", LD_PRELOAD={env['LD_PRELOAD']}" if "LD_PRELOAD" in env
           else " (tcmalloc not found, skipped)"))
    os.execve(sys.executable, [sys.executable, "-m", "repro.launch.serve"]
              + argv[1:], env)
