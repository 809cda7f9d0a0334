"""Pallas TPU kernels for the framework's measured compute hot-spots.

Each kernel package: <name>.py (pl.pallas_call + explicit BlockSpec VMEM
tiling), ops.py (jit'd wrapper with kernel/ref dispatch), ref.py (pure-jnp
oracle used by the allclose sweep tests).

  ns_update        — the paper's NS update rule x_{i+1} = a x0 + sum b_j u_j
                     fused into one HBM pass over the velocity buffer
  flash_attention  — blocked online-softmax causal GQA attention (no S x S
                     materialization; the dominant prefill pathology)
  gla_scan         — chunked gated linear recurrence for RWKV6/Mamba2 with
                     the decay cube resident in VMEM (the dominant SSM-train
                     pathology)

Kernel entry points take ``interpret`` with no default. The ops wrappers
decide it in ONE place, ``interpret_mode()``: compiled for the chip on a TPU
backend, the Pallas interpreter everywhere else (CPU tests).
"""
import jax


def interpret_mode() -> bool:
    """True unless the default backend is a TPU: Pallas TPU kernels run in
    the interpreter off-chip."""
    return jax.default_backend() != "tpu"
