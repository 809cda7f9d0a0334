"""Jit'd wrappers for the attention kernels with layout adaptation to the
model's conventions and kernel/ref dispatch."""
from __future__ import annotations

from repro.kernels import interpret_mode
from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.paged_attention import paged_attention
from repro.kernels.flash_attention.ref import attention_ref, paged_attention_ref


def attend(q, k, v, *, causal: bool = True, use_kernel: bool = True,
           interpret: bool | None = None):
    """q: (B, L, H, hd); k, v: (B, L, KV, hd) — model layout."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if not use_kernel:
        out = attention_ref(qt, kt, vt, causal=causal)
    else:
        if interpret is None:
            interpret = interpret_mode()
        out = flash_attention(qt, kt, vt, causal=causal, interpret=interpret)
    return out.transpose(0, 2, 1, 3)


def paged_attend(q, k_pages, v_pages, block_table, lengths, *,
                 use_kernel: bool = True, interpret: bool | None = None):
    """One-token paged decode attention; q: (B, KV, G, hd) grouped heads,
    k_pages/v_pages: (num_pages, KV, page_size, hd), block_table: (B, nb),
    lengths: (B,). Kernel/oracle dispatch mirrors ``attend``."""
    if not use_kernel:
        return paged_attention_ref(q, k_pages, v_pages, block_table, lengths)
    if interpret is None:
        interpret = interpret_mode()
    return paged_attention(q, k_pages, v_pages, block_table, lengths,
                           interpret=interpret)
