"""The least work a step needs, computed from shapes alone.

Every count is what the mathematics of the step requires, not what a given
program does: a guided flow step is two forwards of operations, padding
rows are the caller's to count or not, and an attention over a causal
prefix counts each query against the keys before it. A roofline share
built on these numbers therefore cannot pass 100% whatever implements the
step.

What the blocks need comes from the block module that the configuration
names (``Model.stack``: ``position_params``, ``causal_attn_flops``,
``matrix_bytes``); what the flow head around them needs is counted here.
``model`` is a ``bench.model.Model``: ``model.c`` is the configuration
dict of ``configs/*.json`` (``d_model``, ``latent_dim``, ``dtype`` and
what its stack reads).
"""
from __future__ import annotations


def param_bytes(c: dict) -> int:
    """Bytes of one parameter in the served dtype."""
    import jax.numpy as jnp

    return jnp.dtype(c["dtype"]).itemsize


# -- flow: the backbone as a velocity field ----------------------------------


def flow_forward_flops(model, rows: int, positions: int) -> float:
    """One unguided backbone forward over ``rows`` latent sequences of
    ``positions`` positions: input and output projections, the blocks,
    causal attention, and the time-embedding MLP once per call."""
    c, stack = model.c, model.stack
    d, lat = c["d_model"], c["latent_dim"]
    per_pos = 2.0 * (stack.position_params(c) + 2 * lat * d)
    return (rows * positions * per_pos
            + rows * stack.causal_attn_flops(c, positions, 0)
            + 2.0 * 2 * d * d)


def flow_request_flops(model, budget: int, positions: int,
                       guided: bool) -> float:
    """Operations one sample needs: ``budget`` steps of one row, two
    forwards each under guidance. No padding; a joiner's prefix steps are
    among its budget's."""
    return budget * (2 if guided else 1) * (
        flow_forward_flops(model, 1, positions)
        - 2.0 * 2 * model.c["d_model"] ** 2)


def flow_weight_bytes(model, tokens: int) -> int:
    """Bytes of matrices one forward over ``tokens`` tokens reads at
    least: the blocks' (``stack.matrix_bytes``; for sparse experts it
    may depend on the tokens) plus the latent input and output projections,
    in the served dtype. The time embedding is left out, which only
    lowers the floor."""
    c = model.c
    return (model.stack.matrix_bytes(c, tokens)
            + param_bytes(c) * 2 * c["latent_dim"] * c["d_model"])
