"""The least work a step needs, computed from shapes alone.

Every count is what the mathematics of the step requires, not what a given
program does: a guided flow step is two forwards of operations, padding
rows are the caller's to count or not, and an attention over a causal
prefix counts each query against the keys before it. A roofline share
built on these numbers therefore cannot pass 100% whatever implements the
step. ``c`` is a configuration dict of the benchmark's ``configs/*.json``
(``n_layers``, ``d_model``, ``n_heads``, ``n_kv_heads``, ``head_dim``,
``d_ff``, ``latent_dim``).
"""
from __future__ import annotations


def attn_params(c: dict) -> int:
    d, h, kv, hd = c["d_model"], c["n_heads"], c["n_kv_heads"], c["head_dim"]
    return d * h * hd + 2 * d * kv * hd + h * hd * d


def mlp_params(c: dict) -> int:
    return 3 * c["d_model"] * c["d_ff"]


def layer_params(c: dict) -> int:
    """Matrix parameters of one dense block (norm scales are O(d))."""
    return attn_params(c) + mlp_params(c)


def causal_attn_flops(c: dict, new: int, past: int) -> float:
    """Score and value products of ``new`` queries that follow ``past``
    cached positions, each seeing itself and what precedes it: query i
    attends to ``past + i + 1`` keys, twice (QK^T and PV), over all
    layers and heads."""
    keys = new * past + new * (new + 1) / 2
    return 4.0 * c["n_layers"] * c["n_heads"] * c["head_dim"] * keys


# -- flow: the backbone as a velocity field ----------------------------------


def flow_forward_flops(c: dict, rows: int, positions: int) -> float:
    """One unguided backbone forward over ``rows`` latent sequences of
    ``positions`` positions: input and output projections, the blocks,
    causal attention, and the time-embedding MLP once per call."""
    d, lat = c["d_model"], c["latent_dim"]
    per_pos = 2.0 * (c["n_layers"] * layer_params(c) + 2 * lat * d)
    return (rows * positions * per_pos
            + rows * causal_attn_flops(c, positions, 0)
            + 2.0 * 2 * d * d)


def flow_request_flops(c: dict, budget: int, positions: int,
                       guided: bool) -> float:
    """Operations one sample needs: ``budget`` steps of one row, two
    forwards each under guidance. No padding; a joiner's prefix steps are
    among its budget's."""
    return budget * (2 if guided else 1) * (
        flow_forward_flops(c, 1, positions) - 2.0 * 2 * c["d_model"] ** 2)
