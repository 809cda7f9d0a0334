"""A configuration file of the benchmark, as the harness uses it.

``configs/<name>.json`` holds the sizes as they are run (``model``), the
source, every key ``reduced`` or ``corrected`` from it with the published
value, what is ``assumed``, the deployment it stands for, and ``smoke``
sizes for the CPU rehearsal. ``arch`` names the program's own
configuration that these sizes replace field by field.

A key of ``model`` whose ``ModelConfig`` field is itself a dataclass
(``moe``: ``MoEConfig``, ``ssm``: ``SSMConfig``, ``frontend``:
``FrontendStub``) holds a dict of that sub-configuration's sizes, such as
``"moe": {"num_experts": 8}``: it replaces the program's own
sub-configuration field by field, or builds one where the program has
none. ``smoke`` merges into ``model`` one level deep, so a smoke
sub-configuration states only the sub-keys it shrinks.

``"blocks": "<stack>"`` names the block stack of the plain reference, of
the work counts and of the device kernels: the module
``blocks/<stack>.py`` (``Model.stack``, with ``blocks``,
``position_params``, ``causal_attn_flops``, ``matrix_bytes`` and
``KERNELS``, a dict from a kernel's name to substrings of its device
operations' names, which the trace reduction credits: ``{}`` for a stack
that runs no kernel of its own). A name with no module, or a module
without one of these, is an error, never a default.
"""
from __future__ import annotations

import dataclasses
import importlib
import typing


# what a block module states
STACK_NAMES = ("blocks", "position_params", "causal_attn_flops",
               "matrix_bytes", "KERNELS")


def stack_module(name: str):
    """The module ``blocks/<name>.py``, which states every name of
    ``STACK_NAMES``."""
    mod = f"bench.blocks.{name}"
    try:
        stack = importlib.import_module(mod)
    except ModuleNotFoundError as exc:
        if exc.name != mod:
            raise
        raise ValueError(f"no block stack {name!r} "
                         f"(bench/blocks/{name}.py)") from None
    missing = [n for n in STACK_NAMES if not hasattr(stack, n)]
    if missing:
        raise ValueError(f"block stack {name!r} (bench/blocks/{name}.py) "
                         f"states no {', '.join(missing)}")
    return stack


class Model:
    def __init__(self, doc: dict, smoke: bool = False):
        self.doc = doc
        self.c = dict(doc["model"])
        if smoke:
            for k, v in doc["smoke"].items():
                self.c[k] = ({**(self.c.get(k) or {}), **v}
                             if isinstance(v, dict) else v)
        self.stack = stack_module(doc["blocks"])

    @property
    def name(self) -> str:
        return self.doc["name"]

    def program_config(self):
        """The program's ``ModelConfig`` with every size of the file: a
        size the program cannot take is an error, never a default."""
        from repro.configs import get_config

        base = get_config(self.doc["arch"])
        return _replace(base, self.c, self.name)


def _refuse_unknown(cls, keys, where: str) -> None:
    unknown = sorted(set(keys) - {f.name for f in dataclasses.fields(cls)})
    if unknown:
        raise KeyError(f"{where}: keys unknown to the program's "
                       f"{cls.__name__}: {unknown}")


def _replace(base, sizes: dict, where: str):
    """``base`` (a dataclass) with ``sizes`` set field by field; a dict
    for a field whose type is a dataclass builds that sub-configuration
    the same way, from the base's own where it has one."""
    _refuse_unknown(type(base), sizes, where)
    hints = typing.get_type_hints(type(base))
    out = {}
    for k, v in sizes.items():
        sub = [t for t in typing.get_args(hints[k]) or (hints[k],)
               if dataclasses.is_dataclass(t)]
        if sub and isinstance(v, dict):
            if getattr(base, k) is None:
                _refuse_unknown(sub[0], v, f"{where}.{k}")
                v = sub[0](**v)
            else:
                v = _replace(getattr(base, k), v, f"{where}.{k}")
        out[k] = v
    return dataclasses.replace(base, **out)
