"""A configuration file of the benchmark, as the harness uses it.

``configs/<name>.json`` holds the sizes as they are run (``model``), the
source, every key ``reduced`` or ``corrected`` from it with the published
value, what is ``assumed``, the deployment it stands for, and ``smoke``
sizes for the CPU rehearsal. ``arch`` names the program's own
configuration that these sizes replace field by field.
"""
from __future__ import annotations

import dataclasses


class Model:
    def __init__(self, doc: dict, smoke: bool = False):
        self.doc = doc
        self.c = dict(doc["model"])
        if smoke:
            self.c.update(doc["smoke"])

    @property
    def name(self) -> str:
        return self.doc["name"]

    def program_config(self):
        """The program's ``ModelConfig`` with every size of the file: a
        size the program cannot take is an error, never a default."""
        from repro.configs import get_config

        base = get_config(self.doc["arch"])
        fields = {f.name for f in dataclasses.fields(base)}
        unknown = sorted(set(self.c) - fields)
        if unknown:
            raise KeyError(f"{self.name}: keys unknown to the program's "
                           f"ModelConfig: {unknown}")
        return dataclasses.replace(base, **self.c)
