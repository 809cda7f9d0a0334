"""The one generator of open-loop traffic, driven by a mix file.

A mix (``traffic/<name>.json``) holds parameters only: the arrival
process and its rate, the request kind and its shares and sizes, and the
server settings the traffic is offered to. ``schedule`` turns a mix and a
seed into arrival times and requests. The arrival process and the request
kind are found by name, each in a module of its own:
``generators/arrival_<process>.py`` (``times(params, seconds, order)``,
seconds after the stream's start) and ``generators/requests_<kind>.py``
(``specs(params, n, order, rng, c)``, what the client sends). A mix that
names one with no module is refused, so a new process or kind adds a file
and edits none.

Every seed gets the same work: the generators draw stratified quantiles
of their distributions and exact shares, which the ``order`` generator
shuffles; the seed's own generator draws the contents (token ids, noise).
A mix whose ``arrival`` names an ``order_seed`` replays one order for every
seed (a fixed trace; the run's seed still draws the contents): near a knee
the order alone moves a latency tail more than two runs of one order
differ.

Streams: the same seed gives disjoint streams for the measured window,
the traffic kept up after it, the warm-up and the check's sample, so
warm-up traffic never repeats a measured request.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
from typing import Any

import numpy as np

STREAMS = {"window": 0, "after": 1, "warmup": 2, "check": 3}


@dataclasses.dataclass
class Arrival:
    t: float                  # seconds after the stream's start
    kind: str                 # the plane that serves it
    spec: dict[str, Any]      # what the client sends


def load_mix(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def rng_for(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng([int(seed), STREAMS[stream]])


def strata(n: int) -> np.ndarray:
    """The midpoints of ``n`` equal slices of (0, 1)."""
    return (np.arange(n) + 0.5) / n


def quota(n: int, weights) -> np.ndarray:
    """Index per item such that each index holds its exact share of
    ``n`` (largest remainder), in sorted order."""
    w = np.asarray(weights, float)
    raw = n * w / w.sum()
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts))[:n - counts.sum()]:
        counts[i] += 1
    return np.repeat(np.arange(len(w)), counts)


def generator(role: str, name: str):
    """The module ``generators/<role>_<name>.py``; a name with no module
    is an error, never a default."""
    mod = f"bench.generators.{role}_{name}"
    try:
        return importlib.import_module(mod)
    except ModuleNotFoundError as exc:
        if exc.name != mod:
            raise
        raise ValueError(f"no {role} generator {name!r} "
                         f"(bench/generators/{role}_{name}.py)") from None


def schedule(mix: dict, seed: int, seconds: float, stream: str,
             c: dict) -> list[Arrival]:
    """Arrivals over ``seconds`` for one stream of one seed; ``c`` is the
    configuration (vocabulary, latent width)."""
    rng = rng_for(seed, stream)
    arrival, req = mix["arrival"], mix["requests"]
    order = (rng_for(arrival["order_seed"], stream)
             if "order_seed" in arrival else rng)
    times = generator("arrival", arrival["process"]).times(
        arrival, seconds, order)
    specs = generator("requests", req["kind"]).specs(
        req, len(times), order, rng, c)
    return [Arrival(float(t), mix["plane"], s) for t, s in zip(times, specs)]
