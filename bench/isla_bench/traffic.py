"""The one general traffic generator: reads a mix file (``bench/traffic/
<mix>.json``) and yields query specs from a seed.

A mix file holds:

* ``loop``: ``"closed"`` -- ``clients`` that each send their next query
  ``think_ms`` after their last answer;
* ``queries``: ``{"kind": <kind>, ...}`` -- the query shape, whose file
  ``bench/queries/<kind>.py`` draws the specs from the rest of the entry
  (``deployment``);
* ``warmup`` and ``check``: how the harness warms up and how many answers
  it compares with the reference.

Every query asks for the configuration's ``(e, beta)``.
"""
from __future__ import annotations

from pathlib import Path
from typing import Iterator

import numpy as np

from . import deployment


class Mix:
    """A traffic mix bound to a configuration's precision; its query kind
    is loaded from the benchmark root."""

    def __init__(self, spec: dict, e: float, beta: float,
                 root: Path = deployment.ROOT):
        self.spec = spec
        self.e = float(e)
        self.beta = float(beta)
        if spec["loop"] != "closed":
            raise ValueError(f"unknown loop {spec['loop']!r}")
        self.queries_spec = spec["queries"]
        self.kind = deployment.load(root, "queries", self.queries_spec["kind"])

    @property
    def clients(self) -> int:
        return int(self.spec.get("clients", 1))

    @property
    def think_s(self) -> float:
        return float(self.spec.get("think_ms", 0.0)) / 1e3

    def queries(self, rng: np.random.Generator) -> Iterator:
        """An endless stream of queries drawn from ``rng``."""
        return self.kind.queries(self.queries_spec, self.e, self.beta, rng)

    def sweep(self, rng: np.random.Generator, e_factors) -> list:
        """The warm-up's queries at each precision factor."""
        return self.kind.sweep(self.queries_spec, self.e, self.beta, rng,
                               e_factors)
