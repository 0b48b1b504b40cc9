"""One generator for every serving traffic mix, read from a data file.

A mix file (``bench/traffic/<name>.json``) gives lengths, tenant skew
and loop shape; nothing here knows a mix by name.

Sizes come from a fixed pool of ``pool`` requests: prompt and output
lengths at evenly spaced quantiles of their lognormal distributions
(median, sigma, clipped to [min, max]), paired by a permutation drawn
from the file's ``pool_seed``.  Every run thus serves the same set of
sizes, whatever its seed; the run's ``--seed`` orders the pool, draws
each request's tenant and its prompt tokens.  Client ``i`` of ``C``
sends entries ``i, i + C, ...`` of the run's order, cycling, one after
another (a closed loop), so a client's sequence does not depend on
timing.

Tenants follow ``repro.serving.scheduler.synthetic_workload``'s Zipf
draw (probability of rank k proportional to k^-a over the universe) with
a seeded map from rank to tenant id, one independent draw per request,
so cold tenants keep arriving however long the run.
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Pool:
    prompt: np.ndarray        # prompt lengths
    output: np.ndarray        # tokens to generate, first one included


def _quantiles(spec: dict, n: int) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def zipf_probs(universe: int, a: float) -> np.ndarray:
    p = np.arange(1, universe + 1, dtype=np.float64) ** -a
    return p / p.sum()


def make_pool(mix: dict) -> Pool:
    """The fixed set of request sizes a mix serves."""
    n = mix["pool"]
    pair = np.random.default_rng(mix["pool_seed"]).permutation(n)
    return Pool(_quantiles(mix["prompt"], n),
                _quantiles(mix["output"], n)[pair])


class Traffic:
    """A run's request stream: ``request(client, j)`` is client's j-th."""

    def __init__(self, mix: dict, universe: int, vocab: int, clients: int,
                 seed: int):
        self.pool = make_pool(mix)
        self.tenants = mix["tenants"]
        self.universe = universe
        self.clients = clients
        self.vocab = vocab
        self.seed = int(seed) % (1 << 64)
        rng = np.random.default_rng(self.seed)
        self.order = rng.permutation(len(self.pool.prompt))
        self.tenant_of_rank = rng.permutation(universe)
        if self.tenants["dist"] == "zipf":
            self.cdf = np.cumsum(zipf_probs(universe, self.tenants["a"]))
        elif self.tenants["dist"] != "one":
            raise ValueError(f"unknown tenant distribution "
                             f"{self.tenants['dist']!r}")

    def rank(self, rng) -> int:
        if self.tenants["dist"] == "one":
            return 0
        return int(min(np.searchsorted(self.cdf, rng.random()),
                       self.universe - 1))

    def request(self, client: int, j: int) -> tuple[int, np.ndarray, int]:
        """(tenant id, prompt token ids, tokens to generate)."""
        k = self.order[(j * self.clients + client) % len(self.order)]
        rng = np.random.default_rng((self.seed, client, j))
        tenant = int(self.tenant_of_rank[self.rank(rng)])
        tokens = rng.integers(0, self.vocab, int(self.pool.prompt[k]),
                              dtype=np.int32)
        return tenant, tokens, int(self.pool.output[k])
