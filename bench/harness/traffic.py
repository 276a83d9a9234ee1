"""The one traffic generator: requests from a mix's parameters and a seed.

A mix is a data file, ``bench/traffic/<name>.json``.  Lengths come from the
mix's distributions through fixed quantiles, not random draws: the lengths
are cut into ``strata`` equal-probability bands, and every consecutive block
of ``strata`` requests holds one length of each band, in an order drawn
from the seed.  So every seed serves the same mix of sizes, in another
order, with other token ids, and the seed does not change the amount of
work.  Output lengths are paired with prompt lengths by a fixed shuffle
(independent of the seed), so long prompts do not always get long answers.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def quantile(dist: dict, q: float) -> int:
    """Length at probability ``q`` of a clipped distribution."""
    if dist["dist"] == "lognormal":
        v = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(q))
    elif dist["dist"] == "uniform":
        v = dist["min"] + q * (dist["max"] - dist["min"])
    elif dist["dist"] == "fixed":
        v = dist["value"]
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return int(min(max(round(v), dist["min"]), dist["max"]))


def strata_lengths(mix: dict) -> list:
    """The ``strata`` (prompt, output) length pairs every block holds."""
    n = mix["strata"]
    qs = [(k + 0.5) / n for k in range(n)]
    prompts = [quantile(mix["prompt_len"], q) for q in qs]
    outs = [quantile(mix["output_len"], q) for q in qs]
    pair = np.random.default_rng(0).permutation(n)
    return [(prompts[k], outs[int(pair[k])]) for k in range(n)]


class Requests:
    """Request ``i`` of a mix under a seed: (prompt ids, max new tokens).

    Deterministic per (seed, i) and independent of the order of calls."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix = mix
        self.seed = int(seed)
        self.vocab = int(vocab)
        self.pairs = strata_lengths(mix)

    def lengths(self, i: int) -> tuple:
        n = len(self.pairs)
        block, k = divmod(i, n)
        order = np.random.default_rng([self.seed, 1, block]).permutation(n)
        return self.pairs[int(order[k])]

    def request(self, i: int) -> tuple:
        plen, out = self.lengths(i)
        rng = np.random.default_rng([self.seed, 2, i])
        return rng.integers(0, self.vocab, plen).astype(np.int32), out

    def warmup(self, buckets) -> list:
        """One prompt filling each prompt bucket, so set-up runs every
        admission program once."""
        rng = np.random.default_rng([self.seed, 3])
        n = self.mix.get("warmup", {}).get("max_new_tokens", 2)
        return [(rng.integers(0, self.vocab, b).astype(np.int32), n)
                for b in buckets]

    def clients(self, slots: int) -> int:
        return (self.mix["clients_per_slot"] * slots
                * self.mix.get("replicas", 1))
