"""One general generator for every traffic mix: a mix is a data file.

A mix file (``bench/traffic/<name>.json``) gives the loop kind and the
length distributions:

    {"loop": "open" | "closed",
     "prompt": {"median": 1020, "sigma": 0.8, "min": 64, "max": 3584},
     "output": {"median": 129, "sigma": 0.9, "min": 8, "max": 512},
     "clients_per_slot": 2,          # closed loop only
     "pool": 256}                    # closed loop only: requests per block

Lengths are lognormal (median, sigma), clipped to [min, max]. An open loop
sends at the cell's fixed rate (``bench/cells/<cell>.json``:
``rate_per_s``) with exponential gaps; a closed loop keeps
``clients_per_slot * max_slots`` clients, each sending its next request as
soon as its last one finished.

So that the seed changes the order of the work and not its amount, a block
of n requests always holds the same lengths and gaps: those at the n
quantiles (i + 0.5) / n of each distribution. The seed permutes them and
draws the token ids. Every seed thus offers the same tokens at the same
rate.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Optional

import numpy as np


@dataclass(frozen=True)
class Arrival:
    due: float          # seconds after the start of its block (open loop)
    prompt: np.ndarray  # int32 token ids
    max_new: int


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A generator for one stream of one seed; any whole seed, negative or
    past 64 bits, maps to a valid one."""
    s = int(seed) % (1 << 64)
    return np.random.default_rng([s & 0xFFFFFFFF, s >> 32, int(stream)])


def quantile_grid(n: int) -> np.ndarray:
    return (np.arange(n, dtype=np.float64) + 0.5) / n


def lognormal_lengths(dist: dict, n: int) -> np.ndarray:
    """The n quantile lengths of a clipped lognormal, ascending."""
    inv = NormalDist().inv_cdf
    z = np.array([inv(q) for q in quantile_grid(n)])
    x = np.rint(dist["median"] * np.exp(dist["sigma"] * z))
    return np.clip(x, dist["min"], dist["max"]).astype(np.int64)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    return -np.log1p(-quantile_grid(n)) / rate


class Traffic:
    """The requests of one mix for one seed, in numbered blocks (streams).

    Open loop: a block is ``round(rate * seconds)`` requests due within
    ``seconds`` of the block's start. Closed loop: a block is ``pool`` requests taken
    in order by whichever client is free."""

    def __init__(self, mix: dict, seed: int, vocab: int, seconds: float, *,
                 rate: Optional[float] = None, max_slots: int = 0):
        self.mix = mix
        self.seed = seed
        self.seconds = float(seconds)
        self.vocab = int(vocab)
        self.loop = mix["loop"]
        if self.loop == "open":
            if not rate or rate <= 0:
                raise ValueError("an open-loop mix needs the cell's "
                                 "rate_per_s")
            self.rate = float(rate)
            self.block_size = max(1, round(self.rate * seconds))
            self.clients = 0
        elif self.loop == "closed":
            self.rate = None
            self.block_size = int(mix["pool"])
            self.clients = int(mix["clients_per_slot"]) * int(max_slots)
            if self.clients <= 0:
                raise ValueError("a closed-loop mix needs clients")
        else:
            raise ValueError(f"unknown loop kind {self.loop!r}")

    def block(self, stream: int) -> List[Arrival]:
        n = self.block_size
        rng = rng_for(self.seed, stream)
        prompts = rng.permutation(lognormal_lengths(self.mix["prompt"], n))
        outs = rng.permutation(lognormal_lengths(self.mix["output"], n))
        if self.loop == "open":
            # exclusive sums of the gaps, stretched so that the block spans
            # exactly ``seconds``: block k covers [k, k + 1) * seconds
            gaps = rng.permutation(exponential_gaps(self.rate, n))
            dues = (np.cumsum(gaps) - gaps) * (self.seconds / gaps.sum())
        else:
            dues = np.zeros(n)
        return [Arrival(float(d), rng.integers(0, self.vocab, int(p),
                                               dtype=np.int32), int(o))
                for d, p, o in zip(dues, prompts, outs)]
