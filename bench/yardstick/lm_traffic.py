"""One generator for every LM request mix: a seeded bank of requests.

A mix file (``bench/traffic/<name>.json`` with ``"loop": "closed"`` and a
``prompt`` and an ``output`` block) gives the decode slots and cache
length the cell serves with, the bank's size, and the lognormal lengths
of prompts and outputs, each clipped to its range.

The lengths are drawn once for the mix, from its own ``length_seed``:
the bank is cut into blocks of ``block`` requests, and each block holds
the ``block`` quantiles of the prompt distribution and, paired
independently, those of the output distribution, in a shuffled order.
Every run seed serves the same lengths in the same order and draws its
own token ids (uniform over the vocabulary) and weights: in a closed
loop the order of lengths decides which requests join together, and so
the work, and a seed that reordered them would change the work and not
only the inputs.

The first ``slots`` requests fill the pool before the window.  They are
served only the remaining part of their output (a stratified share of
it), as requests already under way would be, so that the window starts
with lanes at every stage of a request and not with every lane fresh at
once.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

from yardstick.traffic import rng_for


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of a lognormal length distribution
    (``median``, ``sigma``), rounded and clipped to [``min``, ``max``]."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.rint(dist["median"] * np.exp(dist["sigma"] * z))
    return np.clip(x, dist["min"], dist["max"]).astype(np.int64)


class RequestBank:
    def __init__(self, mix: dict, seed: int, vocab: int):
        if mix["loop"] != "closed":
            raise ValueError(f"unknown loop {mix['loop']!r}")
        self.slots, self.max_len = int(mix["slots"]), int(mix["max_len"])
        n, block = int(mix["requests"]), int(mix["block"])
        if n % block or n < self.slots:
            raise ValueError("requests must be whole blocks, at least slots")
        p_len, o_len = mix["prompt"], mix["output"]
        if p_len["max"] - 1 + o_len["max"] > self.max_len:
            raise ValueError("the longest request does not fit max_len")
        rng = rng_for(mix["length_seed"], 11)
        p_q, o_q = quantile_lengths(p_len, block), quantile_lengths(o_len,
                                                                     block)
        self.prompt_len = np.concatenate(
            [p_q[rng.permutation(block)] for _ in range(n // block)])
        self.out_len = np.concatenate(
            [o_q[rng.permutation(block)] for _ in range(n // block)])
        share = (rng.permutation(self.slots) + 0.5) / self.slots
        self.out_len[:self.slots] = np.maximum(
            1, np.ceil(share * self.out_len[:self.slots]))
        self.tokens = rng_for(seed, 12).integers(
            0, vocab, (n, int(p_len["max"])), dtype=np.int32)

    def __len__(self) -> int:
        return len(self.prompt_len)

    def request(self, i: int) -> tuple[np.ndarray, int]:
        """Prompt token ids and output budget of request ``i``."""
        i %= len(self)
        return self.tokens[i, :self.prompt_len[i]], int(self.out_len[i])

    def warm_prompt_lengths(self) -> list[int]:
        """For each power-of-two prefill width that a prompt of the bank
        needs (its length less the one token the first decode step
        feeds), the longest prompt of the bank that needs it, shortest
        first: a join is as wide as its widest joiner, so these are all
        the widths the window can reach."""
        width = [1 << max(0, math.ceil(math.log2(max(n - 1, 1))))
                 for n in self.prompt_len]
        longest = {}
        for w, n in zip(width, self.prompt_len):
            longest[w] = max(longest.get(w, 0), int(n))
        return sorted(longest.values())
