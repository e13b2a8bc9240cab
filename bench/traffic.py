"""The one traffic generator: a traffic file's parameters plus ``--seed``
give the requests of a run.

A traffic file (``bench/traffic/<mix>.json``) holds:

- ``loop``: ``"closed"`` (``clients`` callers, each sends its next request
  when the last one returns) or ``"open"`` (arrivals on a schedule at
  ``rate_per_s``, whatever the server does);
- ``lengths``: ``{"kind": "fixed", "tokens": n}`` or ``{"kind":
  "lognormal", "median": m, "sigma": s}``; a length is rounded up to the
  smallest of ``buckets`` that holds it and clipped at the largest, and the
  prompt is generated at that bucket's length (the served path reads the
  next-token logits at the last position of the bucket);
- ``buckets``, ``num_chunks``, ``max_batch``: the engine's shapes;
- ``drain_cap_s``: how long after the window requests may still finish;
- ``schedule_seed`` (optional): where given, the order of the lengths and
  the arrival times come from it and not from the run's seed, so that
  every run replays one schedule; the run's seed still makes the tokens.

Every seed gets the same work: the lengths are the distribution's
quantiles at evenly spaced probabilities (a fixed multiset), and an open
loop sends exactly ``round(rate_per_s * seconds)`` requests, their arrival
times uniform over the window (a Poisson process conditioned on that
count). The seed sets their order, the arrival times and the tokens.
An open loop's tails swing by tens of percent with the order alone (a
queue remembers a burst), so an open-loop mix that is judged on its tails
fixes the order with ``schedule_seed``.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

CLOSED_CYCLE = 64   # a closed loop walks a multiset of this many lengths


def bucket_of(buckets, n: int) -> int:
    for b in sorted(buckets):
        if n <= b:
            return b
    return max(buckets)


def length_multiset(t: dict, count: int) -> list:
    """``count`` prompt lengths (already bucketed), in ascending order."""
    spec = t["lengths"]
    if spec["kind"] == "fixed":
        raw = [spec["tokens"]] * count
    elif spec["kind"] == "lognormal":
        nd = NormalDist()
        mu = math.log(spec["median"])
        raw = [math.exp(mu + spec["sigma"] * nd.inv_cdf((i + 0.5) / count))
               for i in range(count)]
    else:
        raise ValueError(f"unknown length kind {spec['kind']!r}")
    return sorted(bucket_of(t["buckets"], math.ceil(n)) for n in raw)


def tokens(seed: int, index: int, length: int, vocab: int) -> np.ndarray:
    """Prompt ``index`` of the run with ``seed``: ``length`` ids."""
    rng = np.random.default_rng([int(seed), int(index), 1])
    return rng.integers(0, vocab, size=length, dtype=np.int32)


def warm_tokens(length: int, vocab: int) -> np.ndarray:
    """Warm-up prompts do not depend on the seed."""
    return tokens(0, 2**31 - 1, length, vocab)


class Schedule:
    """The requests of one run: ``lengths[i]`` and, for an open loop,
    ``arrivals[i]`` in seconds from the window's start."""

    def __init__(self, t: dict, seed: int, seconds: float):
        self.loop = t["loop"]
        rng = np.random.default_rng([int(t.get("schedule_seed", seed)), 0])
        if self.loop == "open":
            n = max(1, round(t["rate_per_s"] * seconds))
            self.lengths = list(rng.permutation(length_multiset(t, n)))
            self.arrivals = sorted(rng.uniform(0.0, seconds, n).tolist())
        elif self.loop == "closed":
            self._cycle = list(rng.permutation(length_multiset(t,
                                                               CLOSED_CYCLE)))
            self.lengths = None
            self.arrivals = None
        else:
            raise ValueError(f"unknown loop {self.loop!r}")

    def length(self, i: int) -> int:
        if self.lengths is not None:
            return int(self.lengths[i])
        return int(self._cycle[i % len(self._cycle)])
