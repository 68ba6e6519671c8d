"""Seeded request streams for one traffic mix (a file in ``traffic/``).

The schedule is the mix's own: the output lengths and, in an open
loop, the interarrival gaps are fixed multisets (evenly spaced
quantiles and largest-remainder counts), put in one order drawn from
the mix alone.  The seed draws the prompt ids (and, in ``run_cell``,
the weights).  So every seed serves the same work at the same times,
and the spread between seeds is not a spread of work or of the luck of
a burst: with the order drawn from the seed, an open loop's TTFT tail
moves with where its bursts fall.

A mix file holds:

  loop            "open" (arrivals on a schedule) or "closed" (clients
                  that resubmit as soon as their reply is complete)
  rate_rps        open loop: mean arrivals per second (Poisson)
  clients         closed loop: number of clients
  prompt_len      the one prompt length (a cohort has one prompt length)
  max_news        output lengths, with max_new_probs their shares
  capacity        requests in flight at most (the admission capacity)
"""

from __future__ import annotations

import numpy as np

BALANCED_BLOCK = 64   # closed loop: output lengths repeat in blocks this long
SCHEDULE = 20240917   # the stream the schedule's order is drawn from


def balanced(values, probs, n: int) -> list:
    """``n`` values with each one's count as close to ``probs * n`` as
    whole numbers allow (largest remainder), in a fixed order."""
    p = np.asarray(probs, float)
    if len(p) != len(values) or (p < 0).any() or p.sum() <= 0:
        raise ValueError("probs must be non-negative, one per value, sum > 0")
    share = p / p.sum() * n
    counts = np.floor(share).astype(int)
    for i in np.argsort(-(share - counts), kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    return [v for v, c in zip(values, counts) for _ in range(int(c))]


def stratified_gaps(n: int, total_s: float) -> np.ndarray:
    """``n`` exponential interarrival gaps taken at evenly spaced
    quantiles, scaled so that they add up to ``total_s``."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    return gaps * (total_s / gaps.sum())


class Traffic:
    """The request stream of one mix under one seed.

    Open loop: ``arrivals[i]`` is request ``i``'s due time in seconds
    after the window opens; there are ``round(rate * seconds)`` of them,
    all inside the window.  Closed loop: requests are numbered in the
    order clients submit them, with no due time of their own.
    ``lengths(i)`` is a function of (mix, seconds, i), ``prompt(i)`` of
    (mix, seed, i)."""

    def __init__(self, mix: dict, seed: int, seconds: float, vocab: int):
        self.mix = mix
        self.seed = int(seed)
        self.vocab = int(vocab)
        self.loop = mix["loop"]
        if self.loop not in ("open", "closed"):
            raise ValueError(f"unknown loop {self.loop!r}")
        self.prompt_len = int(mix["prompt_len"])
        self.capacity = int(mix["capacity"])
        rng = np.random.default_rng([SCHEDULE, 0])
        if self.loop == "open":
            n = max(1, int(round(float(mix["rate_rps"]) * seconds)))
            gaps = rng.permutation(stratified_gaps(n, seconds))
            self.arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
            block = n
        else:
            self.clients = int(mix["clients"])
            self.arrivals = None
            block = BALANCED_BLOCK
        self._news = rng.permutation(
            balanced(list(mix["max_news"]), mix["max_new_probs"], block))

    def __len__(self) -> int:
        return len(self.arrivals) if self.loop == "open" else 0

    def lengths(self, i: int) -> tuple[int, int]:
        """(prompt_len, max_new) of request ``i``."""
        return self.prompt_len, int(self._news[i % len(self._news)])

    def prompt(self, i: int) -> np.ndarray:
        """Request ``i``'s prompt ids, [prompt_len] int32."""
        rng = np.random.default_rng([self.seed, 1, i])
        return rng.integers(0, self.vocab, self.prompt_len).astype(np.int32)

    def cohort_shapes(self) -> list[tuple[int, int, int]]:
        """Every (cohort size, prompt_len, cohort max_new) the greedy
        scheduler can form from this stream: a cohort is any 1..capacity
        requests that are ready together, and its cache is as long as its
        longest reply.  A closed loop whose clients fill the capacity and
        all ask for one length stays in lockstep: one cohort of all
        clients."""
        news = sorted(set(int(v) for v in self.mix["max_news"]))
        if self.loop == "closed" and len(news) == 1 and \
                self.clients <= self.capacity:
            return [(self.clients, self.prompt_len, news[0])]
        return [(b, self.prompt_len, m)
                for b in range(1, self.capacity + 1) for m in news]
