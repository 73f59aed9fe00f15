"""The one generator of serving traffic: it reads a traffic file's parameters
and makes the requests from the seed.

Every seed gets the SAME multiset of (prompt length, answer length) pairs, in
another order and with other token ids: the lengths are the evenly spaced
quantiles of the two clipped lognormals, paired by a shuffle fixed in the
traffic file, so the seed changes the order of the work and not its amount.
"""
import math
from statistics import NormalDist

import numpy as np


def lognormal_quantiles(median, sigma, lo, hi, n):
    """``n`` lengths at the evenly spaced quantiles of a lognormal with the
    given median and log-sigma, clipped to [lo, hi]."""
    nd = NormalDist()
    q = [(i + 0.5) / n for i in range(n)]
    vals = [median * math.exp(sigma * nd.inv_cdf(x)) for x in q]
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def length_pool(traffic, max_total):
    """The fixed pool of (prompt, answer) length pairs of a traffic mix."""
    n = traffic["pool"]
    p, a = traffic["prompt"], traffic["answer"]
    prompts = lognormal_quantiles(p["median"], p["sigma"], p["min"],
                                  p["max"], n)
    answers = lognormal_quantiles(a["median"], a["sigma"], a["min"],
                                  a["max"], n)
    pairing = np.random.default_rng(traffic["pairing_seed"]).permutation(n)
    answers = answers[pairing]
    answers = np.minimum(answers, max_total - prompts)
    return list(zip(prompts.tolist(), answers.tolist()))


class RequestStream:
    """Requests in the order of one seed: the pool permuted by the seed and
    cycled, token ids drawn from the vocabulary by the seed.  ``take`` is
    called by one client thread at a time (the driver holds a lock)."""

    def __init__(self, traffic, vocab_size, max_total, seed):
        self.pool = length_pool(traffic, max_total)
        self.rs = np.random.default_rng([int(seed), 2])
        self.order = self.rs.permutation(len(self.pool))
        self.vocab = vocab_size
        self.n = 0

    def _ids(self, n):
        return self.rs.integers(0, self.vocab, n).astype(np.int32)

    def take(self):
        """The next request: (prompt ids, answer length)."""
        p, a = self.pool[self.order[self.n % len(self.pool)]]
        self.n += 1
        return self._ids(p), int(a)

    def take_in_progress(self):
        """What a slot of a steady closed loop holds at a random moment: a
        request chosen in proportion to its length, caught at a uniform
        point of its life.  The first round is made of these, so that the
        window does not open on a lockstep start."""
        totals = np.array([p + a for p, a in self.pool], float)
        p, a = self.pool[self.rs.choice(len(self.pool),
                                        p=totals / totals.sum())]
        done = int(self.rs.integers(0, p + a))
        if done < p:
            return self._ids(p - done), int(a)
        return self._ids(1), max(1, int(a - (done - p)))
