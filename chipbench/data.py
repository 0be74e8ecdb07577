"""The benchmark's own copy of the training traffic: the order-2 Markov token
stream of the program's ``data/synthetic.py`` (``LMStream``), deterministic
in (seed, step, host). The program's batches are checked against it, and
the reference trains on these."""
from __future__ import annotations

import numpy as np

ORDER_STATES = 64


def _rng(seed: int, step: int, host: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, step, host]))


class Stream:
    """The chain's transition table comes from ``seed``; the rows of each
    step from (``seed``, step, ``host``)."""

    def __init__(self, vocab: int, seq: int, batch: int, seed: int, host: int):
        g = _rng(seed, 0, 0)
        self.v = min(vocab, 4096)
        self.probs = g.dirichlet(np.full(8, 0.3), size=ORDER_STATES)
        self.succ = g.integers(0, self.v, size=(ORDER_STATES, 8))
        self.seq, self.batch, self.seed, self.host = seq, batch, seed, host

    def batch_at(self, step: int) -> dict:
        """Rows of tokens and next-token labels (-1 past the end)."""
        g = _rng(self.seed, step + 1, self.host)
        b, s = self.batch, self.seq
        toks = np.empty((b, s), np.int32)
        state = g.integers(0, ORDER_STATES, size=b)
        cdf = np.cumsum(self.probs, axis=1)
        u = g.random((b, s))
        for t in range(s):
            choice = (u[:, t, None] > cdf[state]).sum(axis=1)
            toks[:, t] = self.succ[state, choice]
            state = (state * 31 + toks[:, t]) % ORDER_STATES
        labels = np.concatenate([toks[:, 1:], np.full((b, 1), -1, np.int32)], axis=1)
        return {"tokens": toks, "labels": labels}
