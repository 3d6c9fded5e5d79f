"""Traffic generators: every input of a run is drawn here from ``--seed``.

A traffic file (``bench/traffic/<mix>.json``) holds only parameters; the
driver it names hands them to one of these generators.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


class TokenFeed:
    """Training batches: ``batch_at(step)`` gives ``tokens`` and next-token
    ``labels`` (last position -1), the same for the same (seed, step).

    Tokens follow a Zipf unigram law over the vocabulary; with probability
    ``copy_prob`` a token is instead a fixed permutation of the one before
    it, so the stream has structure a model can learn.  Every row of every
    step differs.
    """

    def __init__(self, seed: int, vocab_size: int, batch: int, seq_len: int,
                 zipf_exponent: float, copy_prob: float):
        self.seed, self.batch, self.seq_len = int(seed), batch, seq_len
        self.copy_prob = copy_prob
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, 0]))
        self.perm = rng.permutation(vocab_size)
        p = 1.0 / np.arange(1, vocab_size + 1, dtype=np.float64) ** zipf_exponent
        self.cdf = np.cumsum(p / p.sum())
        self.cdf[-1] = 1.0

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, 1, int(step)]))
        shape = (self.batch, self.seq_len)
        toks = np.searchsorted(self.cdf, rng.random(shape), side="right")
        copy = rng.random(shape) < self.copy_prob
        toks[:, 1:] = np.where(copy[:, 1:], self.perm[toks[:, :-1]], toks[:, 1:])
        labels = np.concatenate([toks[:, 1:], np.full((self.batch, 1), -1)], axis=1)
        return {"tokens": toks.astype(np.int32), "labels": labels.astype(np.int32)}


def log_uniform(rng, lo: int, hi: int, count: int) -> np.ndarray:
    """``count`` whole lengths in [lo, hi], log-uniform: a heavy tail."""
    return np.rint(np.exp(rng.uniform(np.log(lo), np.log(hi), count))).astype(int)


def request_specs(seed: int, count: int, vocab_size: int, p: dict):
    """``count`` requests of a closed-loop serving mix, in the order clients
    send them: (prompt tokens, max_new_tokens, temperature, top_k).

    The sizes and their order are one fixed sequence, the same for every
    seed (prompt lengths log-uniform in ``prompt_len``, output lengths
    log-uniform in ``max_new_tokens``, every other request greedy, the rest
    sampled at ``temperature`` / ``top_k``), so runs of different seeds do
    the same work; the seed draws the prompt tokens.
    """
    pool = np.random.default_rng(np.random.SeedSequence([0, 2]))
    plen = log_uniform(pool, *p["prompt_len"], count)
    new = log_uniform(pool, *p["max_new_tokens"], count)
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 2]))
    out = []
    for i in range(count):
        prompt = rng.integers(1, vocab_size, plen[i]).tolist()
        temp, top_k = (0.0, 0) if i % 2 == 0 else (p["temperature"], p["top_k"])
        out.append((prompt, int(new[i]), float(temp), int(top_k)))
    return out
