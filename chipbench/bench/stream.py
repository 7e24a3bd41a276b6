"""The seeded query stream, copied here so that no program change moves the
traffic.

Same interface as the program's synthetic LM stream (`batch(step)`,
`succ`, `cfg`): an order-1 Markov stream over a planted successor graph.
The served path reads `batch(round)` for each tenant's prompts and `succ`
to score answers (the fraction of generated bigrams that follow the graph).
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class StreamConfig:
    vocab: int
    seq_len: int
    global_batch: int
    seed: int = 0
    branch: int = 4          # out-degree of the planted Markov graph


class QueryStream:
    """next ~ Uniform(succ[prev]); rows drawn from (seed, user, step).

    Every user shares the planted graph (the task) and draws queries of
    their own: ``user`` tells the streams of different tenants apart."""

    def __init__(self, cfg: StreamConfig, user: int = 0):
        self.cfg, self.user = cfg, user
        rng = np.random.default_rng(cfg.seed)
        self.succ = rng.integers(0, cfg.vocab, size=(cfg.vocab, cfg.branch),
                                 dtype=np.int32)

    def batch(self, step: int) -> np.ndarray:
        c = self.cfg
        rng = np.random.default_rng((c.seed, self.user, step))
        toks = np.empty((c.global_batch, c.seq_len + 1), np.int32)
        toks[:, 0] = rng.integers(0, c.vocab, c.global_batch)
        pick = rng.integers(0, c.branch, (c.global_batch, c.seq_len + 1))
        for t in range(1, c.seq_len + 1):
            toks[:, t] = self.succ[toks[:, t - 1], pick[:, t]]
        return toks


def quality(succ: np.ndarray, prompts: np.ndarray, gen: np.ndarray) -> float:
    """Fraction of generated bigrams that follow the planted graph; a token
    outside the stream's vocabulary is never a valid successor, nor the
    predecessor of one. The plain form of the served path's reward."""
    seq = np.concatenate([prompts[:, -1:], gen], axis=1)
    prev, nxt = seq[:, :-1], seq[:, 1:]
    ok = np.zeros(prev.shape, bool)
    for r in range(prev.shape[0]):
        for j in range(prev.shape[1]):
            p = prev[r, j]
            ok[r, j] = p < succ.shape[0] and nxt[r, j] in succ[p]
    return float(ok.mean())
