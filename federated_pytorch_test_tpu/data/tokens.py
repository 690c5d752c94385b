"""Seeded per-client shards of packed token sequences.

No corpus is in git.  Each client draws ids from a Zipf unigram over the
chip's slice of the vocabulary, ``p(rank r) ~ 1 / (r + 1)^a``, and the
clients differ: the ``head`` most frequent ranks are mapped to ids by a
permutation of each client's own, so one client's commonest token is
another's rare one (non-identical clients, as in a cross-silo
federation), while a unigram is something a model can learn, so the loss
can fall.  A sample is one packed sequence of ``seq_len`` ids; its labels
are the ids shifted by one (the last position predicts the first id of
the next draw, kept so every position has a label).

What the stream does NOT have: document boundaries.  A packed batch of a
real corpus carries boundary masks, which ``models/qwen3_next.py`` does
not read (ROADMAP: what the system cannot run yet).

Offers what ``BlockwiseFederatedTrainer`` reads from its ``data``
argument (``train_shards_raw``, ``epoch_batches_raw``,
``test_batches_raw``, ``norm_stats``, ``steps``, ``batch``,
``remainder``, ``samples_per_client``).
"""

from __future__ import annotations

from typing import Tuple

import numpy as np


class FederatedTokens:
    """``K`` clients x ``samples_per_client`` sequences of ``seq_len``
    ids in ``[0, vocab)``.  ``samples_per_client`` is a multiple of
    ``batch``: every minibatch is full and ``remainder`` is 0."""

    source = "synthetic-zipf"

    def __init__(self, K: int, batch: int, samples_per_client: int,
                 seq_len: int, vocab: int, seed: int, *,
                 zipf_a: float = 1.1, head: int = 1024, n_test: int = 2):
        if samples_per_client % batch:
            raise ValueError(
                f"samples_per_client={samples_per_client} is not a multiple "
                f"of batch={batch}")
        self.K, self.batch, self.seq_len, self.vocab = K, batch, seq_len, vocab
        self.steps = samples_per_client // batch
        self.remainder = 0
        rng = np.random.default_rng([int(seed), 0x70CE])
        p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** zipf_a
        cdf = np.cumsum(p / p.sum())
        head = min(head, vocab)

        def draw(n_seq: int, ids_of_rank: np.ndarray):
            u = rng.random((n_seq, seq_len + 1))
            ranks = np.minimum(np.searchsorted(cdf, u), vocab - 1)
            ids = ids_of_rank[ranks].astype(np.int32)
            return ids[:, :-1], ids[:, 1:]

        xs, ys = [], []
        for _ in range(K):
            ids_of_rank = np.arange(vocab)
            ids_of_rank[:head] = rng.permutation(head)
            x, y = draw(samples_per_client, ids_of_rank)
            xs.append(x)
            ys.append(y)
        self._train_x, self._train_y = np.stack(xs), np.stack(ys)
        self._test_x, self._test_y = draw(n_test, np.arange(vocab))
        #: the engine stages a per-client row of this beside every batch;
        #: tokens need no normalisation
        self._norm = np.zeros((K, 1), np.float32)

    @property
    def samples_per_client(self) -> int:
        return self._train_x.shape[1]

    @property
    def tokens_per_sample(self) -> int:
        return self.seq_len

    @property
    def norm_stats(self) -> np.ndarray:
        return self._norm

    def train_shards_raw(self) -> Tuple[np.ndarray, np.ndarray]:
        """``([K, n, T] int32 ids, [K, n, T] int32 next ids)``."""
        return self._train_x, self._train_y

    def epoch_batches_raw(self, seed: int):
        """One shuffled epoch ``([K, steps, B, T] ids, labels, [K, steps,
        B] weights)`` for the engine's host-staged path."""
        rng = np.random.default_rng(seed)
        n = self.samples_per_client
        perm = np.stack([rng.permutation(n) for _ in range(self.K)])
        rows = np.arange(self.K)[:, None]
        shape = (self.K, self.steps, self.batch, self.seq_len)
        return (self._train_x[rows, perm].reshape(shape),
                self._train_y[rows, perm].reshape(shape),
                np.ones(shape[:3], np.float32))

    def test_batches_raw(self, batch=None):
        b = batch or self.batch
        n = len(self._test_x)
        tsteps = -(-n // b)
        pad = np.arange(tsteps * b) % n
        w = (np.arange(tsteps * b) < n).astype(np.float32)
        return (self._test_x[pad].reshape(tsteps, b, self.seq_len),
                self._test_y[pad].reshape(tsteps, b, self.seq_len),
                w.reshape(tsteps, b))
