"""Seeded synthetic client shards: the benchmark's traffic generator for
the classifier engine.

No dataset is in git, and the program's own stand-in
(``data/cifar10.py:_synthetic_cifar10``) is a fixed 50,000-image set cut
into K contiguous shards, which cannot give 1,024 images to each of 64
clients.  This makes exactly ``K x samples_per_client`` images from the
seed instead, with the same learnable structure (one low-frequency
template per class plus pixel noise), and offers the attributes and
methods ``BlockwiseFederatedTrainer`` reads from its ``data`` argument.
Noise is uniform bytes added in uint8, not Gaussian floats clipped:
generating the 400 MB of a 128-client cell takes about a second instead
of most of a minute, and set-up is what every run of every later check
pays.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

NUM_CLASSES = 10
IMAGE_SHAPE = (32, 32, 3)


class SeededShards:
    """``K`` clients x ``samples_per_client`` CIFAR-shaped uint8 images.

    ``samples_per_client`` must be a multiple of ``batch``: every
    minibatch is full, ``remainder`` is 0 and the engine takes its
    plain-BN path, as it does on the reference's 5,000-image shards cut
    to whole batches."""

    source = "synthetic"

    def __init__(self, K: int, batch: int, samples_per_client: int,
                 seed: int, biased_input: bool, n_test: int = 128):
        if samples_per_client % batch:
            raise ValueError(
                f"samples_per_client={samples_per_client} is not a multiple "
                f"of batch={batch}")
        self.K, self.batch = K, batch
        self.steps = samples_per_client // batch
        self.remainder = 0
        rng = np.random.default_rng([int(seed), 0x5EED])
        # templates in [0, 127] plus noise in [0, 127]: pixel values
        # 0..254 with no clipping and no overflow, all in uint8
        coarse = rng.integers(0, 128, size=(NUM_CLASSES, 4, 4, 3),
                              dtype=np.uint8)
        self._templates = np.repeat(np.repeat(coarse, 8, axis=1), 8, axis=2)
        self._train_x, self._train_y = self._make(
            rng, K * samples_per_client)
        self._train_x = self._train_x.reshape(K, samples_per_client,
                                              *IMAGE_SHAPE)
        self._train_y = self._train_y.reshape(K, samples_per_client)
        self._test_x, self._test_y = self._make(rng, n_test)
        ks = np.arange(K, dtype=np.float32)
        if biased_input:
            # per-client Normalize((0.5+k/100, 0.5-k/100, 0.5)) with the same
            # triple as mean and std (reference federated_multi.py:60-71).
            # The reference has ten clients; beyond k = 40 the second
            # channel's std would near 0, so the bias repeats every ten
            kk = ks % 10
            m = np.stack([0.5 + kk / 100.0, 0.5 - kk / 100.0,
                          np.full(K, 0.5, np.float32)], axis=1)
        else:
            m = np.full((K, 3), 0.5, np.float32)
        self._norm = np.stack([m, m], axis=1).astype(np.float32)

    def _make(self, rng, n: int, chunk: int = 4096
              ) -> Tuple[np.ndarray, np.ndarray]:
        y = rng.integers(0, NUM_CLASSES, size=n).astype(np.int32)
        x = np.empty((n,) + IMAGE_SHAPE, np.uint8)
        for lo in range(0, n, chunk):       # temporaries stay in cache
            part = x[lo:lo + chunk]
            part[...] = rng.integers(0, 256, size=part.shape, dtype=np.uint8)
            part >>= 1
            part += self._templates[y[lo:lo + chunk]]
        return x, y

    # -- what the engine reads -----------------------------------------
    @property
    def samples_per_client(self) -> int:
        return self._train_x.shape[1]

    @property
    def norm_stats(self) -> np.ndarray:
        """Per-client (mean, std) ``[K, 2, 3]``."""
        return self._norm

    def train_shards_raw(self) -> Tuple[np.ndarray, np.ndarray]:
        return self._train_x, self._train_y

    def epoch_batches_raw(self, seed: int):
        """One shuffled epoch ``([K, steps, B, 32, 32, 3] u8, labels,
        weights)`` for the engine's host-staged path (the device-resident
        path shuffles on the device and never calls this)."""
        rng = np.random.default_rng(seed)
        n = self.samples_per_client
        perm = np.stack([rng.permutation(n) for _ in range(self.K)])
        rows = np.arange(self.K)[:, None]
        shape = (self.K, self.steps, self.batch)
        return (self._train_x[rows, perm].reshape(*shape, *IMAGE_SHAPE),
                self._train_y[rows, perm].reshape(shape),
                np.ones(shape, np.float32))

    def test_batches_raw(self, batch=None):
        b = batch or self.batch
        tsteps = -(-len(self._test_x) // b)
        pad = np.arange(tsteps * b) % len(self._test_x)
        w = (np.arange(tsteps * b) < len(self._test_x)).astype(np.float32)
        return (self._test_x[pad].reshape(tsteps, b, *IMAGE_SHAPE),
                self._test_y[pad].reshape(tsteps, b), w.reshape(tsteps, b))
