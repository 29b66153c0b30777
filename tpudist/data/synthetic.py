"""Synthetic dataset: deterministic random images for benchmarks and tests
(no reference equivalent — the reference hard-requires an ImageNet mount,
``distributed.py:44``; this removes that requirement)."""

from __future__ import annotations

import numpy as np


class SyntheticDataset:
    """Index-addressable fake ImageFolder: image i is deterministic in
    (seed, i), so runs are reproducible and loss decrease is testable."""

    def __init__(self, num_samples: int = 1024, image_size: int = 224,
                 num_classes: int = 1000, seed: int = 0):
        self.num_samples = num_samples
        self.image_size = image_size
        self.num_classes = num_classes
        self.seed = seed

    def __len__(self) -> int:
        return self.num_samples

    def __getitem__(self, index: int):
        rng = np.random.default_rng((self.seed, index))
        img = rng.standard_normal(
            (self.image_size, self.image_size, 3)).astype(np.float32)
        label = int(rng.integers(0, self.num_classes))
        # Plant a weak class-dependent signal so training can learn it.
        img[:4, :4, :] += label % 7
        return img, label


class SyntheticTokens:
    """Index-addressable fake corpus of token rows: row i is deterministic
    in (seed, i). A row is ``seq_len`` int32 ids over ``vocab_size`` with
    the next id as each position's target; the ids walk the vocabulary from
    a random start by a fixed stride (a random id one time in eight), so a
    model that learns ``next = this + stride`` sees its loss fall."""

    def __init__(self, num_samples: int = 1024, seq_len: int = 128,
                 vocab_size: int = 1024, seed: int = 0):
        self.num_samples = num_samples
        self.seq_len = seq_len
        self.vocab_size = vocab_size
        self.seed = seed

    def __len__(self) -> int:
        return self.num_samples

    def __getitem__(self, index: int):
        rng = np.random.default_rng((self.seed, index))
        n = self.seq_len + 1
        ids = (int(rng.integers(0, self.vocab_size))
               + 7 * np.arange(n, dtype=np.int64)) % self.vocab_size
        noise = rng.random(n) < 0.125
        ids[noise] = rng.integers(0, self.vocab_size, int(noise.sum()))
        ids = ids.astype(np.int32)
        return ids[:-1], ids[1:]
