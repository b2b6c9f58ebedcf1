"""Training traffic: real epochs over the training vertices, from a seed.

Each epoch is a fresh permutation of the training vertices; iteration
``g`` takes the next ``roots_per_iteration`` roots of its epoch, split
evenly over the workers' models, and samples with its own seed. Every
seed gives the same sizes in another order, and nothing repeats within
an epoch.
"""
from __future__ import annotations

import numpy as np


class Traffic:
    def __init__(self, params: dict, train_vertices: np.ndarray,
                 workers: int, seed: int):
        self.batch = int(params["roots_per_iteration"])
        if self.batch % workers or self.batch > train_vertices.size:
            raise ValueError(f"{self.batch} roots per iteration do not split "
                             f"over {workers} workers from "
                             f"{train_vertices.size} training vertices")
        self.workers = workers
        self.train = np.asarray(train_vertices, np.int64)
        self.seed = int(seed)
        self.per_epoch = self.train.size // self.batch
        self._perm: dict = {}
        # sampling seeds: one per iteration, distinct across iterations,
        # drawn from the run's seed; at least 2**20, so that a Trainer's
        # seed base (this less an epoch offset) stays positive
        rng = np.random.default_rng([self.seed, 1])
        self.seed_base = int(rng.integers(1 << 20, 1 << 40))

    def _epoch(self, e: int) -> np.ndarray:
        perm = self._perm.get(e)
        if perm is None:
            perm = np.random.default_rng([self.seed, 0, e]).permutation(
                self.train)
            self._perm = {e: perm}
        return perm

    def roots(self, g: int) -> np.ndarray:
        """The roots of global iteration ``g`` (all models together)."""
        e, j = divmod(int(g), self.per_epoch)
        return self._epoch(e)[j * self.batch:(j + 1) * self.batch]

    def per_model(self, g: int) -> list:
        return np.split(self.roots(g), self.workers)

    def sample_seed(self, g: int) -> int:
        return self.seed_base + int(g)
