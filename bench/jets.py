"""Synthetic jet events, copied from the program's ``repro.data.jet_batch``.

The benchmark keeps its own copy so that a later change to the program's data
pipeline cannot change what the benchmark offers. Each class has its own
mean direction, spread and multiplicity decay; later particles decay toward
zero, as pT-ordered constituents do.
"""
from __future__ import annotations

import numpy as np


def jet_batch(n_particles: int, n_features: int, n: int, seed: int,
              n_classes: int = 5, class_seed: int = 0) -> np.ndarray:
    """(n, n_particles, n_features) float32 jets drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, n_classes, n)
    base = np.random.default_rng(class_seed)
    mu = base.normal(0, 0.8, (n_classes, n_features))
    sig = 0.4 + base.uniform(0, 0.8, (n_classes, n_features))
    decay = 0.85 + 0.1 * base.uniform(0, 1, n_classes)
    x = rng.normal(0, 1, (n, n_particles, n_features))
    x = x * sig[labels][:, None, :] + mu[labels][:, None, :]
    ranks = np.arange(n_particles)[None, :, None]
    x = x * (decay[labels][:, None, None] ** ranks)
    return x.astype(np.float32)
