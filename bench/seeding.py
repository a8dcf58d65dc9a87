"""Keys and generators from a run's ``--seed``, which may exceed 32 bits,
and the worst of a set of compared numbers."""
from __future__ import annotations

import numpy as np


def key(seed: int):
    """A JAX key that depends on every bit of ``seed``: ``jax.random.key``
    keeps only the low 32 bits, so the next 32 are folded in."""
    import jax
    s = seed % (1 << 64)
    return jax.random.fold_in(jax.random.key(s & 0xFFFFFFFF), s >> 32)


def rng(seed: int, stream: int) -> np.random.Generator:
    """A host generator for ``stream`` of ``seed``."""
    return np.random.default_rng([seed % (1 << 64), stream])


def worst(values) -> float:
    """The largest of ``values``; infinity where one is not finite, so that
    a NaN can never hide behind ``max``."""
    values = [float(v) for v in values]
    if not all(np.isfinite(values)):
        return float("inf")
    return max(values, default=0.0)
