"""Draws shared by the traffic generators."""
from __future__ import annotations

import numpy as np


def zipf_cdf(n_items: int, theta: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n_items + 1, dtype=np.float64) ** theta
    cdf = np.cumsum(w)
    return cdf / cdf[-1]


def zipf_draw(rng, cdf: np.ndarray, size: int) -> np.ndarray:
    """Ranks in ``[0, len(cdf))`` drawn with probability ``~ 1/(r+1)^theta``
    (``cdf`` from :func:`zipf_cdf`)."""
    return np.minimum(np.searchsorted(cdf, rng.random(size)), cdf.size - 1)


def sizes_in_blocks(rng, sizes, count: int) -> np.ndarray:
    blocks = -(-count // len(sizes))
    return np.concatenate([rng.permutation(sizes) for _ in range(blocks)]
                          )[:count]
