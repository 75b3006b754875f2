"""Arrival schedule ``poisson``: the arrivals of a Poisson process of rate
``rate_edges_per_s`` conditioned on its count in every second of the
window (sorted uniform times within each second).

Every seed then offers the same work, the same number of edges in each
second, in another order: the time a tick fills, which a tail below the
knee follows one for one, moves by hundredths of a second from seed to
seed, and not by the second or so that conditioning on the window's
count alone leaves.
"""

from __future__ import annotations

import math

import numpy as np

__all__ = ["arrival_offsets"]


def arrival_offsets(mix: dict, n_edges: int, seconds: float,
                    rng: np.random.Generator) -> np.ndarray:
    edges = np.arange(math.ceil(seconds) + 1, dtype=np.float64)
    edges[-1] = seconds
    edges = np.minimum(edges, seconds)
    # edges due by the end of each second, spread evenly over the window
    cum = np.floor(edges * (n_edges / seconds)).astype(np.int64)
    cum[-1] = n_edges
    counts = np.diff(cum)
    lo = np.repeat(edges[:-1], counts)
    width = np.repeat(np.diff(edges), counts)
    u = rng.uniform(0.0, 1.0, n_edges)
    # sort within each second: offsets of one second never pass the next's
    order = np.lexsort((u, np.repeat(np.arange(counts.size), counts)))
    return lo + width * u[order]
