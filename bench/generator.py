"""The benchmark's traffic generator: the base graph, and the parts that
every kind of streamed traffic shares.

A configuration (``bench/configs/<name>.json``) fixes the base graph: the
accounts, the background transactions with Zipf-popular endpoints, and the
planted rings.  A traffic mix (``bench/traffic/<mix>.json``) is data: the
parameters of what streams and when.  It names a stream kind and an
arrival schedule, each a file of its own under ``bench/traffic/kinds/``
(``bench/spec.py`` finds them by name), so a mix of a new kind is a new
file and no existing one changes:

* a stream kind defines ``make_streamed(base, mix, n_edges, rng)``, which
  returns ``n_edges`` streamed transactions (:class:`Streamed`);
* an open-loop schedule defines ``arrival_offsets(mix, n_edges, seconds,
  rng)``, when each window edge is created, in seconds after the window
  opens.  ``"arrivals": "backlog"`` needs no file: every window edge is
  due when the window opens (``bench/harness.py``).

The distributions are those of ``repro.graphstore.generators`` (endpoint
popularity ``rank ** -alpha`` over a random rank-to-account map, rings of
uniform ordered pairs), drawn in bulk: endpoint ranks come from one
multinomial over the ranks and one shuffle, which is the same
distribution as independent inverse-CDF draws and costs a few seconds at
Grab4 size instead of ~50.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

__all__ = ["Base", "Streamed", "inverse_cdf", "make_base", "ring_pairs",
           "seeded", "window_edges", "zipf_endpoints"]

# sub-streams of one seed: each draw has its own, so a change to one part
# of the traffic leaves the others as they were
BASE, WARMUP, WINDOW, ARRIVALS = 0, 1, 2, 3


def seeded(seed: int, part: int) -> np.random.Generator:
    """A generator for one part of the inputs of ``--seed``: any whole
    number, negative and beyond 64 bits included."""
    return np.random.default_rng([abs(int(seed)), int(seed < 0), part])


@dataclass
class Base:
    """The base graph with its planted fraud."""

    n_vertices: int  # accounts + actor accounts
    src: np.ndarray  # int64 [m]
    dst: np.ndarray
    amt: np.ndarray  # float64 [m]
    rings: list[np.ndarray]  # account ids of each planted ring
    ring_edges: list[int]  # transactions of each planted ring
    actors: np.ndarray  # account ids reserved for joining actors
    rank_src: np.ndarray  # account of each popularity rank, as source
    rank_dst: np.ndarray  # ... as destination
    p: np.ndarray  # popularity of each rank

    @property
    def fraud_accounts(self) -> np.ndarray:
        """Every planted account: the rings and the actors."""
        return np.unique(np.concatenate([*self.rings, self.actors]))


@dataclass
class Streamed:
    """Streamed transactions, in arrival order."""

    src: np.ndarray  # int64 [k]
    dst: np.ndarray
    amt: np.ndarray  # float64 [k]


def zipf_endpoints(rng: np.random.Generator, rank_to_account: np.ndarray,
                   p: np.ndarray, m: int) -> np.ndarray:
    """``m`` independent draws of an account with rank popularity ``p``:
    how many times each rank is drawn (multinomial), then a shuffle."""
    counts = rng.multinomial(m, p)
    ranks = np.repeat(np.arange(p.shape[0], dtype=np.int64), counts)
    rng.shuffle(ranks)
    return rank_to_account[ranks]


def inverse_cdf(rng: np.random.Generator, rank_to_account: np.ndarray,
                 p: np.ndarray, m: int) -> np.ndarray:
    """A few draws of the same distribution as :func:`zipf_endpoints`."""
    ranks = np.searchsorted(np.cumsum(p), rng.random(m) * p.sum(), side="right")
    return rank_to_account[np.minimum(ranks, p.shape[0] - 1)]


def _popularity(n: int, alpha: float) -> np.ndarray:
    p = np.arange(1, n + 1, dtype=np.float64) ** (-alpha)
    return p / p.sum()


def ring_pairs(rng: np.random.Generator, ring: np.ndarray, k: int):
    """``k`` ordered pairs of distinct ring members, uniform."""
    u = rng.integers(0, ring.shape[0], k)
    v = (u + rng.integers(1, ring.shape[0], k)) % ring.shape[0]
    return ring[u].astype(np.int64), ring[v].astype(np.int64)


def make_base(cfg: dict, seed: int) -> Base:
    """The configuration's base graph from ``seed``: background
    transactions between Zipf-popular accounts (self-transfers dropped),
    then each ring's transactions."""
    rng = seeded(seed, BASE)
    n, m = int(cfg["accounts"]), int(cfg["background_edges"])
    p = _popularity(n, float(cfg["alpha"]))
    rank_src, rank_dst = rng.permutation(n), rng.permutation(n)
    src = zipf_endpoints(rng, rank_src, p, m)
    dst = zipf_endpoints(rng, rank_dst, p, m)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    amt = rng.lognormal(2.0, 1.0, src.shape[0])
    rings, rs, rd, ra = [], [src], [dst], [amt]
    for _ in range(int(cfg["rings"])):
        ring = np.sort(rng.choice(n, size=int(cfg["ring_size"]), replace=False))
        rings.append(ring)
        s, d = ring_pairs(rng, ring, int(cfg["ring_edges"]))
        rs.append(s)
        rd.append(d)
        ra.append(rng.lognormal(3.5, 0.3, s.shape[0]))
    n_actors = int(cfg["actors"])
    return Base(
        n_vertices=n + n_actors,
        src=np.concatenate(rs), dst=np.concatenate(rd),
        amt=np.concatenate(ra), rings=rings,
        ring_edges=[int(cfg["ring_edges"])] * len(rings),
        actors=np.arange(n, n + n_actors, dtype=np.int64),
        rank_src=rank_src, rank_dst=rank_dst, p=p,
    )


def window_edges(mix: dict, seconds: float) -> int:
    """Edges an open-loop window offers: its rate times its length."""
    return max(1, int(math.floor(float(mix["rate_edges_per_s"]) * seconds)))
