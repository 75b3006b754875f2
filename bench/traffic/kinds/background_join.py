"""Stream kind ``background_join``: background transactions with the
paper's Sec. 4.3 join embedded.

Background: transactions drawn as the base graph's (Zipf-popular
endpoints; a self-transfer is redrawn, so the stream keeps its length),
lognormal amounts ``background_amount = [mu, sigma]``.

The join: the configuration's first actor account, new to the graph,
sends a burst of transactions to members of the first ring, half of them
in each direction, amounts ``fraud_amount``.  The burst is placed whole,
at a uniformly random place in the stream.  Its length is drawn
uniformly from ``join_edges = [lo, hi]``, among the lengths that leave
the density of the community the join makes off the grid of
``join_density_off_grid`` (a precision, e.g. ``"bfloat16"``): for the
first ring with the actor, and for every ring with the actor, mass over
accounts.  Under DG a density is a quotient of integers, and a length
that puts it on the grid of the lower precision would let the control of
``correct`` (the reference in that precision) read the same answer.
"""

from __future__ import annotations

import numpy as np

from bench.generator import Base, Streamed, inverse_cdf, zipf_endpoints

__all__ = ["join_lengths", "make_streamed"]


def join_lengths(base: Base, lo: int, hi: int,
                 off_grid: str | None) -> np.ndarray:
    """The admissible join lengths of ``[lo, hi]``."""
    j = np.arange(lo, hi + 1)
    if off_grid is None:
        return j
    import ml_dtypes

    grid = np.dtype(getattr(ml_dtypes, off_grid))
    sizes = [r.shape[0] for r in base.rings]
    ok = np.ones(j.shape, bool)
    for mass, size in ((base.ring_edges[0], sizes[0]),
                       (sum(base.ring_edges), sum(sizes))):
        g = (mass + j).astype(np.float32) / np.float32(size + 1)
        ok &= g.astype(grid).astype(np.float32) != g
    if not ok.any():
        raise ValueError(f"no join length in [{lo}, {hi}] is off the "
                         f"{off_grid} grid")
    return j[ok]


def make_streamed(base: Base, mix: dict, n_edges: int,
                  rng: np.random.Generator) -> Streamed:
    if base.actors.shape[0] < 1:
        raise ValueError("a join needs an actor account in the configuration")
    lengths = join_lengths(base, *mix["join_edges"],
                           mix.get("join_density_off_grid"))
    k = min(int(rng.choice(lengths)), n_edges)
    at = int(rng.integers(0, n_edges - k + 1))
    bg = n_edges - k
    src = zipf_endpoints(rng, base.rank_src, base.p, bg)
    dst = zipf_endpoints(rng, base.rank_dst, base.p, bg)
    clash = np.flatnonzero(src == dst)
    while clash.size:
        dst[clash] = inverse_cdf(rng, base.rank_dst, base.p, clash.size)
        clash = clash[src[clash] == dst[clash]]
    bamt = rng.lognormal(*mix["background_amount"], bg)
    fs = np.full(k, base.actors[0], np.int64)
    fd = rng.choice(base.rings[0], size=k).astype(np.int64)
    flip = rng.random(k) < 0.5
    fs, fd = np.where(flip, fd, fs), np.where(flip, fs, fd)
    famt = rng.lognormal(*mix["fraud_amount"], k)
    return Streamed(
        np.concatenate([src[:at], fs, src[at:]]),
        np.concatenate([dst[:at], fd, dst[at:]]),
        np.concatenate([bamt[:at], famt, bamt[at:]]),
    )
