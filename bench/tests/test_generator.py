"""The benchmark's generator against the program's
(``repro.graphstore.generators``) at a small size: the same degree
distribution, the same planted rings, the same join."""

from __future__ import annotations

import numpy as np

from bench import generator as g
from bench.spec import Bench
from repro.graphstore.generators import (make_power_law_graph,
                                         make_transaction_stream)

N, M = 20_000, 200_000
CFG = {"accounts": N, "background_edges": M, "alpha": 0.3, "rings": 2,
       "ring_size": 12, "ring_edges": 1600, "actors": 1}
BACKLOG = {"stream": "background_join", "join_edges": [250, 350],
           "join_density_off_grid": "bfloat16",
           "background_amount": [2.0, 1.0], "fraud_amount": [5.0, 0.3]}
KIND = Bench().kind("background_join")


def _streamed(base, mix, n, seed):
    return KIND.make_streamed(base, mix, n, g.seeded(seed, g.WINDOW))


def _degree_cdf(src, dst, n, top=200):
    deg = np.bincount(src, minlength=n) + np.bincount(dst, minlength=n)
    hist = np.bincount(np.minimum(deg, top), minlength=top + 1)
    return np.cumsum(hist) / hist.sum()


def test_degree_distribution_matches_the_program():
    b = g.make_base(CFG, 3)
    bg = b.src.shape[0] - 2 * 1600
    s, d, _ = make_power_law_graph(N, M, seed=3, alpha=0.3)
    ours = _degree_cdf(b.src[:bg], b.dst[:bg], N)
    theirs = _degree_cdf(s, d, N)
    # Kolmogorov-Smirnov distance of two samples of 20k degrees
    assert np.abs(ours - theirs).max() < 0.03
    # the most popular account is as popular in both
    top_ours = np.bincount(b.src[:bg], minlength=N).max()
    top_theirs = np.bincount(s, minlength=N).max()
    assert abs(top_ours - top_theirs) < 0.15 * top_theirs


def test_rings_are_planted_as_the_program_plants_them():
    b = g.make_base(CFG, 4)
    ref = make_transaction_stream(n=2000, m=10_000, seed=4)
    assert len(b.rings) == 2
    tail_s, tail_d = b.src[-3200:], b.dst[-3200:]
    for k, ring in enumerate(b.rings):
        assert ring.shape == (12,) and np.unique(ring).size == 12
        s, d = tail_s[1600 * k:1600 * (k + 1)], tail_d[1600 * k:1600 * (k + 1)]
        assert np.isin(s, ring).all() and np.isin(d, ring).all()
        assert (s != d).all()
        # all 132 ordered pairs occur, about equally often
        pairs = np.unique(s * 10**7 + d, return_counts=True)[1]
        assert pairs.size == 132 and pairs.max() < 3 * pairs.mean()
    # the program's base graph ends with the same shape of ring edges
    rs, rd = ref.base_src[-3200:], ref.base_dst[-3200:]
    assert np.unique(rs[:1600]).size == 12 and (rs != rd).all()


def test_join_is_placed_as_the_program_places_it():
    b = g.make_base(CFG, 5)
    s = _streamed(b, BACKLOG, 4096, 5)
    actor = b.actors[0]
    assert actor == N and b.n_vertices == N + 1
    hit = (s.src == actor) | (s.dst == actor)
    idx = np.flatnonzero(hit)
    assert s.src.shape == (4096,)
    # one burst, placed whole
    assert 250 <= idx.size <= 350 and (np.diff(idx) == 1).all()
    other = np.where(s.src[idx] == actor, s.dst[idx], s.src[idx])
    assert np.isin(other, b.rings[0]).all()
    long = _streamed(b, BACKLOG, 200_000, 5)
    j = np.flatnonzero((long.src == actor) | (long.dst == actor))
    out_share = (long.src[j] == actor).mean()
    assert 0.35 < out_share < 0.65  # both directions
    starts = [np.flatnonzero((x.src == actor) | (x.dst == actor))[0]
              for x in (_streamed(b, BACKLOG, 4096, sd) for sd in range(40))]
    assert min(starts) < 1024 and max(starts) > 3072  # anywhere
    sizes = {int(((x.src == actor) | (x.dst == actor)).sum())
             for x in (_streamed(b, BACKLOG, 100_000, sd) for sd in range(12))}
    assert len(sizes) > 6 and min(sizes) >= 250 and max(sizes) <= 350
    assert (s.src != s.dst).all()
    ref = make_transaction_stream(n=2000, m=10_000, seed=5)
    rhit = (ref.inc_src == 2000) | (ref.inc_dst == 2000)
    assert rhit.sum() == 300 and (np.diff(np.flatnonzero(rhit)) == 1).all()


def test_join_keeps_the_community_density_off_the_bfloat16_grid():
    """Ring 1 with the actor (13 accounts, 1600 + J transactions) and both
    rings with it (25, 3200 + J): neither density is a bfloat16 number,
    for every length the mix can draw, so the control cannot read the
    same final density on any seed."""
    import ml_dtypes

    b = g.make_base(CFG, 6)
    lengths = KIND.join_lengths(b, 250, 350, "bfloat16")
    assert 60 < lengths.size < 101
    for mass, size in ((1600, 13), (3200, 25)):
        d = (mass + lengths).astype(np.float32) / np.float32(size)
        assert (d.astype(ml_dtypes.bfloat16).astype(np.float32) != d).all()
    assert 300 not in lengths and 325 not in lengths  # 3500 / 25 = 140
    assert KIND.join_lengths(b, 250, 350, None).size == 101
    drawn = {int(((x.src == b.actors[0]) | (x.dst == b.actors[0])).sum())
             for x in (_streamed(b, BACKLOG, 4096, sd) for sd in range(60))}
    assert drawn <= set(lengths.tolist())


def test_same_seed_same_inputs_large_seed():
    seed = 2**31 + 12345
    a, b = g.make_base(CFG, seed), g.make_base(CFG, seed)
    assert np.array_equal(a.src, b.src) and np.array_equal(a.dst, b.dst)
    c = g.make_base(CFG, seed + 1)
    assert not np.array_equal(a.src, c.src)
    mix = {"arrivals": "poisson", "rate_edges_per_s": 100.0}
    poisson = Bench().kind("poisson")
    o1 = poisson.arrival_offsets(mix, 500, 5.0, g.seeded(seed, g.ARRIVALS))
    o2 = poisson.arrival_offsets(mix, 500, 5.0,
                                 g.seeded(seed + 1, g.ARRIVALS))
    assert o1.shape == o2.shape == (500,)  # same work, another order
    assert (np.diff(o1) >= 0).all() and 0 <= o1[0] and o1[-1] < 5.0
    assert not np.array_equal(o1, o2)
    # the same count in every second, whatever the seed
    for o in (o1, o2):
        assert np.bincount(o.astype(int), minlength=5).tolist() == [100] * 5
    # a window that is not whole seconds still holds every edge in it
    o3 = poisson.arrival_offsets(mix, 7, 2.5, g.seeded(seed, g.ARRIVALS))
    assert o3.shape == (7,) and (np.diff(o3) >= 0).all() and o3[-1] < 2.5
