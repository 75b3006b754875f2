"""The staged peel rounds: the edge buffer steps down a static size
ladder as the restricted set shrinks, and the result is the one that
full-buffer rounds give, bit for bit, on integer weights."""

from __future__ import annotations

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.peel import (
    _BulkState,
    _round_step,
    _run_rounds,
    _run_stages,
    edge_ladder,
)
from repro.graphstore.structs import device_graph_from_coo

EPS = 0.1
E_CAP = 1 << 20


@pytest.mark.parametrize("e_capacity, expect", [
    # the Grab4 buffer: 32.5M, 8.13M, 2.03M, 508K, 127K and the 64K floor
    (32_504_320, (32_504_320, 8_126_464, 2_031_616, 507_904, 126_976,
                  65_536)),
    (1 << 20, (1 << 20, 1 << 18, 1 << 16)),
    (4 * 65_536, (4 * 65_536, 65_536)),
    # below four times the floor: one stage, today's buffer
    (4 * 65_536 - 512, (4 * 65_536 - 512,)),
    (1536, (1536,)),
])
def test_edge_ladder(e_capacity, expect):
    ladder = edge_ladder(e_capacity)
    assert ladder == expect
    assert ladder[0] == e_capacity
    assert all(s % 512 == 0 for s in ladder[1:])
    assert all(b < a for a, b in zip(ladder, ladder[1:]))
    assert all(s >= 65_536 for s in ladder[1:])


def _wide_graph(seed=0, n=40_000, m=700_000):
    """Integer weights on a skewed graph with a planted dense block, in a
    2^20-slot buffer: three stages, and several rounds in each."""
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, n + 1) ** 0.8
    p /= p.sum()
    src, dst = rng.choice(n, m, p=p), rng.choice(n, m, p=p)
    blk = rng.integers(0, 30, (4000, 2))
    src = np.concatenate([src, blk[:, 0]])
    dst = np.concatenate([dst, blk[:, 1]])
    keep = src != dst
    src, dst = src[keep], dst[keep]
    c = rng.integers(1, 4, src.shape[0]).astype(np.float32)
    a = rng.integers(0, 3, n).astype(np.float32)
    return device_graph_from_coo(n, src, dst, c, a=a, e_capacity=E_CAP)


def _init(g, keep):
    """The warm peel's start on the set ``keep`` (``bulk_peel_warm``)."""
    V = g.n_capacity
    live = keep & g.vertex_mask
    both = live[g.src] & live[g.dst] & g.edge_mask
    cm = jnp.where(both, g.c, 0.0)
    w0 = jnp.where(live, g.a, 0.0)
    w0 = w0 + jax.ops.segment_sum(cm, g.src, num_segments=V)
    w0 = w0 + jax.ops.segment_sum(cm, g.dst, num_segments=V)
    return _BulkState(
        w=w0, active=live, edge_alive=both,
        f=jnp.sum(jnp.where(live, g.a, 0.0)) + jnp.sum(cm),
        n_act=jnp.sum(live), level=jnp.full(V, -1, jnp.int32),
        best_g=jnp.float32(-jnp.inf), best_level=jnp.int32(0),
        round_=jnp.int32(0))


@pytest.mark.parametrize("suffix", ["all", "warm"])
@pytest.mark.parametrize("max_rounds", [20, 0])
def test_staged_rounds_match_full_buffer_rounds(max_rounds, suffix):
    g = _wide_graph()
    keep = jnp.ones(g.n_capacity, bool)
    if suffix == "warm":  # a warm suffix: a random 90% of the vertices
        keep = jnp.asarray(np.random.default_rng(1).random(g.n_capacity)
                           < 0.9)
    init = _init(g, keep)
    full = jax.jit(lambda s: _run_rounds(
        partial(_round_step, g.src, g.dst, g.c, g.a, EPS, False), s,
        max_rounds))(init)
    staged, _ = jax.jit(lambda s: _run_stages(
        g.src, g.dst, g.c, g.a, EPS, False, s, max_rounds))(init)
    for field in ("level", "best_level", "best_g", "round_", "w"):
        np.testing.assert_array_equal(np.asarray(getattr(staged, field)),
                                      np.asarray(getattr(full, field)),
                                      err_msg=field)
    if max_rounds:
        assert int(staged.round_) == max_rounds
    # the same rounds, counted: all three stages ran, and the set drained
    _, (rv, re, rs) = jax.jit(lambda s: _run_stages(
        g.src, g.dst, g.c, g.a, EPS, False, s, 20, counters=True))(init)
    rv, re, rs = map(np.asarray, (rv, re, rs))
    assert set(rs[rv > 0]) == set(edge_ladder(E_CAP))
    assert rv[-1] == 0 and (rs[rv == 0] == 0).all()
    assert (re <= rs).all()
