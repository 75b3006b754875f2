"""The warm re-peel's seed read from the maintained weights.

When a tick's affected suffix holds every live vertex (``r0 = 0``), the
warm re-peel reads its seed from the state's ``w0`` after the batch
(``core.incremental._next_w0``) instead of summing it from the edge
buffer.  On integer weights the two seeds, and everything the re-peel
computes from them, are bit for bit the same.  A small integer-weight
stream of insert ticks, window expiries and drop-mask slides, each
batch touching a level-0 vertex, with a self-loop and a duplicated edge,
is replayed once per weighting; the tests read its per-tick records.
Non-integer weights, and integer weights past 2^24, clear the state's
``w0_exact`` and the seed is summed on every tick after.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.incremental import (
    _expire,
    full_refresh,
    _next_w0,
    _slide_epilogue,
    _slide_prologue,
    init_state,
    insert_and_maintain,
    slide_and_maintain,
    tick_counters,
)
from repro.core.peel import (
    _seed_by_sums,
    _seed_from_weights,
    _warm_seed,
    bulk_peel_warm,
)
from repro.graphstore.generators import make_transaction_stream
from repro.graphstore.structs import (
    WindowExpiry,
    append_edges,
    device_graph_from_coo,
)
from repro.serve import EngineSpec, SpadeService

from test_tick_counters import _numpy_recount
from test_window_differential import _spade_dg

EPS = 0.1
N, V_CAP = 40, 48
B, N_VALID, WINDOW, MAX_ROUNDS, TICKS = 8, 6, 2, 6, 8


def _base(rng, weights):
    m = 90
    src = rng.integers(0, 30, m)  # accounts 30-39 start isolated (level 0)
    dst = (src + 1 + rng.integers(0, 29, m)) % 30
    src = np.concatenate([src, [3, 5, 5]])  # a self-loop, a duplicated edge
    dst = np.concatenate([dst, [3, 7, 7]])
    if weights == "dg":
        return src, dst, np.ones(src.shape[0], np.float32), None
    return (src, dst, rng.integers(1, 5, src.shape[0]).astype(np.float32),
            rng.integers(0, 3, N).astype(np.float32))


def _batch(rng, level, weights):
    """``B`` lanes, ``N_VALID`` valid; lane 0 touches a level-0 account
    (so ``r0 = 0``), lane 1 is a self-loop and lane 2 repeats lane 0."""
    lv0 = np.flatnonzero(level[:N] == 0)
    src = rng.integers(0, N, B)
    dst = (src + 1 + rng.integers(0, N - 1, B)) % N
    src[0] = rng.choice(lv0)
    dst[1] = src[1]
    src[2], dst[2] = src[0], dst[0]
    c = np.ones(B, np.float32) if weights == "dg" \
        else rng.integers(1, 5, B).astype(np.float32)
    return (jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32),
            jnp.asarray(c), jnp.asarray(np.arange(B) < N_VALID))


def _np(tree):
    return jax.tree_util.tree_map(np.asarray, tree)


def _replay(weights):
    """Per tick: both seeds, the post-tick state against the DG
    reference, and the counter vector against a numpy recount."""
    rng = np.random.default_rng(7 if weights == "dg" else 8)
    src, dst, c, a = _base(rng, weights)
    m_base = src.shape[0]
    e_cap = -(-(m_base + (WINDOW + 1) * B) // 64) * 64
    state = init_state(device_graph_from_coo(
        N, src, dst, c, a=a, n_capacity=V_CAP, e_capacity=e_cap), eps=EPS)
    ref = _spade_dg().SpadeDG(N, src, dst, eps=EPS, max_rounds=MAX_ROUNDS,
                              window_ticks=WINDOW) if weights == "dg" else None
    ring: list[int] = []
    records = []
    for t in range(TICKS):
        level0 = np.asarray(state.level)
        bs, bd, bc, valid = _batch(rng, level0, weights)
        g0 = state.graph
        drop, kind, drop_ends = None, "insert", np.zeros(0, np.int64)
        if len(ring) >= WINDOW:
            cnt0 = ring.pop(0)
            slot = np.arange(e_cap)
            mask = (slot >= m_base) & (slot < m_base + cnt0)
            drop_ends = np.concatenate([np.asarray(g0.src)[mask],
                                        np.asarray(g0.dst)[mask]])
            if t % 2:
                kind, drop = "mask", jnp.asarray(mask)
            else:
                kind = "expiry"
                drop = WindowExpiry(jnp.int32(m_base), jnp.int32(cnt0),
                                    WINDOW * B)
        # the seed both ways, on the post-tick graph
        bk = _slide_prologue(state, drop, bs, bd, valid)
        g, n_removed = (g0, 0) if drop is None else _expire(g0, drop)
        g = append_edges(g, state.edge_count - n_removed, bs, bd, bc,
                         valid=valid)
        w_next, exact = _next_w0(state, bk, bs, bd, bc, valid,
                                 with_drops=drop is not None)
        rec = dict(kind=kind, r0=int(bk.r0),
                   live=np.asarray(bk.keep & g.vertex_mask),
                   reused=_np(_warm_seed(g, bk.keep, w_next, exact)),
                   summed=_np(_seed_by_sums(g, bk.keep & g.vertex_mask)))
        args = (bs, bd, bc, valid)
        if drop is None:
            state, cnt = insert_and_maintain(
                state, *args, eps=EPS, max_rounds=MAX_ROUNDS, counters=True)
        else:
            state, cnt = slide_and_maintain(
                state, drop, *args, eps=EPS, max_rounds=MAX_ROUNDS,
                counters=True)
        ring.append(N_VALID)
        rec.update(state=_np(state), peel_weights=np.asarray(
            state.graph.peel_weights()), counts=np.asarray(cnt),
            recount=_numpy_recount(level0, state.graph, np.asarray(bs),
                                   np.asarray(bd), np.asarray(valid),
                                   drop_ends, MAX_ROUNDS))
        if ref is not None:
            v = np.asarray(valid)
            ref.tick(np.asarray(bs)[v], np.asarray(bd)[v])
            rec["ref"] = dict(level=ref.level.copy(), best_g=ref.best_g,
                              community=ref.community.copy(),
                              w0=ref.w0.copy())
        records.append(rec)
    return records


@pytest.fixture(scope="module", params=["dg", "integer"])
def stream(request):
    return request.param, _replay(request.param)


def test_reused_seed_equals_the_summed_seed(stream):
    """(a) Where the suffix holds every live vertex, the seed read from
    the maintained weights is the segment sums' seed, bit for bit."""
    _, records = stream
    assert {r["kind"] for r in records} == {"insert", "expiry", "mask"}
    for t, rec in enumerate(records):
        assert rec["r0"] == 0, t
        live, w, alive, f, reused = rec["reused"]
        s_w, s_alive, s_f = rec["summed"]
        assert bool(reused), t
        np.testing.assert_array_equal(live, rec["live"])
        np.testing.assert_array_equal(w, s_w, err_msg=f"tick {t}: w")
        np.testing.assert_array_equal(alive, s_alive,
                                      err_msg=f"tick {t}: edge_alive")
        assert f.tobytes() == s_f.tobytes(), t


def test_seed_helpers_agree_when_called_directly():
    """(a) The two branches of the seed, called directly on a graph whose
    every vertex is live: the same ``(w, edge_alive, f)``."""
    rng = np.random.default_rng(9)
    src, dst, c, a = _base(rng, "integer")
    g = device_graph_from_coo(N, src, dst, c, a=a, n_capacity=V_CAP,
                              e_capacity=128)
    live = g.vertex_mask
    got = _np(_seed_from_weights(g, live, g.peel_weights()))
    want = _np(_seed_by_sums(g, live))
    for x, y, name in zip(got, want, ("w", "edge_alive", "f")):
        np.testing.assert_array_equal(x, y, err_msg=name)


def test_stream_matches_the_dg_reference(stream):
    """(b) The levels, best density, community and ``w0`` after every tick
    equal the plain DG reference's, and the counter vector equals a numpy
    recount of the same warm re-peel, with ``seed_reused`` 1."""
    weights, records = stream
    for t, rec in enumerate(records):
        got = tick_counters(rec["counts"][None], MAX_ROUNDS)
        r0, rv, re = rec["recount"]
        assert int(got["suffix_r0"][0]) == r0 == 0
        assert int(got["seed_reused"][0]) == 1
        assert got["round_vertices"][0].tolist() == rv
        assert got["round_edges"][0].tolist() == re
        if weights != "dg":
            continue
        ref, st = rec["ref"], rec["state"]
        np.testing.assert_array_equal(st.level[:N], ref["level"],
                                      err_msg=f"tick {t}: level")
        assert float(st.best_g) == float(ref["best_g"]), t
        np.testing.assert_array_equal(st.community[:N], ref["community"],
                                      err_msg=f"tick {t}: community")
        np.testing.assert_array_equal(st.w0[:N], ref["w0"],
                                      err_msg=f"tick {t}: w0")


def test_maintained_w0_is_the_graph_peel_weights(stream):
    """(c) After every tick the state's ``w0`` is its graph's peel weights,
    bit for bit, self-loop and duplicated edge included, and it is marked
    exact."""
    _, records = stream
    for t, rec in enumerate(records):
        assert rec["state"].w0.tobytes() == rec["peel_weights"].tobytes(), t
        assert bool(rec["state"].w0_exact), t


def test_top_level_batch_sums_its_seed():
    """(d) A batch that touches only top-level accounts leaves live
    vertices outside the suffix: the seed is summed (``seed_reused`` 0),
    and the re-peel and the tick are those of the summed seed alone."""
    rng = np.random.default_rng(10)
    src, dst, c, a = _base(rng, "integer")
    g0 = device_graph_from_coo(N, src, dst, c, a=a, n_capacity=V_CAP,
                               e_capacity=256)
    state = init_state(g0, eps=EPS)
    level = np.asarray(state.level)
    top = np.flatnonzero(level >= np.sort(level)[-4])  # the top levels
    bs = jnp.asarray(np.resize(top, B), jnp.int32)
    bd = jnp.asarray(np.resize(np.roll(top, 1), B), jnp.int32)
    bc = jnp.asarray(rng.integers(1, 5, B), jnp.float32)
    valid = jnp.asarray(np.arange(B) < N_VALID)
    bk = _slide_prologue(state, None, bs, bd, valid)
    assert int(bk.r0) > 0
    assert not bool(jnp.all(bk.keep | ~g0.vertex_mask))
    g = append_edges(g0, state.edge_count, bs, bd, bc, valid=valid)
    w_next, exact = _next_w0(state, bk, bs, bd, bc, valid, with_drops=False)
    assert bool(exact)
    kw = dict(eps=EPS, max_rounds=MAX_ROUNDS, counters=True)
    res, counts = bulk_peel_warm(g, bk.keep, bk.prior_g, w_full=w_next,
                                 w_exact=exact, **kw)
    # the parent's path: weights not marked exact, so the seed is summed
    want, want_counts = bulk_peel_warm(g, bk.keep, bk.prior_g, w_full=w_next,
                                       w_exact=jnp.bool_(False), **kw)
    for f in res._fields:
        np.testing.assert_array_equal(np.asarray(getattr(res, f)),
                                      np.asarray(getattr(want, f)),
                                      err_msg=f)
    for x, y in zip(counts, want_counts):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert int(counts[0][0]) == 0
    expect = _np(_slide_epilogue(state, g, want, bk, jnp.int32(0), w_next,
                                 exact))
    out, cnt = insert_and_maintain(
        jax.tree_util.tree_map(jnp.copy, state), bs, bd, bc, valid, **kw)
    assert int(tick_counters(np.asarray(cnt)[None],
                             MAX_ROUNDS)["seed_reused"][0]) == 0
    out = _np(out)
    for f in ("level", "best_g", "community", "edge_count", "w0"):
        np.testing.assert_array_equal(getattr(out, f), getattr(expect, f),
                                      err_msg=f)
    for f in ("src", "dst", "c", "edge_mask"):
        np.testing.assert_array_equal(getattr(out.graph, f),
                                      getattr(expect.graph, f), err_msg=f)


def _window_stream(state, batches, window):
    """Slide ``batches`` through a ``window``-tick window; per tick the
    seed the re-peel took against the summed seed, and the state after."""
    m_base = int(state.edge_count)
    ring: list[int] = []
    out = []
    for bs, bd, bc in batches:
        bs, bd = jnp.asarray(bs, jnp.int32), jnp.asarray(bd, jnp.int32)
        bc = jnp.asarray(bc, jnp.float32)
        valid = jnp.ones(bs.shape[0], bool)
        drop = None
        if len(ring) >= window:  # the oldest batch, at the base's end
            drop = WindowExpiry(jnp.int32(m_base), jnp.int32(ring.pop(0)),
                                window * bs.shape[0])
        bk = _slide_prologue(state, drop, bs, bd, valid)
        g0 = state.graph
        g, n_removed = (g0, 0) if drop is None else _expire(g0, drop)
        g = append_edges(g, state.edge_count - n_removed, bs, bd, bc,
                         valid=valid)
        w_next, exact = _next_w0(state, bk, bs, bd, bc, valid,
                                 with_drops=drop is not None)
        seed = _np(_warm_seed(g, bk.keep, w_next, exact))
        summed = _np(_seed_by_sums(g, bk.keep & g.vertex_mask))
        args = (bs, bd, bc, valid)
        kw = dict(eps=EPS, max_rounds=MAX_ROUNDS, counters=True)
        if drop is None:
            state, cnt = insert_and_maintain(state, *args, **kw)
        else:
            state, cnt = slide_and_maintain(state, drop, *args, **kw)
        ring.append(int(bs.shape[0]))
        out.append(dict(seed=seed, summed=summed, state=_np(state),
                        counts=tick_counters(np.asarray(cnt)[None],
                                             MAX_ROUNDS),
                        peel_weights=np.asarray(state.graph.peel_weights())))
    return state, out


def test_fractional_weights_sum_their_seed():
    """(e) Raw float amounts (DW) on a long windowed stream: a hub carries
    ~1e8 for two ticks and then falls to small weights.  The maintained
    ``w0`` drifts by the old weight's rounding; the state marks it inexact
    and every tick's seed is the summed seed, bit for bit."""
    rng = np.random.default_rng(11)
    src, dst, _, _ = _base(rng, "integer")
    c = rng.lognormal(2.0, 1.0, src.shape[0]).astype(np.float32)
    e_cap = -(-(src.shape[0] + 3 * B) // 64) * 64
    state = init_state(device_graph_from_coo(
        N, src, dst, c, n_capacity=V_CAP, e_capacity=e_cap), eps=EPS)
    assert not bool(state.w0_exact)
    batches = []
    for t in range(24):
        bs = rng.integers(0, N, B)
        bd = (bs + 1 + rng.integers(0, N - 1, B)) % N
        bc = rng.lognormal(2.0, 1.0, B)
        if t < 2:
            bs[:4] = 0  # the hub
            bc[:4] = 1e8 + rng.random(4) * 1e3
        batches.append((bs, bd, bc))
    state, out = _window_stream(state, batches, WINDOW)
    drift = max(abs(float(r["state"].w0[0] - r["peel_weights"][0]))
                for r in out[WINDOW + 1:])
    assert drift > 0.5  # the hazard is real on this stream
    for t, r in enumerate(out):
        assert not bool(r["state"].w0_exact), t
        assert int(r["counts"]["seed_reused"][0]) == 0, t
        live, w, alive, f, reused = r["seed"]
        assert not bool(reused), t
        for x, y, name in zip((w, alive, f), r["summed"],
                              ("w", "edge_alive", "f")):
            assert x.tobytes() == y.tobytes(), (t, name)


def test_exactness_is_lost_past_two_to_the_24_until_a_refresh():
    """(e) Integer weights whose sum on one vertex passes 2^24 clear
    ``w0_exact``; it stays clear after that mass has expired, and a
    refresh, which sums ``w0`` afresh, sets it again."""
    rng = np.random.default_rng(12)
    src, dst, c, _ = _base(rng, "dg")
    e_cap = -(-(src.shape[0] + 3 * B) // 64) * 64
    state = init_state(device_graph_from_coo(
        N, src, dst, c, n_capacity=V_CAP, e_capacity=e_cap), eps=EPS)
    assert bool(state.w0_exact)
    batches = []
    for t in range(6):
        bs = rng.integers(0, N, B)
        bd = (bs + 1 + rng.integers(0, N - 1, B)) % N
        bc = np.ones(B)
        if t == 1:
            bs[:3] = 0
            bc[:3] = 2.0**23  # three of them pass 2^24 on account 0
        batches.append((bs, bd, bc))
    state, out = _window_stream(state, batches, WINDOW)
    exact = [bool(r["state"].w0_exact) for r in out]
    assert exact == [True, False, False, False, False, False]
    assert [int(r["counts"]["seed_reused"][0]) for r in out][1:] == [0] * 5
    assert out[0]["counts"]["seed_reused"][0] == int(out[0]["seed"][4])
    assert float(np.max(out[-1]["peel_weights"])) < 2.0**24
    assert bool(full_refresh(state, eps=EPS).w0_exact)


def test_raw_amounts_never_reuse_the_seed_in_the_service():
    """(e) The service under DW (raw amounts) on a window stream: no tick
    reads its seed from the maintained weights."""
    stream = make_transaction_stream(n=600, m=3000, seed=2)
    rep = SpadeService("DW", EngineSpec(batch_edges=32, max_rounds=8,
                                        window_ticks=3)).run(stream)
    assert rep.n_ticks > 3 and rep.n_expired_edges > 0
    assert rep.seed_reused.shape == (rep.n_ticks,)
    assert not rep.seed_reused.any()
