"""Device plane (JAX) vs host oracle: exact peel equality, bulk-peel
guarantees, and incremental suffix re-peel invariants."""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.incremental import (
    benign_mask,
    full_refresh,
    init_state,
    insert_and_maintain,
)
from repro.core.peel import (
    _div_rn_step,
    bulk_peel,
    bulk_peel_warm,
    div_rn,
    exact_peel,
)
from repro.core.reference import AdjGraph, detect, static_peel
from repro.graphstore.structs import device_graph_from_coo

jax.config.update("jax_platform_name", "cpu")


def random_coo(rng, n, m, int_weights=True):
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    c = rng.integers(1, 6, src.shape[0]).astype(np.float32)
    a = rng.integers(0, 3, n).astype(np.float32)
    return src, dst, c, a


def to_oracle(n, src, dst, c, a):
    return AdjGraph.from_arrays(n, src, dst, c, a)


# ---------------------------------------------------------------------------
# densities: f32 division rounded to nearest on every backend
# ---------------------------------------------------------------------------


def _division_cases(rng, n):
    """Integer masses over counts, floats over wide exponents, and
    numerators rounded from exact midpoints between two floats."""
    q = (rng.random(n) * 1000 + 1).astype(np.float32)
    mid = (q.astype(np.float64)
           + np.nextafter(q, np.float32(np.inf)).astype(np.float64)) / 2
    b3 = rng.integers(1, 2**12, n).astype(np.float32)
    a = np.concatenate([
        rng.integers(1, 2**24, n).astype(np.float32),
        (rng.random(n) * 2.0 ** rng.integers(-20, 40, n)).astype(np.float32),
        np.round(mid * b3.astype(np.float64)).astype(np.float32),
        -rng.integers(0, 2**24, n).astype(np.float32),
    ])
    b = np.concatenate([
        rng.integers(1, 2**23, n).astype(np.float32),
        rng.integers(1, 2**31, n).astype(np.float32),
        b3,
        rng.integers(1, 2**23, n).astype(np.float32),
    ])
    return a, b


@pytest.mark.parametrize("hw_error_ulps", [0, 1])
def test_div_rn_is_the_ieee_quotient(hw_error_ulps):
    """``div_rn`` returns numpy's (IEEE) f32 quotient bit for bit, also
    when the hardware quotient it starts from is an ulp off either way,
    as XLA:TPU's can be."""
    rng = np.random.default_rng(hw_error_ulps)
    a, b = _division_cases(rng, 50_000)
    want = a / b
    if hw_error_ulps == 0:
        got = jax.jit(div_rn)(a, b)
    else:
        m, w = np.abs(a), np.abs(want)
        k = rng.integers(-1, 2, a.size)
        q0 = np.where(k > 0, np.nextafter(w, np.float32(np.inf)),
                      np.where(k < 0, np.nextafter(w, np.float32(0)), w))
        q0 = np.where(m == 0, w, q0).astype(np.float32)
        got = jax.jit(lambda a, b, q: _div_rn_step(a, b, _div_rn_step(
            a, b, q)))(m, b, q0)
        want = w
    np.testing.assert_array_equal(np.asarray(got).view(np.int32),
                                  want.view(np.int32))


# ---------------------------------------------------------------------------
# exact sequential peel == host oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(4))
def test_exact_peel_matches_oracle(seed):
    rng = np.random.default_rng(seed)
    n, m = 24, 70
    src, dst, c, a = random_coo(rng, n, m)
    g = device_graph_from_coo(n, src, dst, c, a)
    res = jax.jit(exact_peel)(g)
    host = static_peel(to_oracle(n, src, dst, c, a))
    np.testing.assert_array_equal(np.asarray(res.order[:n]), host.order())
    np.testing.assert_allclose(np.asarray(res.delta[:n]), host.delta(), rtol=1e-6)
    _, g_host = detect(host)
    assert np.isclose(float(res.best_g), g_host, rtol=1e-6)


def test_exact_peel_with_capacity_padding():
    rng = np.random.default_rng(9)
    n, m = 15, 40
    src, dst, c, a = random_coo(rng, n, m)
    g = device_graph_from_coo(n, src, dst, c, a, n_capacity=32, e_capacity=128)
    res = jax.jit(exact_peel)(g)
    host = static_peel(to_oracle(n, src, dst, c, a))
    np.testing.assert_array_equal(np.asarray(res.order[:n]), host.order())


# ---------------------------------------------------------------------------
# bulk peel: approximation guarantee + planted-community recovery
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed,eps", [(0, 0.1), (1, 0.1), (2, 0.5), (3, 0.01)])
def test_bulk_peel_guarantee_vs_exact(seed, eps):
    rng = np.random.default_rng(seed)
    n, m = 40, 150
    src, dst, c, a = random_coo(rng, n, m)
    g = device_graph_from_coo(n, src, dst, c, a)
    bulk = bulk_peel(g, eps=eps)
    host = static_peel(to_oracle(n, src, dst, c, a))
    _, g_seq = detect(host)
    # sequential-peel best is itself >= g*/2; bulk must be >= g*/(2(1+eps))
    # and g* >= g_seq, so bulk >= g_seq / (2(1+eps)) is implied; check the
    # direct relation instead: bulk best cannot beat optimal, and must be
    # within its guarantee of the sequential result.
    assert float(bulk.best_g) >= g_seq / (2.0 * (1.0 + eps)) - 1e-5
    # community mask consistent with level bookkeeping
    comm = np.asarray(bulk.community_mask() & g.vertex_mask)
    assert comm.sum() > 0


def test_bulk_peel_finds_planted_clique():
    rng = np.random.default_rng(5)
    n = 200
    src, dst, c, a = random_coo(rng, n, 300)
    block = np.arange(10)
    bs, bd = np.meshgrid(block, block)
    mask = bs < bd
    src = np.concatenate([src, bs[mask]])
    dst = np.concatenate([dst, bd[mask]])
    c = np.concatenate([c, np.full(mask.sum(), 10.0, np.float32)])
    g = device_graph_from_coo(n, src, dst, c, a)
    res = bulk_peel(g, eps=0.1)
    comm = np.where(np.asarray(res.community_mask()))[0]
    assert set(block.tolist()).issubset(set(comm.tolist()))
    assert int(res.n_rounds) < n  # genuinely bulk: far fewer rounds than V


# ---------------------------------------------------------------------------
# incremental maintenance
# ---------------------------------------------------------------------------


def test_incremental_matches_refresh_guarantee():
    rng = np.random.default_rng(6)
    n, m = 100, 250
    src, dst, c, a = random_coo(rng, n, m)
    g = device_graph_from_coo(n, src, dst, c, a, e_capacity=m + 256)
    state = init_state(g, eps=0.1)

    # stream 8 batches of 16 edges
    for i in range(8):
        bs = rng.integers(0, n, 16).astype(np.int32)
        bd = rng.integers(0, n, 16).astype(np.int32)
        valid = bs != bd
        bc = rng.integers(1, 6, 16).astype(np.float32)
        state = insert_and_maintain(
            state, jnp.asarray(bs), jnp.asarray(bd), jnp.asarray(bc),
            jnp.asarray(valid), eps=0.1
        )

    # maintained best must be >= the from-scratch bulk best / never regress,
    # and both must satisfy the guarantee vs the exact sequential peel.
    fresh = full_refresh(state, eps=0.1)
    assert float(state.best_g) >= float(fresh.best_g) - 1e-5
    host_g = to_oracle(
        n,
        np.asarray(state.graph.src)[np.asarray(state.graph.edge_mask)],
        np.asarray(state.graph.dst)[np.asarray(state.graph.edge_mask)],
        np.asarray(state.graph.c)[np.asarray(state.graph.edge_mask)],
        np.asarray(state.graph.a)[:n],
    )
    _, g_seq = detect(static_peel(host_g))
    assert float(state.best_g) >= g_seq / 2.2 - 1e-5


def test_incremental_detects_emerging_fraud_block():
    rng = np.random.default_rng(7)
    n, m = 150, 300
    src, dst, c, a = random_coo(rng, n, m)
    g = device_graph_from_coo(n, src, dst, c, a, e_capacity=m + 512)
    state = init_state(g, eps=0.1)
    g0 = float(state.best_g)

    block = np.arange(20, 28)
    for u in block:
        for v in block:
            if u < v:
                state = insert_and_maintain(
                    state,
                    jnp.asarray([u], jnp.int32),
                    jnp.asarray([v], jnp.int32),
                    jnp.asarray([8.0], jnp.float32),
                    jnp.asarray([True]),
                    eps=0.1,
                )
    comm = np.where(np.asarray(state.community))[0]
    assert set(block.tolist()).issubset(set(comm.tolist()))
    assert float(state.best_g) > g0


def test_benign_mask_is_conservative():
    rng = np.random.default_rng(8)
    n, m = 80, 200
    src, dst, c, a = random_coo(rng, n, m)
    g = device_graph_from_coo(n, src, dst, c, a, e_capacity=m + 64)
    state = init_state(g, eps=0.1)
    # heavy edge into the current community must be urgent
    comm = np.where(np.asarray(state.community))[0]
    bm = benign_mask(
        state,
        jnp.asarray([comm[0]], jnp.int32),
        jnp.asarray([comm[-1]], jnp.int32),
        jnp.asarray([100.0], jnp.float32),
    )
    assert not bool(bm[0])


def test_empty_batch_noop():
    rng = np.random.default_rng(10)
    n, m = 30, 60
    src, dst, c, a = random_coo(rng, n, m)
    g = device_graph_from_coo(n, src, dst, c, a, e_capacity=m + 32)
    state = init_state(g, eps=0.1)
    lvl0 = np.asarray(state.level).copy()
    m_real = int(jnp.sum(g.edge_mask))
    z = jnp.zeros(4, jnp.int32)
    state2 = insert_and_maintain(
        state, z, z, z.astype(jnp.float32), jnp.zeros(4, bool), eps=0.1
    )
    assert int(state2.edge_count) == m_real
    np.testing.assert_array_equal(np.asarray(state2.level), lvl0)


# ---------------------------------------------------------------------------
# benign/urgent routing: device benign_mask == host oracle Def 4.1
# ---------------------------------------------------------------------------


def test_benign_mask_matches_host_urgency_stream():
    """Edge-by-edge over a random stream, the vectorized benign_mask agrees
    with the host oracle's Def 4.1 urgency test.

    Both tests read the same exact g(S^P) (host-maintained), so the
    comparison isolates the device plane's incrementally maintained w0 and
    the vectorized test itself; integer weights keep every sum exact."""
    import dataclasses

    from repro.core.reference import insert_edges, peeling_weights_full

    rng = np.random.default_rng(5)
    n, m = 30, 60
    src, dst, c, a = random_coo(rng, n, m)
    # plant a heavy block so g(S^P) is high and sparse-endpoint edges are
    # genuinely benign — both branches of Def 4.1 get exercised
    block = np.arange(6)
    bs_, bd_ = np.meshgrid(block, block)
    tri = bs_ < bd_
    src = np.concatenate([src, bs_[tri]])
    dst = np.concatenate([dst, bd_[tri]])
    c = np.concatenate([c, np.full(tri.sum(), 40.0, np.float32)])
    g_dev = device_graph_from_coo(n, src, dst, c, a, e_capacity=src.shape[0] + 128)
    state = init_state(g_dev, eps=0.1)
    host = to_oracle(n, src, dst, c, a)
    host_state = static_peel(host)
    _, g_best = detect(host_state)
    w0_host = peeling_weights_full(host)
    np.testing.assert_allclose(np.asarray(state.w0)[:n], w0_host, rtol=1e-6)

    checked_benign = checked_urgent = 0
    for _ in range(40):
        u, v = (int(x) for x in rng.integers(0, n, 2))
        if u == v:
            continue
        cv = float(rng.integers(1, 5))
        host_urgent = (w0_host[u] + cv >= g_best) or (w0_host[v] + cv >= g_best)
        dev = dataclasses.replace(state, best_g=jnp.float32(g_best))
        dev_benign = bool(
            benign_mask(
                dev,
                jnp.asarray([u], jnp.int32),
                jnp.asarray([v], jnp.int32),
                jnp.asarray([cv], jnp.float32),
            )[0]
        )
        assert dev_benign == (not host_urgent), (u, v, cv)
        checked_benign += dev_benign
        checked_urgent += not dev_benign
        # apply the edge on both planes, then re-check w0 parity
        insert_edges(host_state, [(u, v, cv)])
        w0_host[u] += cv
        w0_host[v] += cv
        _, g_best = detect(host_state)
        state = insert_and_maintain(
            state,
            jnp.asarray([u], jnp.int32),
            jnp.asarray([v], jnp.int32),
            jnp.asarray([cv], jnp.float32),
            jnp.asarray([True]),
            eps=0.1,
        )
        np.testing.assert_allclose(np.asarray(state.w0)[:n], w0_host, rtol=1e-6)
    assert checked_benign > 0 and checked_urgent > 0  # both branches exercised


def test_benign_mask_matches_host_urgency_with_deletions():
    """Satellite of the sliding-window work: the vectorized Def 4.1 test
    must keep agreeing with the host oracle when edges are *deleted* —
    the decremented device w0 and the (possibly regressed) best density
    both enter the urgency test.  Unique edge pairs keep the host's
    combined-adjacency deletion 1:1 with device slots; integer weights
    keep every sum exact."""
    import dataclasses

    from repro.core.incremental import delete_and_maintain
    from repro.core.reference import delete_edge, peeling_weights_full

    rng = np.random.default_rng(12)
    n = 24
    pairs = [(u, v) for u in range(n) for v in range(u + 1, n)]
    rng.shuffle(pairs)
    pairs = pairs[:80]
    src = np.array([p[0] for p in pairs])
    dst = np.array([p[1] for p in pairs])
    c = rng.integers(1, 6, len(pairs)).astype(np.float32)
    # heavy block so benign edges genuinely exist
    for i in range(10):
        c[i] = 40.0
    a = rng.integers(0, 3, n).astype(np.float32)
    g_dev = device_graph_from_coo(n, src, dst, c, a, e_capacity=len(pairs) + 32)
    state = init_state(g_dev, eps=0.1)
    host = to_oracle(n, src, dst, c, a)
    host_state = static_peel(host)
    _, g_best = detect(host_state)
    w0_host = peeling_weights_full(host)

    live = list(range(len(pairs)))
    checked_benign = checked_urgent = 0
    slot_ids = jnp.arange(g_dev.e_capacity, dtype=jnp.int32)
    for step in range(12):
        # delete one live edge on both planes
        k = live[int(rng.integers(0, len(live)))]
        em = np.asarray(state.graph.edge_mask)
        slot = [
            i for i in range(em.sum())
            if (int(np.asarray(state.graph.src)[i]),
                int(np.asarray(state.graph.dst)[i])) == pairs[k]
        ][0]
        state = delete_and_maintain(state, slot_ids == slot, eps=0.1)
        delete_edge(host_state, *pairs[k])
        w0_host[pairs[k][0]] -= c[k]
        w0_host[pairs[k][1]] -= c[k]
        live.remove(k)
        _, g_best = detect(host_state)
        np.testing.assert_allclose(np.asarray(state.w0)[:n], w0_host, rtol=1e-6)

        # device benign test (with the host's exact g to isolate w0) must
        # equal host urgency for random candidate edges
        for _ in range(6):
            u, v = (int(x) for x in rng.integers(0, n, 2))
            if u == v:
                continue
            cv = float(rng.integers(1, 5))
            host_urgent = (
                w0_host[u] + cv >= g_best or w0_host[v] + cv >= g_best
            )
            dev = dataclasses.replace(state, best_g=jnp.float32(g_best))
            dev_benign = bool(
                benign_mask(
                    dev,
                    jnp.asarray([u], jnp.int32),
                    jnp.asarray([v], jnp.int32),
                    jnp.asarray([cv], jnp.float32),
                )[0]
            )
            assert dev_benign == (not host_urgent), (step, u, v, cv)
            checked_benign += dev_benign
            checked_urgent += not dev_benign
    assert checked_benign > 0 and checked_urgent > 0


def test_edge_grouping_buffered_edges_then_deleted():
    """Grouping + deletion interaction: benign edges sit in the buffer,
    then the very same edges are deleted.  DeleteEdge must flush first
    (the buffered edge has to exist in the graph to be removable) and the
    final state must equal a scratch peel without the deleted edge."""
    from repro.core.spade import Spade

    sp = Spade(metric="DW", edge_grouping=True)
    # heavy triangle 0-1-2 keeps g(S^P) high; 3 and 4 hang off it lightly,
    # so an edge between them is benign under Def 4.1
    sp.LoadGraph([0, 1, 2, 0, 0], [1, 2, 0, 3, 4],
                 [100.0, 100.0, 100.0, 1.0, 1.0], n_vertices=5)
    r1 = sp.InsertEdge(3, 4, 1.0)  # benign: buffers
    assert not r1.triggered and sp.buffered_edges == 1
    res = sp.DeleteEdge(3, 4)  # deletes the edge that was still buffered
    assert res.triggered and sp.buffered_edges == 0
    assert 4 not in sp.graph.adj[3]
    expect = static_peel(sp.graph.copy())
    np.testing.assert_array_equal(sp.state.order(), expect.order())
    np.testing.assert_allclose(sp.state.delta(), expect.delta())

    # buffered benign edge NOT deleted must survive a deletion elsewhere
    r2 = sp.InsertEdge(4, 3, 1.0)
    assert not r2.triggered and sp.buffered_edges == 1
    sp.DeleteEdge(0, 3)
    assert sp.buffered_edges == 0  # flush-first semantics
    assert 3 in sp.graph.adj[4]  # the buffered edge was materialized
    expect = static_peel(sp.graph.copy())
    np.testing.assert_array_equal(sp.state.order(), expect.order())
    # w0 stayed exact through buffer + flush + delete accounting
    from repro.core.reference import peeling_weights_full

    np.testing.assert_allclose(sp._w0[: sp.graph.n],
                               peeling_weights_full(sp.graph))


def test_spade_insert_delete_window_equals_scratch():
    """Host-plane C.3 window: inserts + expiries through the public API
    track a scratch peel of the surviving graph exactly."""
    from repro.core.spade import Spade

    rng = np.random.default_rng(21)
    n = 20
    base = []
    seen = set()
    while len(base) < 40:
        u, v = (int(x) for x in rng.integers(0, n, 2))
        if u == v or (u, v) in seen or (v, u) in seen:
            continue
        seen.add((u, v))
        base.append((u, v, float(rng.integers(1, 6))))
    sp = Spade(metric="DW")
    sp.LoadGraph([e[0] for e in base], [e[1] for e in base],
                 [e[2] for e in base], n_vertices=n)
    window = list(base)
    for _ in range(15):
        # slide: insert a fresh unique edge, expire the oldest
        while True:
            u, v = (int(x) for x in rng.integers(0, n, 2))
            if u != v and (u, v) not in seen and (v, u) not in seen:
                break
        seen.add((u, v))
        cv = float(rng.integers(1, 6))
        sp.InsertEdge(u, v, cv)
        window.append((u, v, cv))
        old = window.pop(0)
        sp.DeleteEdge(old[0], old[1])
        seen.discard((old[0], old[1]))
        expect = static_peel(sp.graph.copy())
        np.testing.assert_array_equal(sp.state.order(), expect.order())
        np.testing.assert_allclose(sp.state.delta(), expect.delta())


def test_append_compacts_interior_invalid_batch_entries():
    """Regression: the k-th *valid* edge of a batch must land in slot
    offset+k, or a later batch (offset advanced by sum(valid)) silently
    overwrites earlier edges when invalid entries sit between valid ones."""
    g = device_graph_from_coo(
        6, np.array([0]), np.array([1]), np.ones(1, np.float32), e_capacity=8
    )
    state = init_state(g, eps=0.1)
    s1 = insert_and_maintain(
        state,
        jnp.asarray([0, 3], jnp.int32), jnp.asarray([0, 4], jnp.int32),
        jnp.ones(2, jnp.float32), jnp.asarray([False, True]), eps=0.1,
    )
    s2 = insert_and_maintain(
        s1,
        jnp.asarray([4, 2], jnp.int32), jnp.asarray([5, 5], jnp.int32),
        jnp.ones(2, jnp.float32), jnp.asarray([True, True]), eps=0.1,
    )
    em = np.asarray(s2.graph.edge_mask)
    edges = set(zip(np.asarray(s2.graph.src)[em].tolist(),
                    np.asarray(s2.graph.dst)[em].tolist()))
    assert edges == {(0, 1), (3, 4), (4, 5), (2, 5)}
    assert int(s2.edge_count) == 4
