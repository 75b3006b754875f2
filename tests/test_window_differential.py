"""Property-based differential harness for sliding-window maintenance.

Random interleavings of inserts, deletes, and window slides are replayed
through the incremental engines and checked against from-scratch oracles:

* **host plane** — ``insert_edges`` / ``delete_edge`` on a ``PeelState``
  must reproduce ``static_peel`` of the maintained graph *exactly*
  (order and peel-time weights) after every operation; ``Spade`` with
  edge grouping must do the same at every flush point.
* **device plane** — the windowed replay (the fused ``slide_and_maintain``
  service tick, expiring the oldest tick as the service does with a
  ``WindowExpiry``, alternated with composed ``delete_and_maintain`` +
  ``insert_and_maintain``, under the service's slot bookkeeping) must track
  the host-mirrored window edge multiset and ``w0`` bit-exactly (integer
  weights), report a community whose density upper-bounds ``best_g`` and
  never exceed the brute-forced optimal density; a final ``full_refresh``
  must coincide with a from-scratch ``bulk_peel`` of the surviving graph.
  (Community *membership* parity with the host is not expected: the
  device plane is the 2(1+eps)-approximate bulk engine.)

Integer weights keep every float32/float64 sum exact, so all equality
checks are bit-level, and ``derandomize=True`` pins hypothesis to
deterministic example sequences — failures replay by rerunning the test.
The ``_hypothesis_stub`` fallback runner is seeded by test name and is
deterministic by construction.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import itertools
import sys
from pathlib import Path

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container may lack hypothesis; stub runner takes over
    from _hypothesis_stub import given, settings, st

import jax
import jax.numpy as jnp

from repro.core.incremental import (
    BucketPredictor,
    _phase_b,
    _slide_prologue,
    delete_and_maintain,
    full_refresh,
    init_state,
    insert_and_maintain,
    insert_and_maintain_auto,
    insert_and_maintain_predictive,
    slide_and_maintain,
    slide_and_maintain_auto,
    slide_and_maintain_predictive,
)
from repro.core.peel import (
    bulk_peel,
    bulk_peel_warm,
    bulk_peel_warm_workset,
    select_bucket,
    workset_sizes,
)
from repro.core.reference import (
    AdjGraph,
    delete_edge,
    detect,
    insert_edges,
    peeling_weights_full,
    static_peel,
)
from repro.core.spade import Spade
from repro.graphstore.generators import make_transaction_stream
from repro.graphstore.structs import (
    WindowExpiry,
    append_edges,
    device_graph_from_coo,
    expire_window,
    remove_edges,
)
from repro.serve import EngineSpec, SpadeService

N = 10  # vertex universe: small enough to brute-force optimal density
V_CAP, E_CAP = 16, 96  # fixed capacities -> one jit compilation per engine
EPS = 0.1

edge_st = st.tuples(
    st.integers(0, N - 1), st.integers(0, N - 1), st.integers(1, 5)
).filter(lambda e: e[0] != e[1])


def build_host(edges):
    g = AdjGraph(N)
    for u, v, c in edges:
        g.add_edge(int(u), int(v), float(c))
    return g


def brute_best_density(edges) -> float:
    """Exhaustive argmax_g over all non-empty subsets (a = 0)."""
    best = 0.0
    for r in range(1, N + 1):
        for S in itertools.combinations(range(N), r):
            Sset = set(S)
            f = sum(c for u, v, c in edges if u in Sset and v in Sset)
            best = max(best, f / r)
    return best


def exact_density(edges, members) -> float:
    mset = set(int(x) for x in members)
    if not mset:
        return 0.0
    f = sum(c for u, v, c in edges if u in mset and v in mset)
    return f / len(mset)


def live_edge_multiset(state):
    em = np.asarray(state.graph.edge_mask)
    return sorted(
        zip(
            np.asarray(state.graph.src)[em].tolist(),
            np.asarray(state.graph.dst)[em].tolist(),
            np.asarray(state.graph.c)[em].tolist(),
        )
    )


# ---------------------------------------------------------------------------
# host plane: interleaved insert/delete == scratch, after every op
# ---------------------------------------------------------------------------


@settings(max_examples=25, deadline=None, derandomize=True)
@given(
    base=st.lists(edge_st, min_size=2, max_size=15),
    ops=st.lists(
        st.tuples(st.booleans(), edge_st, st.integers(0, 10**6)),
        min_size=1,
        max_size=12,
    ),
)
def test_property_host_interleaved_insert_delete(base, ops):
    """(is_insert, edge, pick): inserts add the edge; deletes remove the
    pick-th live combined edge.  Exact order/delta equality with a scratch
    peel must hold after *every* operation (paper §4 + Appendix C.1)."""
    g = build_host(base)
    state = static_peel(g)
    live = list(base)
    for is_insert, (u, v, c), pick in ops:
        if is_insert or not live:
            insert_edges(state, [(u, v, float(c))])
            live.append((u, v, c))
        else:
            du, dv, _ = live[pick % len(live)]
            if dv not in state.graph.adj[du]:
                continue  # already fully removed via a combined deletion
            delete_edge(state, du, dv)  # removes the whole combined weight
            live = [e for e in live if set(e[:2]) != {du, dv}]
        expect = static_peel(state.graph.copy())
        np.testing.assert_array_equal(state.order(), expect.order())
        np.testing.assert_allclose(state.delta(), expect.delta())


@settings(max_examples=20, deadline=None, derandomize=True)
@given(
    base=st.lists(edge_st, min_size=3, max_size=15),
    batches=st.lists(
        st.lists(edge_st, min_size=1, max_size=4), min_size=1, max_size=4
    ),
    metric=st.sampled_from(["DG", "DW"]),
)
def test_property_spade_grouping_flush_interleaving(base, batches, metric):
    """Spade with edge grouping: after every forced flush the maintained
    state equals a scratch peel of the maintained graph."""
    sp = Spade(metric=metric, edge_grouping=True)
    src = [e[0] for e in base]
    dst = [e[1] for e in base]
    w = [float(e[2]) for e in base]
    sp.LoadGraph(src, dst, w, n_vertices=N)
    for batch in batches:
        sp.InsertBatchEdges([(u, v, float(c)) for u, v, c in batch])
        sp.FlushBuffer()
        expect = static_peel(sp.graph.copy())
        np.testing.assert_array_equal(sp.state.order(), expect.order())
        np.testing.assert_allclose(sp.state.delta(), expect.delta())


# ---------------------------------------------------------------------------
# device plane: windowed replay vs host mirror + scratch oracles
# ---------------------------------------------------------------------------


def assert_states_bit_identical(a, b, tag=""):
    """Full-state bit equality (integer weights keep every sum exact)."""
    for f in ("level", "best_g", "community", "edge_count", "w0"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)),
            err_msg=f"{tag}:{f}",
        )
    for f in ("src", "dst", "c", "edge_mask"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a.graph, f)), np.asarray(getattr(b.graph, f)),
            err_msg=f"{tag}:graph.{f}",
        )


def run_window_differential(base, ticks, window):
    """Replay ``ticks`` batches through the device engine with an
    N-tick sliding window, mirroring the service's slot bookkeeping, and
    check the full invariant set against host oracles after every tick.

    A twin state runs every tick through the **workset engine**
    (``*_and_maintain_auto``: gather the affected suffix into bucketed
    buffers, re-peel the workset only, scatter back — with automatic
    full-buffer fallback) and must stay bit-identical to the fused
    full-buffer path; the tiny ``min_bucket`` makes the replay cross
    bucket boundaries and exercise workset and fallback ticks alike.  A
    third state takes the served predictive engine, expiring each oldest
    tick as a ``WindowExpiry`` (traced base count, static span), and must
    stay bit-identical too."""
    B = 4  # fixed padded batch size -> stable jit shapes
    src = np.array([e[0] for e in base], np.int64)
    dst = np.array([e[1] for e in base], np.int64)
    c = np.array([e[2] for e in base], np.float32)
    mk = lambda: device_graph_from_coo(
        N, src, dst, c, n_capacity=V_CAP, e_capacity=E_CAP
    )
    state = init_state(mk(), eps=EPS)
    state_ws = init_state(mk(), eps=EPS)  # independent buffers (donation)
    state_pr = init_state(mk(), eps=EPS)
    predictor = BucketPredictor(V_CAP, E_CAP, min_bucket=4)
    m_base = len(base)
    ring: list[list[tuple[int, int, int]]] = []
    slot_ids = jnp.arange(E_CAP, dtype=jnp.int32)
    zi = jnp.zeros(B, jnp.int32)
    zf = jnp.zeros(B, jnp.float32)
    zv = jnp.zeros(B, bool)

    for t, batch in enumerate(ticks):
        n_exp = len(ring.pop(0)) if len(ring) >= window else 0
        drop = (slot_ids >= m_base) & (slot_ids < m_base + n_exp)
        expiry = WindowExpiry(jnp.int32(m_base), jnp.int32(n_exp),
                              window * B)
        bs = np.zeros(B, np.int32)
        bd = np.zeros(B, np.int32)
        bc = np.zeros(B, np.float32)
        valid = np.zeros(B, bool)
        for k, (u, v, w) in enumerate(batch):
            bs[k], bd[k], bc[k], valid[k] = u, v, w, True
        bs, bd = jnp.asarray(bs), jnp.asarray(bd)
        bc, valid = jnp.asarray(bc), jnp.asarray(valid)
        # alternate the fused service tick and the composed ops so both
        # maintenance paths face the same oracle
        if t % 2 == 0:
            state = slide_and_maintain(state, expiry, bs, bd, bc, valid,
                                       eps=EPS)
            state_ws, _ = slide_and_maintain_auto(
                state_ws, drop, bs, bd, bc, valid, eps=EPS, min_bucket=4
            )
            state_pr, _ = slide_and_maintain_predictive(
                state_pr, expiry, bs, bd, bc, valid, predictor, eps=EPS
            )
        else:
            state = delete_and_maintain(state, drop, eps=EPS)
            state = insert_and_maintain(state, bs, bd, bc, valid, eps=EPS)
            state_ws, _ = slide_and_maintain_auto(  # pure-deletion twin
                state_ws, drop, zi, zi, zf, zv, eps=EPS, min_bucket=4
            )
            state_ws, _ = insert_and_maintain_auto(
                state_ws, bs, bd, bc, valid, eps=EPS, min_bucket=4
            )
            state_pr, _ = slide_and_maintain_predictive(
                state_pr, expiry, zi, zi, zf, zv, predictor, eps=EPS
            )
            state_pr, _ = insert_and_maintain_predictive(
                state_pr, bs, bd, bc, valid, predictor, eps=EPS
            )
        assert_states_bit_identical(state, state_ws, tag=f"tick{t}")
        assert_states_bit_identical(state, state_pr, tag=f"pred-tick{t}")
        ring.append(list(batch))

        mirror = list(base) + [e for b in ring for e in b]
        # 1. graph content parity with the host-mirrored window (exact)
        assert live_edge_multiset(state) == sorted(
            (u, v, float(w)) for u, v, w in mirror
        )
        assert int(state.edge_count) == len(mirror)
        # 2. w0 == host full-graph peeling weights (exact integer sums)
        host = build_host(mirror)
        np.testing.assert_array_equal(
            np.asarray(state.w0)[:N], peeling_weights_full(host)
        )
        # 3. density bookkeeping: best_g is conservative (never above the
        #    reported community's exact density, never above the optimum)
        comm = np.where(np.asarray(state.community))[0]
        assert comm.size > 0
        g_comm = exact_density(mirror, comm)
        g_star = brute_best_density(mirror)
        assert float(state.best_g) <= g_comm + 1e-4
        assert float(state.best_g) <= g_star + 1e-4

    # 4. refresh differential: a from-scratch bulk peel of the surviving
    #    buffers must coincide with the refreshed state (level bit-parity),
    #    and the refreshed best carries the bulk 2(1+eps) guarantee.
    mirror = list(base) + [e for b in ring for e in b]
    refreshed = full_refresh(state, eps=EPS)
    scratch = bulk_peel(state.graph, eps=EPS)
    np.testing.assert_array_equal(
        np.asarray(refreshed.level), np.asarray(scratch.level)
    )
    assert float(refreshed.best_g) == float(scratch.best_g)
    _, g_seq = detect(static_peel(build_host(mirror)))
    assert float(refreshed.best_g) >= g_seq / (2.0 * (1.0 + EPS)) - 1e-4
    return state


@settings(max_examples=15, deadline=None, derandomize=True)
@given(
    base=st.lists(edge_st, min_size=2, max_size=10),
    ticks=st.lists(
        st.lists(edge_st, min_size=0, max_size=4), min_size=1, max_size=6
    ),
    window=st.integers(1, 3),
)
def test_property_device_window_differential(base, ticks, window):
    run_window_differential(base, ticks, window)


@pytest.mark.parametrize("seed", range(3))
def test_window_replay_seeded(seed):
    """Non-property twin with pinned seeds: always runs, regardless of
    which property runner is active."""
    rng = np.random.default_rng(100 + seed)

    def rand_edges(k):
        out = []
        for _ in range(k):
            u, v = rng.integers(0, N, 2)
            if u != v:
                out.append((int(u), int(v), int(rng.integers(1, 6))))
        return out

    base = rand_edges(12) or [(0, 1, 2)]
    ticks = [rand_edges(int(rng.integers(1, 5))) for _ in range(6)]
    state = run_window_differential(base, ticks, window=2)
    # window bound: only base + at most 2 ticks of <=4 edges remain
    assert int(state.edge_count) <= len(base) + 2 * 4


# ---------------------------------------------------------------------------
# window expiry: the oldest tick by a span shift, as remove_edges and the
# [E] prologue would expire it
# ---------------------------------------------------------------------------


def _window_state(seed, m_base, ticks, window, batch, n=40, e_pad=0):
    """A state whose live edges are a base graph then ``ticks`` resident
    ticks (their edge counts, oldest first), in the windowed service's
    buffer of ``m_base + (window + 1) * batch`` slots (plus ``e_pad``),
    with random levels and a random community so that ``r0`` and the
    community's loss both depend on what expires."""
    rng = np.random.default_rng(seed)
    m = m_base + sum(ticks)
    src = rng.integers(0, n, m)
    dst = (src + 1 + rng.integers(0, n - 1, m)) % n
    c = rng.integers(1, 5, m).astype(np.float32)
    g = device_graph_from_coo(n, src, dst, c, n_capacity=64,
                              e_capacity=m_base + (window + 1) * batch
                              + e_pad)
    state = init_state(g, eps=EPS)
    level = np.where(np.asarray(g.vertex_mask), rng.integers(0, 6, 64), -1)
    comm = np.asarray(g.vertex_mask) & (rng.random(64) < 0.5)
    state = dataclasses.replace(state, level=jnp.asarray(level, jnp.int32),
                                community=jnp.asarray(comm))
    bs = rng.integers(0, n, batch)
    bd = (bs + 1 + rng.integers(0, n - 1, batch)) % n
    batch_arrays = (jnp.asarray(bs, jnp.int32), jnp.asarray(bd, jnp.int32),
                    jnp.asarray(rng.integers(1, 5, batch), jnp.float32),
                    jnp.asarray(rng.random(batch) < 0.8))
    return state, batch_arrays


def _copy(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


@pytest.mark.parametrize("m_base, ticks, window, batch, e_pad", [
    (50, [8, 8, 8], 3, 8, 0),   # a full window of full ticks
    (50, [8, 8, 5], 3, 8, 0),   # a partial newest tick
    (37, [3, 8, 8], 3, 8, 0),   # a partial oldest tick
    (50, [0, 8, 8], 3, 8, 0),   # count = 0: nothing expires
    (50, [8, 8], 3, 8, 0),      # a window not yet full
    (0, [8, 8, 8, 8], 4, 8, 0),  # no base graph
    (61, [7, 8, 8], 3, 8, 59),  # a capacity rounded up past the window
])
def test_window_expiry_matches_remove_edges(m_base, ticks, window, batch,
                                            e_pad):
    """``expire_window`` against ``remove_edges`` on the same slot mask,
    and the expiry's bookkeeping against the ``[E]`` prologue's: the
    same slots, removed count, ``r0``, dropped mass, community loss
    (in ``prior_g``) and, through phase B and the fused tick, the same
    ``w0`` decrement and state, bit for bit."""
    state, (bs, bd, bc, valid) = _window_state(
        m_base + 7 * len(ticks), m_base, ticks, window, batch, e_pad=e_pad)
    g = state.graph
    count = ticks[0]
    slot = jnp.arange(g.e_capacity)
    mask = (slot >= m_base) & (slot < m_base + count)
    expiry = WindowExpiry(jnp.int32(m_base), jnp.int32(count), window * batch)

    want, n_want = remove_edges(g, mask)
    got, n_got = expire_window(g, expiry)
    for f in ("src", "dst", "c", "edge_mask"):
        np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                      np.asarray(getattr(want, f)), err_msg=f)
    assert int(n_got) == int(n_want) == count

    bk_m = _slide_prologue(state, mask, bs, bd, valid)
    bk_x = _slide_prologue(state, expiry, bs, bd, valid)
    assert bk_x.cd.shape == (window * batch,)
    for f in ("r0", "n_new", "keep", "prior_g"):
        np.testing.assert_array_equal(np.asarray(getattr(bk_x, f)),
                                      np.asarray(getattr(bk_m, f)), err_msg=f)
    assert int(jnp.sum(bk_x.dropped)) == int(jnp.sum(bk_m.dropped)) == count
    assert float(jnp.sum(bk_x.cd)) == float(jnp.sum(bk_m.cd))

    # phase B's merge decrements w0 by the dropped mass, scattered over
    # every lane or compacted into a bucket of the dropped count
    g_m = append_edges(want, state.edge_count - n_want, bs, bd, bc, valid)
    g_x = append_edges(got, state.edge_count - n_got, bs, bd, bc, valid)
    for d_bucket in (0, select_bucket(count, g.e_capacity, floor=4)):
        outs = [_phase_b(_copy(state), _copy(gg), bk, n, bs, bd, bc, valid,
                         eps=EPS, max_rounds=6, d_bucket=d_bucket)
                for gg, bk, n in ((g_m, bk_m, n_want), (g_x, bk_x, n_got))]
        assert_states_bit_identical(*outs, tag=f"phase_b d_bucket={d_bucket}")
    fused = [slide_and_maintain(_copy(state), drop, bs, bd, bc, valid,
                                eps=EPS, max_rounds=6)
             for drop in (mask, expiry)]
    assert_states_bit_identical(*fused, tag="fused")


def _spade_dg():
    """The benchmark's plain DG reference, loaded by its path."""
    path = Path(__file__).resolve().parents[1] / "bench" / "references" \
        / "spade_dg.py"
    spec = importlib.util.spec_from_file_location("spade_dg_ref", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # its dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("workset", [False, True],
                         ids=["fused", "predictive"])
def test_served_window_matches_the_dg_reference(workset):
    """A served windowed run under DG answers what the plain reference
    (``bench/references/spade_dg.py``) answers replaying the same stream:
    the density, the live and expired edges, the ticks, the benign count,
    the planted accounts detected and every tick's affected suffix."""
    stream = make_transaction_stream(n=600, m=3000, seed=2)
    spec = EngineSpec(batch_edges=32, max_rounds=6, window_ticks=3,
                      workset=workset, min_bucket=16)
    rep = SpadeService("DG", spec).run(stream)
    ref = _spade_dg().SpadeDG(stream.n_vertices, stream.base_src,
                              stream.base_dst, eps=spec.eps,
                              max_rounds=spec.max_rounds,
                              window_ticks=spec.window_ticks)
    n_inc = stream.inc_src.shape[0]
    for i in range(0, n_inc, spec.batch_edges):
        ref.tick(stream.inc_src[i:i + spec.batch_edges],
                 stream.inc_dst[i:i + spec.batch_edges])
    want = ref.result()
    assert rep.n_ticks == want.n_ticks > spec.window_ticks
    assert rep.final_g == want.final_g
    assert rep.live_edges == want.live_edges
    assert rep.n_expired_edges == want.n_expired_edges > 0
    assert round(rep.benign_fraction * n_inc) == want.benign
    fraud = set(stream.fraud_block.tolist())
    assert rep.fraud_recall == len(fraud & want.detected) / len(fraud)
    assert int(rep.suffix_edges.max()) == want.max_suffix_edges
    if workset:
        assert rep.max_suffix_edges == want.max_suffix_edges
        assert rep.n_workset_ticks + rep.n_fallback_ticks == rep.n_ticks


@pytest.mark.parametrize("workset", [False, True],
                         ids=["fused", "predictive"])
def test_window_tick_programs_compile_once_per_configuration(workset):
    """Two base graphs a few edges apart, in buffers of one capacity, go
    through the served window path: the second compiles nothing, since no
    static argument or shape of a tick program comes from the base
    graph's edge count."""
    stream = make_transaction_stream(n=600, m=3000, seed=2)
    fewer = dataclasses.replace(stream, base_src=stream.base_src[3:],
                                base_dst=stream.base_dst[3:],
                                base_amt=stream.base_amt[3:])
    spec = EngineSpec(batch_edges=32, max_rounds=6, window_ticks=3,
                      workset=workset)
    cap = [-(-(s.base_src.shape[0] + 4 * 32) // 512) * 512
           for s in (stream, fewer)]
    assert cap[0] == cap[1]
    compiled: list[str] = []

    def on_event(event, duration, fun_name="?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(fun_name)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        first = SpadeService("DG", spec).run(stream)
        n_first = len(compiled)
        second = SpadeService("DG", spec).run(fewer)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert first.n_expired_edges > 0 and second.n_expired_edges > 0
    assert first.live_edges - second.live_edges == 3
    assert compiled[n_first:] == []


# ---------------------------------------------------------------------------
# workset warm peel: bit-parity across bucket-boundary suffix sizes
# ---------------------------------------------------------------------------

FLOOR = 8  # tiny bucket floor so the boundaries are cheap to cross


def _boundary_graph():
    """Integer-weight graph big enough for non-trivial suffixes."""
    rng = np.random.default_rng(77)
    n, m = 120, 500
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    keep = src != dst
    c = rng.integers(1, 6, keep.sum()).astype(np.float32)
    return device_graph_from_coo(
        n, src[keep], dst[keep], c, n_capacity=128, e_capacity=1024
    )


@pytest.mark.parametrize("kn", [0, 1, FLOOR - 1, FLOOR, FLOOR + 1, 40])
def test_workset_warm_peel_bucket_boundaries(kn):
    """Suffix sizes straddling a bucket boundary (empty, 1, bucket-1,
    bucket, bucket+1, several buckets): the workset warm peel must match
    the full-buffer warm peel bit-for-bit on integer weights — level (on
    the kept suffix and as the full scattered vector), best density,
    best level, and round count."""
    g = _boundary_graph()
    res0 = bulk_peel(g, eps=EPS)
    lv = np.where(np.asarray(g.vertex_mask), np.asarray(res0.level), -1)
    top = np.argsort(lv)[-kn:] if kn else np.empty(0, np.int64)
    keep = jnp.zeros(g.n_capacity, bool).at[jnp.asarray(top, jnp.int32)].set(
        True, mode="drop"
    )
    nv, ne = workset_sizes(g, keep)
    bv = select_bucket(int(nv), g.n_capacity, floor=FLOOR)
    be = select_bucket(int(ne), g.e_capacity, floor=FLOOR)
    assert bv is not None and be is not None
    full = bulk_peel_warm(g, keep, prior_best_g=res0.best_g, eps=EPS,
                          w_full=g.peel_weights(), w_exact=jnp.bool_(False))
    ws = bulk_peel_warm_workset(
        g, keep, prior_best_g=res0.best_g, eps=EPS, v_bucket=bv, e_bucket=be
    )
    np.testing.assert_array_equal(np.asarray(full.level), np.asarray(ws.level))
    assert float(full.best_g) == float(ws.best_g)
    assert int(full.best_level) == int(ws.best_level)
    assert int(full.n_rounds) == int(ws.n_rounds)


def test_select_bucket_ladder_and_fallback_threshold():
    """The ladder rounds up to powers of two from the floor; counts above
    the largest bucket (largest power of two <= capacity/2) return None."""
    assert select_bucket(0, 1024, floor=8) == 8
    assert select_bucket(1, 1024, floor=8) == 8
    assert select_bucket(8, 1024, floor=8) == 8
    assert select_bucket(9, 1024, floor=8) == 16
    assert select_bucket(511, 1024, floor=8) == 512
    assert select_bucket(512, 1024, floor=8) == 512  # largest bucket
    assert select_bucket(513, 1024, floor=8) is None  # > largest -> fallback
    with pytest.raises(ValueError):
        select_bucket(-1, 1024)


def test_auto_dispatch_falls_back_beyond_largest_bucket():
    """A batch touching level-0 vertices drags the whole graph into the
    suffix: the auto engine must take the full-buffer fallback and still
    match the fused path bit-for-bit."""
    g1, g2 = _boundary_graph(), _boundary_graph()
    s_full = init_state(g1, eps=EPS)
    s_auto = init_state(g2, eps=EPS)
    lv = np.where(np.asarray(g1.vertex_mask), np.asarray(s_full.level), 99)
    cold = np.argsort(lv)[:8]  # lowest-level endpoints -> maximal suffix
    bs = jnp.asarray(cold[:4], jnp.int32)
    bd = jnp.asarray(cold[4:], jnp.int32)
    bc = jnp.ones(4, jnp.float32)
    valid = bs != bd
    s_full = insert_and_maintain(s_full, bs, bd, bc, valid, eps=EPS)
    s_auto, info = insert_and_maintain_auto(
        s_auto, bs, bd, bc, valid, eps=EPS, min_bucket=FLOOR
    )
    assert info.fallback
    assert info.v_bucket == 0 and info.e_bucket == 0
    # the suffix swallowed (nearly) the whole vertex set, past the largest
    # vertex bucket (largest power of two <= n_capacity/2)
    assert info.n_suffix_vertices > g1.n_capacity // 2
    assert_states_bit_identical(s_full, s_auto, tag="fallback")


def test_predictive_dispatch_matches_fused_across_hit_miss_fallback():
    """The predictive selector (buckets from the previous tick's counts,
    device-side fit check) must stay bit-identical to the fused path on
    a tick mix that covers: the synced first tick, predicted workset hits,
    a bucket miss (suffix outgrows the prediction -> in-program fallback),
    and re-anchoring after the miss."""
    from repro.core.incremental import (
        BucketPredictor,
        insert_and_maintain_predictive,
    )

    g1, g2 = _boundary_graph(), _boundary_graph()
    s_full = init_state(g1, eps=EPS)
    s_pred = init_state(g2, eps=EPS)
    predictor = BucketPredictor(g1.n_capacity, g1.e_capacity,
                                min_bucket=FLOOR)
    lv = np.where(np.asarray(g1.vertex_mask), np.asarray(s_full.level), -1)
    hot = np.argsort(lv)[-8:]
    cold = np.argsort(np.where(np.asarray(g1.vertex_mask),
                               np.asarray(s_full.level), 99))[:8]
    saw_predicted = saw_miss = False
    for t, ids in enumerate([hot, hot, cold, hot, hot]):
        bs = jnp.asarray(ids[:4], jnp.int32)
        bd = jnp.asarray(ids[4:], jnp.int32)
        bc = jnp.full(4, float(t + 1), jnp.float32)
        valid = bs != bd
        s_full = insert_and_maintain(s_full, bs, bd, bc, valid, eps=EPS)
        s_pred, info = insert_and_maintain_predictive(
            s_pred, bs, bd, bc, valid, predictor, eps=EPS
        )
        saw_predicted |= info.predicted
        saw_miss |= info.miss
        assert_states_bit_identical(s_full, s_pred, tag=f"pred-tick{t}")
    assert saw_predicted and saw_miss


def test_auto_dispatch_hot_suffix_takes_workset_path():
    """A batch confined to the highest-level vertices keeps the suffix
    small: the auto engine must take the workset path (no fallback) and
    match the fused path bit-for-bit."""
    g1, g2 = _boundary_graph(), _boundary_graph()
    s_full = init_state(g1, eps=EPS)
    s_auto = init_state(g2, eps=EPS)
    lv = np.where(np.asarray(g1.vertex_mask), np.asarray(s_full.level), -1)
    hot = np.argsort(lv)[-8:]
    bs = jnp.asarray(hot[:4], jnp.int32)
    bd = jnp.asarray(hot[4:], jnp.int32)
    bc = jnp.ones(4, jnp.float32)
    valid = bs != bd
    s_full = insert_and_maintain(s_full, bs, bd, bc, valid, eps=EPS)
    s_auto, info = insert_and_maintain_auto(
        s_auto, bs, bd, bc, valid, eps=EPS, min_bucket=FLOOR
    )
    assert not info.fallback
    assert info.e_bucket >= FLOOR
    assert_states_bit_identical(s_full, s_auto, tag="hot")


# ---------------------------------------------------------------------------
# pluggable semantics: a user-defined (non-builtin) SuspSemantics must reach
# every engine with no engine-file edits, bit-identically on integer weights
# ---------------------------------------------------------------------------

from repro.core.semantics import SuspSemantics  # noqa: E402

# parity-boost semantics: odd src+dst doubles the amount; vertex prior
# id % 3.  Integer-valued on integer inputs, so every f32/f64 sum is exact
# and cross-plane equality is bit-level — and it is *not* DG/DW/FD.
PARITY_SEM = SuspSemantics(
    name="XPARITY",
    esusp=lambda xp, src, dst, raw, deg, aux: raw * (1.0 + (src + dst) % 2),
    vsusp=lambda xp, ids, deg, aux: (ids % 3) * 1.0,
)


def _brute_best_density_weighted(edges, a) -> float:
    """Exhaustive argmax_g with vertex priors (f = Σa + Σc)."""
    best = 0.0
    for r in range(1, N + 1):
        for S in itertools.combinations(range(N), r):
            Sset = set(S)
            f = sum(float(a[u]) for u in Sset)
            f += sum(c for u, v, c in edges if u in Sset and v in Sset)
            best = max(best, f / r)
    return best


def test_custom_semantics_cross_plane_differential():
    """Per-tick bit-equality of a user-defined semantics across the host
    oracle, single-device fused, single-device workset, and mesh-sharded
    workset engines (acceptance criterion of the semantics-plane redesign).

    The host oracle (``Spade`` maintained incrementally through the same
    semantics, expiry via ``DeleteEdge``) pins the exact invariants the
    device planes must track bit-for-bit on integer weights: the window's
    edge multiset (through the host funnel's weighting), ``w0`` with
    vertex priors included, and a conservative ``best_g``.  The three
    device engines must agree on the *full state* — community included —
    among themselves (community parity with the exact oracle is not
    expected from the 2(1+eps) bulk engine)."""
    import jax

    from repro.core.incremental import insert_and_maintain_auto as _ins_auto
    from repro.core.incremental import slide_and_maintain_auto as _sl_auto
    from repro.dist.graph import (
        init_sharded_state,
        shard_graph,
        sharded_insert_and_maintain_auto,
        sharded_slide_and_maintain,
    )
    from repro.launch.mesh import make_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 (forced host) devices")
    mesh = make_mesh((8,), ("data",))
    sem = PARITY_SEM
    rng = np.random.default_rng(1234)

    def rand_batch(k):
        out = []
        for _ in range(k):
            u, v = (int(x) for x in rng.integers(0, N, 2))
            if u != v:
                out.append((u, v, int(rng.integers(1, 6))))
        return out

    base = rand_batch(12) or [(0, 1, 2)]
    ticks = [rand_batch(int(rng.integers(1, 4))) for _ in range(6)]
    window = 2
    B = 4

    src = np.array([e[0] for e in base], np.int64)
    dst = np.array([e[1] for e in base], np.int64)
    amt = np.array([e[2] for e in base], np.float64)

    # device seeding through the semantics' batch-seeding rule (the API —
    # no engine knows the semantics' name)
    base_w, in_deg = sem.seed_base(src, dst, amt, N)
    a0 = sem.seed_vertices(N, in_deg)
    mk = lambda: device_graph_from_coo(
        N, src, dst, base_w, a=a0, n_capacity=V_CAP, e_capacity=E_CAP
    )
    state = init_state(mk(), eps=EPS)
    state_ws = init_state(mk(), eps=EPS)
    state_sh = init_sharded_state(shard_graph(mk(), mesh), mesh, eps=EPS)

    # host oracle through the identical semantics (funnel-compiled)
    sp = Spade(metric=sem)
    sp.LoadGraph(src, dst, amt, n_vertices=N)
    m = sp.metric

    weight_fn = jax.jit(sem.batch_weights)
    deg_dev = jnp.zeros(V_CAP, jnp.int32).at[: N].set(
        jnp.asarray(in_deg, jnp.int32)
    )
    m_base = len(base)
    ring: list[list[tuple[int, int, float]]] = []
    slot_ids = jnp.arange(E_CAP, dtype=jnp.int32)

    for t, batch in enumerate(ticks):
        expired = ring.pop(0) if len(ring) >= window else []
        drop = (slot_ids >= m_base) & (slot_ids < m_base + len(expired))
        bs = np.zeros(B, np.int32)
        bd = np.zeros(B, np.int32)
        raw = np.zeros(B, np.float32)
        valid = np.zeros(B, bool)
        for k, (u, v, r) in enumerate(batch):
            bs[k], bd[k], raw[k], valid[k] = u, v, r, True
        bs_d, bd_d = jnp.asarray(bs), jnp.asarray(bd)
        valid_d = jnp.asarray(valid)
        w, deg_dev = weight_fn(deg_dev, bs_d, bd_d, jnp.asarray(raw), valid_d)

        # host-funnel weights must equal the device weights bit-for-bit
        host_w = [m.edge_susp(u, v, float(r), sp.graph) for u, v, r in batch]
        np.testing.assert_array_equal(
            np.asarray(w)[: len(batch)], np.asarray(host_w, np.float32)
        )

        # the three device engines take the identical tick
        state = slide_and_maintain(state, drop, bs_d, bd_d, w, valid_d, eps=EPS)
        state_ws, _ = _sl_auto(state_ws, drop, bs_d, bd_d, w, valid_d,
                               eps=EPS, min_bucket=4)
        state_sh = sharded_slide_and_maintain(
            state_sh, drop, bs_d, bd_d, w, valid_d, mesh=mesh, eps=EPS
        )
        assert_states_bit_identical(state, state_ws, tag=f"sem-ws-tick{t}")
        assert_states_bit_identical(state, state_sh, tag=f"sem-sh-tick{t}")

        # host oracle: insert the batch, expire the window's oldest batch
        sp.InsertBatchEdges([(u, v, float(r)) for u, v, r in batch])
        for u, v, c in expired:
            sp.DeleteEdge(u, v, c)
        ring.append([(u, v, float(cw)) for (u, v, _), cw in zip(batch, host_w)])

        mirror = [(u, v, float(c)) for (u, v), c in zip(
            zip(src.tolist(), dst.tolist()), base_w.tolist())]
        mirror += [e for b in ring for e in b]
        # 1. window edge-multiset parity with the host mirror (exact)
        assert live_edge_multiset(state) == sorted(mirror)
        # 2. w0 (priors included) == host full-graph peeling weights
        np.testing.assert_array_equal(
            np.asarray(state.w0)[:N], peeling_weights_full(sp.graph)[:N]
        )
        # 3. conservative density bookkeeping under the custom semantics
        comm = np.where(np.asarray(state.community))[0]
        assert comm.size > 0
        g_comm = (sum(float(a0[u]) for u in comm)
                  + sum(c for u, v, c in mirror
                        if u in set(comm) and v in set(comm))) / comm.size
        assert float(state.best_g) <= g_comm + 1e-4
        assert float(state.best_g) <= _brute_best_density_weighted(
            mirror, a0) + 1e-4

    # insert-only twin parity through the auto (workset) engines as well,
    # sharded included
    bs = jnp.asarray([0, 1, 2, 3], jnp.int32)
    bd = jnp.asarray([4, 5, 6, 7], jnp.int32)
    raw = jnp.asarray([2.0, 3.0, 1.0, 4.0], jnp.float32)
    valid = jnp.ones(4, bool)
    w, deg_dev = weight_fn(deg_dev, bs, bd, raw, valid)
    state = insert_and_maintain(state, bs, bd, w, valid, eps=EPS)
    state_ws, _ = _ins_auto(state_ws, bs, bd, w, valid, eps=EPS, min_bucket=4)
    state_sh, _ = sharded_insert_and_maintain_auto(
        state_sh, bs, bd, w, valid, mesh=mesh, eps=EPS, min_bucket=4
    )
    assert_states_bit_identical(state, state_ws, tag="sem-final-insert-ws")
    assert_states_bit_identical(state, state_sh, tag="sem-final-insert-sh")

    # 4. refresh differential: scratch bulk peel of the survivors agrees
    refreshed = full_refresh(state, eps=EPS)
    scratch = bulk_peel(state.graph, eps=EPS)
    np.testing.assert_array_equal(
        np.asarray(refreshed.level), np.asarray(scratch.level)
    )
    assert float(refreshed.best_g) == float(scratch.best_g)
