"""The tick programs' round counters, their named scopes, and the served
loop's ``spade.*`` profiler spans.

Counters are checked against a brute-force numpy recount: the same
bounded warm re-peel, replayed round by round on the host from the
pre-tick levels and the post-append edge buffer, on integer weights (so
every f32 sum is exact and the two peels take the same rounds).  The
predictive workset engine's counters are checked against the fused
engine's on the same window stream.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.incremental import (
    DeviceSpadeState,
    init_state,
    insert_and_maintain,
    slide_and_maintain,
    tick_counters,
)
from repro.core.peel import edge_ladder
from repro.graphstore.generators import make_transaction_stream
from repro.graphstore.structs import device_graph_from_coo
from repro.serve import EngineSpec, SpadeService

EPS = 0.1


def _graph(rng, n=120, m=900, e_cap=1536):
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    # a planted dense block keeps the suffix alive for a few rounds
    blk = rng.integers(0, 12, (200, 2))
    blk = blk[blk[:, 0] != blk[:, 1]]
    src = np.concatenate([src, blk[:, 0]])
    dst = np.concatenate([dst, blk[:, 1]])
    c = rng.integers(1, 4, src.shape[0]).astype(np.float32)
    a = rng.integers(0, 3, n).astype(np.float32)
    return device_graph_from_coo(n, src, dst, c, a=a, e_capacity=e_cap)


def _batch(rng, n, b=64, n_valid=50):
    src = rng.integers(0, n, b).astype(np.int32)
    dst = ((src + 1 + rng.integers(0, n - 1, b)) % n).astype(np.int32)
    c = rng.integers(1, 4, b).astype(np.float32)
    valid = np.arange(b) < n_valid
    return src, dst, c, valid


def _numpy_recount(level0, g, src_b, dst_b, valid, drop_endpoints,
                   max_rounds):
    """r0 and the per-round (active vertices, live edges) of the warm
    re-peel, recomputed on the host from the post-append graph ``g``."""
    e_src, e_dst = np.asarray(g.src), np.asarray(g.dst)
    c, emask = np.asarray(g.c), np.asarray(g.edge_mask)
    a, vmask = np.asarray(g.a), np.asarray(g.vertex_mask)
    ends = np.concatenate([src_b[valid], dst_b[valid], drop_endpoints])
    r0 = min(int(level0[ends].min()), 2**30)
    active = (level0 >= r0) & vmask
    alive = active[e_src] & active[e_dst] & emask
    cm = np.where(alive, c, 0.0)
    V = a.shape[0]
    w = np.where(active, a, 0.0) + np.bincount(e_src, cm, V) \
        + np.bincount(e_dst, cm, V)
    f = np.float32(np.where(active, a, 0.0).sum() + cm.sum())
    n_act = int(active.sum())
    rv, re = [], []
    for _ in range(max_rounds):
        rv.append(n_act)
        re.append(int(alive.sum()))
        g_cur = np.float32(f) / np.float32(max(n_act, 1))
        peel = active & (w <= np.float32(2.0 * (1.0 + EPS)) * g_cur)
        if not peel.any() and active.any():
            peel = active & (w <= w[active].min())
        e_ps, e_pd = peel[e_src], peel[e_dst]
        cm = np.where(alive, c, 0.0)
        w = w - np.bincount(e_dst, np.where(e_ps & ~e_pd, cm, 0.0), V) \
            - np.bincount(e_src, np.where(e_pd & ~e_ps, cm, 0.0), V)
        alive = alive & ~(e_ps | e_pd)
        active = active & ~peel
        n_act = int(active.sum())
        f = np.float32(np.where(active, a, 0.0).sum()
                       + np.where(alive, c, 0.0).sum())
    return r0, rv, re


@pytest.mark.parametrize("width", ["narrow", "wide"])
@pytest.mark.parametrize("kind", ["insert", "slide"])
@pytest.mark.parametrize("max_rounds", [3, 40])
def test_counters_match_numpy_recount(kind, max_rounds, width):
    rng = np.random.default_rng(11 + max_rounds)
    # wide: a buffer of three ladder stages, which the rounds step down
    graph = _graph(rng) if width == "narrow" else _graph(
        rng, n=6000, m=280_000, e_cap=1 << 19)
    ladder = edge_ladder(graph.e_capacity)
    state = init_state(graph, eps=EPS)
    n = int(np.asarray(state.graph.vertex_mask).sum())
    for tick in range(3):
        src_b, dst_b, c_b, valid = _batch(rng, n)
        level0 = np.asarray(state.level).copy()
        args = (jnp.asarray(src_b), jnp.asarray(dst_b), jnp.asarray(c_b),
                jnp.asarray(valid))
        drop_ends = np.zeros(0, np.int64)
        if kind == "slide":
            # expire the oldest 40 live slots (the window's head)
            drop = np.zeros(state.graph.e_capacity, bool)
            drop[:40] = True
            drop &= np.asarray(state.graph.edge_mask)
            drop_ends = np.concatenate([np.asarray(state.graph.src)[drop],
                                        np.asarray(state.graph.dst)[drop]])
            state, cnt = slide_and_maintain(
                state, jnp.asarray(drop), *args, eps=EPS,
                max_rounds=max_rounds, counters=True)
        else:
            state, cnt = insert_and_maintain(
                state, *args, eps=EPS, max_rounds=max_rounds, counters=True)
        cnt = np.asarray(cnt)
        assert cnt.dtype == np.int32 and cnt.shape == (1 + 3 * max_rounds,)
        r0, rv, re = _numpy_recount(level0, state.graph, src_b, dst_b,
                                    valid, drop_ends, max_rounds)
        got = tick_counters(cnt[None], max_rounds)
        assert int(got["suffix_r0"][0]) == r0
        assert got["round_vertices"][0].tolist() == rv
        assert got["round_edges"][0].tolist() == re
        assert int(got["suffix_vertices"][0]) == rv[0]
        assert int(got["suffix_edges"][0]) == re[0]
        # each round streamed a ladder size that holds its live edges; no
        # slot where no round ran; the sizes only step down
        rs = got["round_slots"][0]
        ran = np.asarray(rv) > 0
        assert set(rs[ran]) <= set(ladder)
        assert (rs >= np.asarray(re)).all()
        assert ((rs == 0) == ~ran).all()
        assert (np.diff(rs) <= 0).all()
        if width == "wide" and max_rounds == 40:
            assert len(set(rs[ran])) > 1
    if max_rounds == 40:
        # the suffix drains well before 40 rounds: the rest read 0
        assert rv[-1] == 0 and re[-1] == 0
    else:
        assert rv[-1] > 0


def test_counters_off_returns_the_state_alone():
    """Off, the tick returns the state alone; on, the same state."""
    states = []
    for counters in (False, True):
        rng = np.random.default_rng(3)
        state = init_state(_graph(rng), eps=EPS)
        src_b, dst_b, c_b, valid = _batch(rng, 120)
        out = insert_and_maintain(
            state, jnp.asarray(src_b), jnp.asarray(dst_b), jnp.asarray(c_b),
            jnp.asarray(valid), eps=EPS, max_rounds=4, counters=counters)
        states.append(out[0] if counters else out)
    assert isinstance(states[0], DeviceSpadeState)
    for field in ("level", "best_g", "community", "w0", "edge_count"):
        np.testing.assert_array_equal(getattr(states[0], field),
                                      getattr(states[1], field))


def test_counters_need_a_bounded_peel():
    rng = np.random.default_rng(4)
    state = init_state(_graph(rng), eps=EPS)
    src_b, dst_b, c_b, valid = _batch(rng, 120)
    with pytest.raises(ValueError, match="bounded"):
        insert_and_maintain(state, jnp.asarray(src_b), jnp.asarray(dst_b),
                            jnp.asarray(c_b), jnp.asarray(valid), eps=EPS,
                            max_rounds=0, counters=True)


def test_compiled_tick_carries_the_named_scopes():
    """The scopes reach the compiled program's ``op_name`` metadata, which
    the device trace's ops carry; the counters do not rename the module."""
    rng = np.random.default_rng(5)
    state = init_state(_graph(rng), eps=EPS)
    src_b, dst_b, c_b, valid = _batch(rng, 120)
    args = (state, jnp.asarray(src_b), jnp.asarray(dst_b), jnp.asarray(c_b),
            jnp.asarray(valid))
    for counters in (False, True):
        lowered = insert_and_maintain.lower(*args, eps=EPS, max_rounds=4,
                                            counters=counters)
        hlo = lowered.compile().as_text()
        for scope in ("peel_gather", "peel_scatter", "peel_update",
                      "tick_prologue", "tick_append", "tick_seed",
                      "tick_rounds", "tick_merge"):
            assert f"/{scope}/" in hlo, scope
        assert "jit_insert_and_maintain" in lowered.as_text()


def _host_spans(path):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("spade."):
                    out.append((e.name, e.start_ns, e.start_ns
                                + e.duration_ns, dict(e.stats)))
    return out


def test_served_loop_spans_in_a_profiler_trace(tmp_path):
    stream = make_transaction_stream(n=600, m=3000, seed=2)
    spec = EngineSpec(batch_edges=128, max_rounds=6)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        rep = SpadeService("DG", spec).run(stream)
    finally:
        jax.profiler.stop_trace()
    files = sorted(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    spans = _host_spans(files[-1])
    ticks = sorted((s for s in spans if s[0] == "spade.tick"),
                   key=lambda s: s[1])
    assert rep.n_ticks > 1
    assert len(ticks) == rep.n_ticks
    assert [int(s[3]["tick"]) for s in ticks] == list(range(rep.n_ticks))
    dispatches = [s for s in spans if s[0] == "spade.dispatch"]
    for _, t0, t1, _ in ticks:
        inside = [d for d in dispatches if t0 <= d[1] and d[2] <= t1]
        assert len(inside) == 1
    names = {s[0] for s in spans}
    assert {"spade.seed", "spade.upload", "spade.initial_peel", "spade.read",
            "spade.prep", "spade.weigh", "spade.drain"} <= names
    # the fused engine's counters ride in the same report
    assert rep.round_vertices.shape == (rep.n_ticks, 6)
    assert rep.edge_slots > 0


def test_workset_engine_leaves_the_counters_empty():
    """Under an unbounded peel the workset engine counts nothing; under a
    bounded one it counts every tick, as the fused engine does."""
    stream = make_transaction_stream(n=600, m=3000, seed=2)
    rep = SpadeService("DG", EngineSpec(batch_edges=128, max_rounds=0,
                                        workset=True)).run(stream)
    assert rep.round_vertices is None and rep.edge_slots is None
    rep = SpadeService("DG", EngineSpec(batch_edges=128, max_rounds=6,
                                        workset=True)).run(stream)
    assert rep.round_vertices.shape == rep.round_slots.shape \
        == (rep.n_ticks, 6)
    assert rep.edge_slots > 0


@pytest.mark.parametrize("stream_kw, window", [
    (dict(n=600, m=3000, seed=2), 3),
    (dict(n=3000, m=15000, seed=9, inc_fraction=0.0), 6),
], ids=["fallback", "workset"])
def test_predictive_counters_match_the_fused_engine(stream_kw, window):
    """On one window stream the predictive engine's rounds see what the
    fused engine's see, row for row.  A fallback tick streams the fused
    tick's ladder; a workset tick streams its edge bucket; a round that
    did not run counts 0.  The background stream reaches level-0 accounts
    and falls back every tick; the planted burst alone takes the workset
    once the predictor has seen small suffixes."""
    stream = make_transaction_stream(**stream_kw)
    spec = EngineSpec(batch_edges=32, max_rounds=8, window_ticks=window)
    fused = SpadeService("DG", spec).run(stream)
    pred = SpadeService("DG", dataclasses.replace(spec, workset=True)).run(
        stream)
    assert pred.n_ticks == fused.n_ticks > window
    assert pred.n_expired_edges == fused.n_expired_edges > 0
    for f in ("round_vertices", "round_edges", "suffix_r0", "suffix_vertices",
              "suffix_edges", "edge_slots"):
        np.testing.assert_array_equal(getattr(pred, f), getattr(fused, f),
                                      err_msg=f)
    rs, ran = pred.round_slots, pred.round_vertices > 0
    assert ((rs > 0) == ran).all()
    on_bucket = (rs != fused.round_slots).any(axis=1)
    assert on_bucket.sum() == pred.n_workset_ticks
    for row, r in zip(rs[on_bucket], ran[on_bucket]):
        bucket = int(row[0])
        assert bucket & (bucket - 1) == 0 and bucket <= pred.edge_slots // 2
        assert set(row[r]) <= set(edge_ladder(bucket))
    if stream_kw.get("inc_fraction") == 0.0:
        assert pred.n_workset_ticks > 0 and pred.n_fallback_ticks > 0
    else:
        assert pred.n_workset_ticks == 0


def test_phase_a_carries_the_named_scopes():
    """The workset engine's phase A names its expiry, its append and the
    suffix count; a window's expiry keeps the module's name."""
    from repro.core.incremental import _insert_phase_a, _slide_phase_a
    from repro.graphstore.structs import WindowExpiry

    rng = np.random.default_rng(6)
    state = init_state(_graph(rng), eps=EPS)
    src_b, dst_b, c_b, valid = _batch(rng, 120)
    batch = (jnp.asarray(src_b), jnp.asarray(dst_b), jnp.asarray(c_b),
             jnp.asarray(valid))
    expiry = WindowExpiry(jnp.int32(500), jnp.int32(64), 4 * 64)
    lowered = _slide_phase_a.lower(state, expiry, *batch)
    hlo = lowered.compile().as_text()
    for scope in ("slide_expire", "slide_append", "workset_count"):
        assert f"/{scope}/" in hlo, scope
    assert "jit__slide_phase_a" in lowered.as_text()
    hlo = _insert_phase_a.lower(state, *batch).compile().as_text()
    for scope in ("slide_append", "workset_count"):
        assert f"/{scope}/" in hlo, scope
