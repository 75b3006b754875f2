"""Device-plane incremental maintenance (the paper's §4, TPU-native).

The host oracle reorders an explicit peeling sequence with a pending heap;
on TPU the same *affected-area* idea becomes a **warm suffix re-peel**:

1. Each vertex carries the ``level`` (bulk-peel round) at which it was
   peeled during the last maintenance pass.  The set
   ``{u : level[u] >= r}`` is exactly the active set at the start of round
   ``r`` (nested family — the vectorized analogue of the peel sequence).
2. An inserted batch only raises the weights of its endpoints (Lemma 4.1's
   vectorized form); with ``r0 = min_{endpoints} level``, every set before
   round ``r0`` is untouched, so maintenance re-peels only
   ``keep = level >= r0`` with weights/f recovered w.r.t. that suffix.
3. Thresholds inside the warm re-peel are computed on the *current*
   restricted set, so each round remains a valid generalized peeling step
   and the global ``2(1+eps)`` guarantee is preserved (proof sketch in
   DESIGN.md §2); the maintained best density never regresses because
   insertions only increase ``f`` of any set containing the endpoints.

New vertices are admitted with ``level = INT32_MAX`` (always inside the
re-peeled suffix without dragging ``r0`` down).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.peel import (
    PeelResultDevice,
    bulk_peel,
    bulk_peel_warm,
    bulk_peel_warm_checked,
    bulk_peel_warm_workset,
    div_rn,
    select_bucket,
    workset_sizes,
)
from repro.graphstore.structs import (
    DeviceGraph,
    WindowExpiry,
    append_edges,
    expire_window,
    expiring_edges,
    remove_edges,
)

__all__ = [
    "DeviceSpadeState",
    "WorksetTickInfo",
    "BucketPredictor",
    "init_state",
    "insert_and_maintain",
    "insert_and_maintain_auto",
    "insert_and_maintain_predictive",
    "delete_and_maintain",
    "slide_and_maintain",
    "slide_and_maintain_auto",
    "slide_and_maintain_predictive",
    "full_refresh",
    "benign_mask",
    "tick_counters",
]

_LEVEL_NEW = jnp.int32(2**31 - 1)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["graph", "level", "best_g", "community", "edge_count", "w0",
                 "w0_exact"],
    meta_fields=[],
)
@dataclasses.dataclass(frozen=True)
class DeviceSpadeState:
    """Evolving-graph fraud-detection state (pure pytree, donate-friendly).

    ``w0[u]`` mirrors the full-graph peeling weight ``w_u(S_0)`` for the
    O(1) benign/urgent test (Def 4.1).  ``w0_exact`` holds while every
    weight summed into ``w0`` was a non-negative integer and no sum passed
    2^24: each f32 sum was then exact, in any order, so ``w0`` is a fresh
    sum's bits and the warm re-peel may read its seed from it
    (:func:`_next_w0`).  Once cleared it stays cleared until a refresh
    sums ``w0`` afresh.
    """

    graph: DeviceGraph
    level: jax.Array  # int32 [V_cap] peel round per vertex
    best_g: jax.Array  # float32 scalar — maintained best density
    community: jax.Array  # bool [V_cap] — maintained S^P
    edge_count: jax.Array  # int32 scalar — next free edge slot
    w0: jax.Array  # float32 [V_cap]
    w0_exact: jax.Array  # bool scalar


_EXACT_LIMIT = float(2**24)


def _integral(x: jax.Array) -> jax.Array:
    """Elementwise: ``x`` is a non-negative integer of at most 2^24, so f32
    sums of such values, while they stay at most 2^24, are exact."""
    return (x >= 0) & (x == jnp.floor(x)) & (x <= _EXACT_LIMIT)


def _exact_weights(g: DeviceGraph, w0: jax.Array) -> jax.Array:
    """``DeviceSpadeState.w0_exact`` of ``w0 = g.peel_weights()`` (or any
    sum of the same weights): every live weight and every sum integral."""
    return (jnp.all(~g.edge_mask | _integral(g.c))
            & jnp.all(~g.vertex_mask | _integral(g.a))
            & jnp.all(_integral(w0)))


def init_state(g: DeviceGraph, eps: float = 0.1) -> DeviceSpadeState:
    """Static bulk peel (Algorithm 1, bulk form) to seed the state."""
    res = bulk_peel(g, eps=eps)
    w0 = g.peel_weights()
    return DeviceSpadeState(
        graph=g,
        level=res.level,
        best_g=res.best_g,
        community=res.community_mask() & g.vertex_mask,
        edge_count=jnp.sum(g.edge_mask).astype(jnp.int32),
        w0=w0,
        w0_exact=_exact_weights(g, w0),
    )


def benign_mask(state: DeviceSpadeState, src, dst, c) -> jax.Array:
    """Vectorized Def 4.1: an edge is benign iff *both* endpoint tests fail
    the urgency condition ``w_u(S_0) + c >= g(S^P)``."""
    urgent = (state.w0[src] + c >= state.best_g) | (state.w0[dst] + c >= state.best_g)
    return ~urgent


class _SlideBookkeeping(NamedTuple):
    """Replicated pre-re-peel bookkeeping shared by the single-device and
    the mesh-sharded window-slide paths (one definition so the two engines
    cannot drift — the same role ``compact_slots`` plays for appends).

    ``dropped``, ``cd``, ``d_src`` and ``d_dst`` are aligned lanes of the
    pre-tick buffer: its ``[E]`` slots for a drop mask (``d_src`` and
    ``d_dst`` ``None``: the old graph's own ``src``/``dst``), or the
    window's span from a :class:`WindowExpiry`'s start."""

    dropped: jax.Array  # live slots being expired
    cd: jax.Array  # expired suspiciousness (0 elsewhere)
    n_new: jax.Array
    r0: jax.Array
    keep: jax.Array
    prior_g: jax.Array
    d_src: jax.Array | None = None
    d_dst: jax.Array | None = None


def _slide_prologue(
    state: DeviceSpadeState, drop: jax.Array | WindowExpiry | None, src,
    dst, valid
) -> _SlideBookkeeping:
    """``drop = None`` marks an insert-only tick at trace time: the dropped
    bookkeeping collapses to inert zeros and the [E]-sized passes over the
    drop mask are elided from the program entirely.  A
    :class:`WindowExpiry` reads only the window's span, so expiring a
    window's oldest tick costs the window, not the buffer."""
    g0 = state.graph
    n_new = jnp.sum(valid).astype(jnp.int32)
    d_src = d_dst = None
    if drop is None:
        dropped = jnp.zeros(g0.e_capacity, bool)
        n_del = jnp.int32(0)
        cd = jnp.zeros(g0.e_capacity, jnp.float32)
        lvl = _LEVEL_NEW
        comm_loss = jnp.float32(0.0)
    else:
        if isinstance(drop, WindowExpiry):
            es, ed, dc, dropped = expiring_edges(g0, drop)
            d_src, d_dst = es, ed
        else:
            es, ed, dc = g0.src, g0.dst, g0.c
            dropped = drop & g0.edge_mask
        n_del = jnp.sum(dropped).astype(jnp.int32)
        cd = jnp.where(dropped, dc, 0.0)
        # affected suffix start: min endpoint level over dropped AND
        # inserted edges (both endpoint sets sit inside the suffix)
        lvl = jnp.minimum(
            jnp.min(jnp.where(dropped, state.level[es], _LEVEL_NEW)),
            jnp.min(jnp.where(dropped, state.level[ed], _LEVEL_NEW)),
        )
        # exact density loss of the old community in the post-deletion
        # graph: the dropped mass with both endpoints inside S^P
        in_comm = state.community[es] & state.community[ed]
        comm_loss = jnp.sum(jnp.where(dropped & in_comm, dc, 0.0))
    lvl = jnp.minimum(lvl, jnp.min(jnp.where(valid, state.level[src], _LEVEL_NEW)))
    lvl = jnp.minimum(lvl, jnp.min(jnp.where(valid, state.level[dst], _LEVEL_NEW)))
    r0 = jnp.where((n_del > 0) | (n_new > 0), lvl, _LEVEL_NEW)
    r0 = jnp.minimum(r0, jnp.int32(2**30))

    # re-seed the best tracker with the old community's exact post-deletion
    # density (stale-low if best_g was already conservative — only ever
    # under-reports, never hides fraud); deletion may legally regress it
    n_comm = jnp.sum(state.community).astype(jnp.float32)
    prior_g = jnp.where(
        n_comm > 0, state.best_g - div_rn(comm_loss, jnp.maximum(n_comm, 1.0)),
        -jnp.float32(jnp.inf),
    )
    return _SlideBookkeeping(
        dropped=dropped, cd=cd, n_new=n_new, r0=r0,
        keep=state.level >= r0, prior_g=prior_g, d_src=d_src, d_dst=d_dst,
    )


def _next_w0(
    state: DeviceSpadeState,
    bk: _SlideBookkeeping,
    src, dst, c, valid,
    with_drops: bool = True,
    d_bucket: int = 0,
) -> tuple[jax.Array, jax.Array]:
    """The full graph's peel weights after the tick, ``(w0, w0_exact)``:
    ``state.w0`` less the dropped edges' mass, plus the batch's valid
    lanes, on both endpoints, and whether they are still exact.

    One definition for the merge's ``w0`` and the warm re-peel's seed
    (:func:`repro.core.peel.bulk_peel_warm`).  It is ``peel_weights`` of
    the post-tick graph while every valid lane was appended, which
    callers guarantee by sizing the buffer; padding lanes carry ``cd = 0``
    / ``cv = 0``.  While ``w0_exact`` holds (every batch weight a
    non-negative integer, every weight at most 2^24: DG) the decrement
    and increment are exact, so ``w0`` is a fresh sum's bits.  Otherwise
    each tick's adds and subtracts round, and the rounding walks with
    the stream, sized to the largest weight a vertex has held; the seed
    then sums afresh, and only the benign test reads ``w0``.

    ``with_drops = False`` (insert-only ticks) statically elides the
    dropped-mass decrement, restoring in-place donation of the edge
    buffers (the decrement gathers pre-update ``src/dst``, which otherwise
    blocks XLA from reusing them for the appended graph).

    ``d_bucket > 0`` (workset dispatch; the host has synced the dropped
    count) compacts the dropped edges into a ``d_bucket``-sized buffer by
    the same searchsorted gather the workset uses, so the decrement
    scatter-adds O(dropped) updates instead of one per drop-mask lane —
    on a steady-state tick the dropped batch is ~1k lanes of a ~400k
    buffer.  A :class:`WindowExpiry`'s lanes are the window's span alone.
    """
    g0 = state.graph
    w0 = state.w0
    d_src = g0.src if bk.d_src is None else bk.d_src
    d_dst = g0.dst if bk.d_dst is None else bk.d_dst
    if with_drops and 0 < d_bucket < bk.cd.shape[0]:
        dsum = jnp.cumsum(bk.dropped.astype(jnp.int32))
        nd = dsum[-1]
        lane = jnp.arange(d_bucket, dtype=jnp.int32)
        didx = jnp.searchsorted(dsum, lane + 1).astype(jnp.int32)
        dlive = lane < nd
        didx = jnp.where(dlive, didx, 0)
        pad = jnp.int32(g0.n_capacity)  # out of range -> dropped by scatter
        dsrc = jnp.where(dlive, d_src[didx], pad)
        ddst = jnp.where(dlive, d_dst[didx], pad)
        dc = jnp.where(dlive, bk.cd[didx], 0.0)
        w0 = w0.at[dsrc].add(-dc, mode="drop")
        w0 = w0.at[ddst].add(-dc, mode="drop")
    elif with_drops:
        w0 = w0.at[d_src].add(-bk.cd, mode="drop")
        w0 = w0.at[d_dst].add(-bk.cd, mode="drop")
    cv = jnp.where(valid, c.astype(jnp.float32), 0.0)
    w0 = w0.at[src].add(cv, mode="drop")
    w0 = w0.at[dst].add(cv, mode="drop")
    exact = (state.w0_exact & jnp.all(~valid | _integral(cv))
             & jnp.all(_integral(w0)))
    return w0, exact


def _slide_epilogue(
    state: DeviceSpadeState,
    g: DeviceGraph,
    res: PeelResultDevice,
    bk: _SlideBookkeeping,
    n_removed: jax.Array,
    w0: jax.Array,
    w0_exact: jax.Array,
) -> DeviceSpadeState:
    """Merge a warm re-peel back into the state (level rebase, community
    update, edge-counter move); ``(w0, w0_exact)`` is the tick's
    :func:`_next_w0`."""
    suffix_level = jnp.where(res.level >= 0, res.level, res.n_rounds)
    new_level = jnp.where(bk.keep, bk.r0 + suffix_level, state.level)
    improved = res.best_g > bk.prior_g
    new_comm = jnp.where(
        improved,
        (res.level >= res.best_level) & bk.keep & g.vertex_mask,
        state.community,
    )
    return DeviceSpadeState(
        graph=g,
        level=new_level,
        best_g=jnp.maximum(res.best_g, bk.prior_g),
        community=new_comm,
        edge_count=state.edge_count - n_removed + bk.n_new,
        w0=w0,
        w0_exact=w0_exact,
    )


def tick_counters(counts: np.ndarray, max_rounds: int) -> dict:
    """Split the fused tick's counter vectors, stacked ``[n_ticks, 2 + 3 *
    max_rounds]`` on the host, into named ``int64`` arrays.

    ``suffix_r0``: the tick's affected-suffix start ``r0``;
    ``seed_reused``: 1 where the warm re-peel read its seed from the
    maintained weights (the suffix held every live vertex and the weights
    were exact, :func:`repro.core.peel._warm_seed`), 0 where it summed it;
    ``round_vertices[t, i]`` / ``round_edges[t, i]``: active vertices and
    live edges of the restricted set at the start of round ``i``;
    ``round_slots[t, i]``: the edge slots round ``i`` streamed (0 where no
    round ran, :func:`repro.core.peel._run_stages`).  Round 0 holds the
    whole suffix: ``suffix_vertices`` and ``suffix_edges`` (the suffix's
    induced live edges) are its column.
    """
    counts = np.asarray(counts, np.int64).reshape(-1, 2 + 3 * max_rounds)
    rv, re, rs = np.split(counts[:, 2:], 3, axis=1)
    return {"suffix_r0": counts[:, 0], "seed_reused": counts[:, 1],
            "suffix_vertices": rv[:, 0].copy(),
            "suffix_edges": re[:, 0].copy(), "round_vertices": rv,
            "round_edges": re, "round_slots": rs}


def _merge(state, g, res, bk, n_removed, w_next, counters):
    """A re-peel's merge into the state (scope ``tick_merge``); ``w_next``
    is the tick's :func:`_next_w0`, computed before the re-peel under the
    same scope, since the seed reads it.  With
    ``counters``, ``res`` is ``(result, counts)`` and the tick's int32
    counter vector ``[r0, seed_reused, round_vertices..., round_edges...,
    round_slots...]`` comes back beside the state."""
    if counters:
        res, counts = res
    with jax.named_scope("tick_merge"):
        new = _slide_epilogue(state, g, res, bk, n_removed, *w_next)
    if not counters:
        return new
    return new, jnp.concatenate([bk.r0.astype(jnp.int32)[None], *counts])


def _warm_and_merge(state, g, bk, n_removed, src, dst, c, valid, eps,
                    max_rounds, counters, with_drops=True):
    """The fused tick's warm re-peel and merge (see :func:`_merge`)."""
    with jax.named_scope("tick_merge"):
        w0, exact = _next_w0(state, bk, src, dst, c, valid,
                             with_drops=with_drops)
    res = bulk_peel_warm(g, bk.keep, prior_best_g=bk.prior_g, eps=eps,
                         max_rounds=max_rounds, counters=counters, w_full=w0,
                         w_exact=exact)
    return _merge(state, g, res, bk, n_removed, (w0, exact), counters)


def _expire(g: DeviceGraph, drop) -> tuple[DeviceGraph, jax.Array]:
    """Remove the edges of a drop mask, or a window's oldest tick at the
    cost of the window (:func:`repro.graphstore.structs.expire_window`)."""
    if isinstance(drop, WindowExpiry):
        return expire_window(g, drop)
    return remove_edges(g, drop)


@partial(jax.jit, static_argnames=("eps", "max_rounds", "counters"),
         donate_argnames=("state",))
def insert_and_maintain(
    state: DeviceSpadeState,
    src: jax.Array,
    dst: jax.Array,
    c: jax.Array,
    valid: jax.Array,
    eps: float = 0.1,
    max_rounds: int = 0,
    counters: bool = False,
):
    """Insert an edge batch and maintain the community incrementally.

    ``src/dst/c`` are fixed-size batch arrays with a ``valid`` mask
    (streaming ticks pad to the batch size).  One fused device program:
    append -> affected-suffix recovery -> warm bulk re-peel -> state merge.
    The suffix/merge bookkeeping is the shared ``_slide_prologue`` /
    ``_slide_epilogue`` with an empty drop mask (insertion is a window
    slide that expires nothing — one definition for insert/delete/slide,
    so the three paths cannot drift); unlike the slide the live prefix is
    untouched, so the compaction pass is skipped entirely.

    Returns the new state; with ``counters`` (a bounded peel only) returns
    ``(state, counts)``, ``counts`` the tick's int32 counter vector from
    the same program (:func:`tick_counters` names its parts).  The steps
    sit under the named scopes ``tick_prologue``, ``tick_append``,
    ``tick_seed``, ``tick_rounds`` and ``tick_merge``.
    """
    with jax.named_scope("tick_prologue"):
        bk = _slide_prologue(state, None, src, dst, valid)
    with jax.named_scope("tick_append"):
        g = append_edges(state.graph, state.edge_count, src, dst, c,
                         valid=valid)
    return _warm_and_merge(state, g, bk, jnp.int32(0), src, dst, c, valid,
                           eps, max_rounds, counters, with_drops=False)


def delete_and_maintain(
    state: DeviceSpadeState,
    drop: jax.Array,
    eps: float = 0.1,
    max_rounds: int = 0,
) -> DeviceSpadeState:
    """Delete the edges in slot mask ``drop`` and maintain incrementally.

    The deletion mirror of :func:`insert_and_maintain` (paper Appendix C.1,
    vectorized — DESIGN.md §6): deleted edges only *lower* the weights of
    their endpoints, and with ``r0 = min_{endpoints} level`` both endpoints
    of every dropped edge sit inside the suffix ``level >= r0``, so no
    prefix vertex's peel-time weight changes and only the suffix is
    re-peeled.  Unlike insertion the maintained best density may legally
    *regress*: the tracker is re-seeded with the exact density of the
    previous community in the post-deletion graph (its stored value minus
    the dropped mass with both endpoints inside it) rather than the stale
    pre-deletion value.  ``remove_edges`` compacts the surviving slots to
    the buffer prefix, so the edge counter simply shrinks by the number of
    live edges dropped.

    Exactly a window slide with an empty insert batch (the shared jitted
    program handles both).
    """
    z = jnp.zeros(1, jnp.int32)
    return slide_and_maintain(
        state, drop, z, z, z.astype(jnp.float32), jnp.zeros(1, bool),
        eps=eps, max_rounds=max_rounds,
    )


@partial(jax.jit, static_argnames=("eps", "max_rounds", "counters"),
         donate_argnames=("state",))
def slide_and_maintain(
    state: DeviceSpadeState,
    drop: jax.Array | WindowExpiry,
    src: jax.Array,
    dst: jax.Array,
    c: jax.Array,
    valid: jax.Array,
    eps: float = 0.1,
    max_rounds: int = 0,
    counters: bool = False,
):
    """One fused sliding-window tick: expire ``drop``, insert the batch,
    re-peel **once** (paper Appendix C.3, vectorized).

    Composing :func:`delete_and_maintain` + :func:`insert_and_maintain`
    would re-peel the affected suffix twice per tick; here ``r0`` is the
    minimum endpoint level over dropped *and* inserted edges, so a single
    warm re-peel covers both updates — the steady-state serving loop does
    one device program per tick.  Bookkeeping composes the two paths:
    ``w0`` is decremented by dropped mass and incremented by inserted
    mass, the best-density tracker is re-seeded with the old community's
    exact post-deletion density (DESIGN.md §6), and the edge counter
    shrinks by the dropped count and grows by the inserted count.
    ``drop`` is an ``[E]`` slot mask, or the :class:`WindowExpiry` of a
    window's oldest tick, whose expiry reads and moves only the window's
    span.  ``counters`` and the named scopes as in
    :func:`insert_and_maintain`.
    """
    with jax.named_scope("tick_prologue"):
        bk = _slide_prologue(state, drop, src, dst, valid)
    with jax.named_scope("tick_append"):
        g, n_removed = _expire(state.graph, drop)
        g = append_edges(g, state.edge_count - n_removed, src, dst, c,
                         valid=valid)
    return _warm_and_merge(state, g, bk, n_removed, src, dst, c, valid,
                           eps, max_rounds, counters)


# ---------------------------------------------------------------------------
# workset dispatch: gather the affected suffix, peel the workset only
# ---------------------------------------------------------------------------
#
# The fused programs above stream the full capacity-padded buffers every
# round.  The workset engine (DESIGN.md §8) splits a tick into two device
# programs: phase A applies the structural update and counts the affected
# suffix; the host syncs the two count scalars, picks power-of-two buckets
# (O(log E) jitted variants), and dispatches phase B — the warm re-peel
# over the gathered workset, or the full-buffer path when the suffix
# exceeds the largest bucket.


class WorksetTickInfo(NamedTuple):
    """Host-side telemetry for one auto-dispatched maintenance tick.

    ``n_suffix_edges`` is the global suffix-induced live-edge count on a
    single device but the MAX **per-shard** count under a mesh (the
    sharded engine buckets each shard's local workset; see
    ``sharded_workset_sizes``) — compare across modes accordingly.
    """

    n_suffix_vertices: int
    n_suffix_edges: int
    v_bucket: int  # 0 on fallback
    e_bucket: int  # 0 on fallback
    fallback: bool
    # predictive dispatch (BucketPredictor): buckets were chosen from the
    # previous tick's counts without waiting for this tick's sync; a miss
    # (counts outgrew the prediction) rode the in-program full-buffer
    # fallback — correct, just slower — and re-anchored the predictor
    predicted: bool = False
    miss: bool = False
    # with ``counters`` (single device): phase B's int32 counter vector, on
    # device, as the fused tick's (:func:`tick_counters`); its rounds
    # stream the staged ladder on a fallback, the edge bucket otherwise
    counts: jax.Array | None = None


# Phase A's steps sit under the named scopes ``slide_expire`` (a slide
# tick's bookkeeping and expiry), ``slide_append`` (an insert tick's
# bookkeeping, and every tick's append) and ``workset_count``.


@jax.jit
def _insert_phase_a(state, src, dst, c, valid):
    with jax.named_scope("slide_append"):
        bk = _slide_prologue(state, None, src, dst, valid)
        g = append_edges(state.graph, state.edge_count, src, dst, c,
                         valid=valid)
    with jax.named_scope("workset_count"):
        nv, ne = workset_sizes(g, bk.keep)
    return g, bk, jnp.int32(0), nv, ne


@jax.jit
def _slide_phase_a(state, drop, src, dst, c, valid):
    with jax.named_scope("slide_expire"):
        bk = _slide_prologue(state, drop, src, dst, valid)
        g, n_removed = _expire(state.graph, drop)
    with jax.named_scope("slide_append"):
        g = append_edges(g, state.edge_count - n_removed, src, dst, c,
                         valid=valid)
    with jax.named_scope("workset_count"):
        nv, ne = workset_sizes(g, bk.keep)
    return g, bk, n_removed, nv, ne


@partial(
    jax.jit,
    static_argnames=("eps", "max_rounds", "v_bucket", "e_bucket", "use_kernel",
                     "with_drops", "d_bucket", "counters"),
    donate_argnames=("state", "g"),
)
def _phase_b(
    state, g, bk, n_removed, src, dst, c, valid,
    eps: float = 0.1,
    max_rounds: int = 0,
    v_bucket: int = 0,
    e_bucket: int = 0,
    use_kernel: bool = False,
    with_drops: bool = True,
    d_bucket: int = 0,
    counters: bool = False,
):
    """Warm re-peel + state merge.  ``v_bucket/e_bucket = 0`` selects the
    full-buffer fallback; otherwise the bucketed workset path.  With
    ``counters``, ``(state, counts)`` as :func:`_merge` says."""
    with jax.named_scope("tick_merge"):
        w0, exact = _next_w0(state, bk, src, dst, c, valid,
                             with_drops=with_drops, d_bucket=d_bucket)
    if v_bucket and e_bucket:
        res = bulk_peel_warm_workset(
            g, bk.keep, prior_best_g=bk.prior_g, eps=eps, max_rounds=max_rounds,
            v_bucket=v_bucket, e_bucket=e_bucket, use_kernel=use_kernel,
            counters=counters,
        )
    else:
        res = bulk_peel_warm(g, bk.keep, prior_best_g=bk.prior_g, eps=eps,
                             max_rounds=max_rounds, use_kernel=use_kernel,
                             counters=counters, w_full=w0, w_exact=exact)
    return _merge(state, g, res, bk, n_removed, (w0, exact), counters)


def _unpack(out, counters: bool):
    """``(state, counts)`` of a phase B that counted, ``(state, None)`` of
    one that did not."""
    return out if counters else (out, None)


def _dispatch_phase_b(
    state, g, bk, n_removed, src, dst, c, valid,
    nv, ne, eps, max_rounds, use_kernel, min_bucket, with_drops=True,
    counters=False,
) -> tuple[DeviceSpadeState, WorksetTickInfo]:
    n_cap, e_cap = state.graph.n_capacity, state.graph.e_capacity
    # the tick's only device->host sync: three scalars, one transfer
    nv_i, ne_i, nd_i = (int(x) for x in np.asarray(
        jnp.stack([nv, ne, n_removed])
    ))
    bv = select_bucket(nv_i, n_cap, floor=min_bucket)
    be = select_bucket(ne_i, e_cap, floor=min_bucket)
    if bv is None or be is None:  # suffix too large: full-buffer fallback
        bv = be = 0
    # nothing actually dropped (e.g. window still filling): statically skip
    # the w0 decrement — same program as an insert tick, no extra variant
    with_drops = with_drops and nd_i > 0
    # bucket the dropped-edge count too: the w0 decrement then scatters
    # O(dropped) updates instead of O(E_capacity) (None -> full scatter)
    bd = 0
    if with_drops:
        bd = select_bucket(nd_i, e_cap, floor=min_bucket) or 0
    new_state, counts = _unpack(_phase_b(
        state, g, bk, n_removed, src, dst, c, valid,
        eps=eps, max_rounds=max_rounds, v_bucket=bv, e_bucket=be,
        use_kernel=use_kernel, with_drops=with_drops, d_bucket=bd,
        counters=counters,
    ), counters)
    return new_state, WorksetTickInfo(nv_i, ne_i, bv, be, not (bv and be),
                                      counts=counts)


def insert_and_maintain_auto(
    state: DeviceSpadeState,
    src: jax.Array,
    dst: jax.Array,
    c: jax.Array,
    valid: jax.Array,
    eps: float = 0.1,
    max_rounds: int = 0,
    use_kernel: bool = False,
    min_bucket: int = 64,
    counters: bool = False,
) -> tuple[DeviceSpadeState, WorksetTickInfo]:
    """:func:`insert_and_maintain` through the workset engine.

    Two device programs + one scalar sync per tick; bit-identical to the
    fused path on integer weights (workset or fallback alike).
    ``counters`` (a bounded peel) puts phase B's counter vector in
    ``WorksetTickInfo.counts``.
    """
    g, bk, n_removed, nv, ne = _insert_phase_a(state, src, dst, c, valid)
    return _dispatch_phase_b(state, g, bk, n_removed, src, dst, c, valid,
                             nv, ne, eps, max_rounds, use_kernel, min_bucket,
                             with_drops=False, counters=counters)


def slide_and_maintain_auto(
    state: DeviceSpadeState,
    drop: jax.Array | WindowExpiry,
    src: jax.Array,
    dst: jax.Array,
    c: jax.Array,
    valid: jax.Array,
    eps: float = 0.1,
    max_rounds: int = 0,
    use_kernel: bool = False,
    min_bucket: int = 64,
    counters: bool = False,
) -> tuple[DeviceSpadeState, WorksetTickInfo]:
    """:func:`slide_and_maintain` through the workset engine (also covers
    pure deletion: pass an all-False ``valid``)."""
    g, bk, n_removed, nv, ne = _slide_phase_a(state, drop, src, dst, c, valid)
    return _dispatch_phase_b(state, g, bk, n_removed, src, dst, c, valid,
                             nv, ne, eps, max_rounds, use_kernel, min_bucket,
                             counters=counters)


# ---------------------------------------------------------------------------
# predictive dispatch: pick buckets from the PREVIOUS tick's counts, check
# the fit on device, and fetch this tick's counts only after phase B is
# already in flight — no blocking device->host sync in the serving loop
# ---------------------------------------------------------------------------


class BucketPredictor:
    """Host-side predictive workset-bucket selector.

    The synced dispatcher (:func:`insert/slide_and_maintain_auto`) blocks
    on this tick's suffix counts before it can pick buckets and dispatch
    phase B — the serving loop's only blocking device->host transfer.
    The predictor removes it: buckets come from the running max of the
    last ``history`` ticks' counts, phase B dispatches immediately with a
    device-side fit check (:func:`repro.core.peel.bulk_peel_warm_checked`),
    and the actual counts are drained *after* dispatch, off the critical
    path, to feed the next prediction.  A bucket miss rides the in-program
    full-buffer fallback — the synced-scalar semantics, selected on device
    instead of on host — so prediction can cost a slow tick but never a
    wrong one.

    One predictor per served stream; ``e_capacity`` is the *per-shard*
    local capacity under a mesh (the sharded engine buckets per-shard
    counts; see ``sharded_workset_sizes``).
    """

    def __init__(
        self,
        n_capacity: int,
        e_capacity: int,
        min_bucket: int = 64,
        history: int = 4,
    ):
        self.n_capacity = int(n_capacity)
        self.e_capacity = int(e_capacity)
        self.min_bucket = int(min_bucket)
        self.history = max(int(history), 1)
        self._nv: list[int] = []
        self._ne: list[int] = []

    def predict(self) -> tuple[int, int] | None:
        """``None`` before any observation (callers take the synced path);
        ``(0, 0)`` when the recent suffix outgrew the bucket ladder (direct
        full-buffer dispatch, no check needed); else ``(v_bucket,
        e_bucket)`` for the checked dispatch."""
        if not self._nv:
            return None
        bv = select_bucket(max(self._nv), self.n_capacity, floor=self.min_bucket)
        be = select_bucket(max(self._ne), self.e_capacity, floor=self.min_bucket)
        if bv is None or be is None:
            return (0, 0)
        return (bv, be)

    def observe(self, nv: int, ne: int) -> None:
        self._nv = (self._nv + [int(nv)])[-self.history:]
        self._ne = (self._ne + [int(ne)])[-self.history:]


@partial(
    jax.jit,
    static_argnames=("eps", "max_rounds", "v_bucket", "e_bucket", "use_kernel",
                     "with_drops", "d_bucket", "counters"),
    donate_argnames=("state", "g"),
)
def _phase_b_checked(
    state, g, bk, n_removed, nv, ne, src, dst, c, valid,
    eps: float = 0.1,
    max_rounds: int = 0,
    v_bucket: int = 0,
    e_bucket: int = 0,
    use_kernel: bool = False,
    with_drops: bool = True,
    d_bucket: int = 0,
    counters: bool = False,
):
    """Phase B with predicted buckets: the workset/full-buffer choice moves
    onto the device (``lax.cond`` on the actual counts), so dispatch needs
    no host-resident count.  Returns ``(out, fits)``, ``out`` as
    :func:`_phase_b`'s."""
    with jax.named_scope("tick_merge"):
        w0, exact = _next_w0(state, bk, src, dst, c, valid,
                             with_drops=with_drops, d_bucket=d_bucket)
    res, fits = bulk_peel_warm_checked(
        g, bk.keep, bk.prior_g, nv, ne, eps=eps, max_rounds=max_rounds,
        v_bucket=v_bucket, e_bucket=e_bucket, use_kernel=use_kernel,
        counters=counters, w_full=w0, w_exact=exact,
    )
    return _merge(state, g, res, bk, n_removed, (w0, exact), counters), fits


def _predictive_dispatch_core(
    state, nv, ne, predictor: BucketPredictor, with_drops, n_dropped,
    *, synced, checked, full, counters=False,
) -> tuple[DeviceSpadeState, WorksetTickInfo]:
    """Predictor-driven phase-B dispatch, shared by the single-device and
    mesh-sharded engines (they differ only in the three phase-B callables:
    ``synced(with_drops)``, ``checked(bv, be, wd, bd)``,
    ``full(wd, bd)``; with ``counters`` the last two return ``(state,
    counts)``).

    Counts are fetched only *after* dispatch.  ``n_dropped`` is the host's
    (upper bound on the) number of live edges in the drop mask — the
    windowed service knows it exactly from its ring bookkeeping, which
    keeps the ``d_bucket`` compaction static without a sync; ``None``
    falls back to the full-width w0 decrement scatter."""
    pred = predictor.predict()
    if pred is None:
        # no history yet: classic synced-scalar dispatch seeds the predictor
        new_state, info = synced(with_drops)
        predictor.observe(info.n_suffix_vertices, info.n_suffix_edges)
        return new_state, info

    wd = with_drops and n_dropped != 0
    bd = 0
    if wd and n_dropped is not None:
        bd = select_bucket(n_dropped, state.graph.e_capacity,
                           floor=predictor.min_bucket) or 0
    bv, be = pred
    if bv and be:
        out, _fits = checked(bv, be, wd, bd)
    else:  # recent suffixes outgrew the ladder: full-buffer, no check
        out = full(wd, bd)
    new_state, counts = _unpack(out, counters)
    # drained AFTER dispatch: the transfer overlaps phase B instead of
    # gating it — feeds the next prediction and the telemetry only
    nv_i, ne_i = (int(x) for x in np.asarray(jnp.stack([nv, ne])))
    predictor.observe(nv_i, ne_i)
    hit = bool(bv and be) and nv_i <= bv and ne_i <= be
    return new_state, WorksetTickInfo(
        nv_i, ne_i,
        v_bucket=bv if hit else 0,
        e_bucket=be if hit else 0,
        fallback=not hit,
        predicted=True,
        miss=bool(bv and be) and not hit,
        counts=counts,
    )


def _predictive_dispatch(
    state, g, bk, n_removed, src, dst, c, valid, nv, ne,
    predictor: BucketPredictor, eps, max_rounds, use_kernel,
    with_drops=True, n_dropped=None, counters=False,
) -> tuple[DeviceSpadeState, WorksetTickInfo]:
    """Single-device binding of :func:`_predictive_dispatch_core`."""
    return _predictive_dispatch_core(
        state, nv, ne, predictor, with_drops, n_dropped,
        synced=lambda wd: _dispatch_phase_b(
            state, g, bk, n_removed, src, dst, c, valid, nv, ne,
            eps, max_rounds, use_kernel, predictor.min_bucket, with_drops=wd,
            counters=counters,
        ),
        checked=lambda bv, be, wd, bd: _phase_b_checked(
            state, g, bk, n_removed, nv, ne, src, dst, c, valid,
            eps=eps, max_rounds=max_rounds, v_bucket=bv, e_bucket=be,
            use_kernel=use_kernel, with_drops=wd, d_bucket=bd,
            counters=counters,
        ),
        full=lambda wd, bd: _phase_b(
            state, g, bk, n_removed, src, dst, c, valid,
            eps=eps, max_rounds=max_rounds, v_bucket=0, e_bucket=0,
            use_kernel=use_kernel, with_drops=wd, d_bucket=bd,
            counters=counters,
        ),
        counters=counters,
    )


def insert_and_maintain_predictive(
    state: DeviceSpadeState,
    src: jax.Array,
    dst: jax.Array,
    c: jax.Array,
    valid: jax.Array,
    predictor: BucketPredictor,
    eps: float = 0.1,
    max_rounds: int = 0,
    use_kernel: bool = False,
    counters: bool = False,
) -> tuple[DeviceSpadeState, WorksetTickInfo]:
    """:func:`insert_and_maintain_auto` without the blocking count sync:
    buckets are predicted from ``predictor``'s history and checked on
    device.  Bit-identical results to the synced/fused paths on integer
    weights (bucket choice never changes the math, only the cost).
    ``counters`` as in :func:`insert_and_maintain_auto`."""
    g, bk, n_removed, nv, ne = _insert_phase_a(state, src, dst, c, valid)
    return _predictive_dispatch(state, g, bk, n_removed, src, dst, c, valid,
                                nv, ne, predictor, eps, max_rounds, use_kernel,
                                with_drops=False, n_dropped=0,
                                counters=counters)


def slide_and_maintain_predictive(
    state: DeviceSpadeState,
    drop: jax.Array | WindowExpiry,
    src: jax.Array,
    dst: jax.Array,
    c: jax.Array,
    valid: jax.Array,
    predictor: BucketPredictor,
    n_dropped: int | None = None,
    eps: float = 0.1,
    max_rounds: int = 0,
    use_kernel: bool = False,
    counters: bool = False,
) -> tuple[DeviceSpadeState, WorksetTickInfo]:
    """:func:`slide_and_maintain_auto` without the blocking count sync.

    ``n_dropped``: host-known upper bound on the live edges in a drop
    mask (the windowed service's ring count is exact); ``None`` keeps the
    decrement over every lane of ``drop`` (a :class:`WindowExpiry` has
    the window's span of them)."""
    g, bk, n_removed, nv, ne = _slide_phase_a(state, drop, src, dst, c, valid)
    return _predictive_dispatch(state, g, bk, n_removed, src, dst, c, valid,
                                nv, ne, predictor, eps, max_rounds, use_kernel,
                                n_dropped=n_dropped, counters=counters)


@partial(jax.jit, static_argnames=("eps",))
def full_refresh(state: DeviceSpadeState, eps: float = 0.1) -> DeviceSpadeState:
    """Periodic from-scratch bulk peel (compaction / drift control)."""
    res = bulk_peel(state.graph, eps=eps)
    w0 = state.graph.peel_weights()
    return DeviceSpadeState(
        graph=state.graph,
        level=res.level,
        best_g=res.best_g,
        community=res.community_mask() & state.graph.vertex_mask,
        edge_count=state.edge_count,
        w0=w0,
        w0_exact=_exact_weights(state.graph, w0),
    )


def admit_vertices(state: DeviceSpadeState, ids: jax.Array, a: jax.Array) -> DeviceSpadeState:
    """Activate new vertex ids (host-orchestrated; ids within capacity)."""
    g = state.graph
    vm = g.vertex_mask.at[ids].set(True, mode="drop")
    av = g.a.at[ids].set(a.astype(jnp.float32), mode="drop")
    return dataclasses.replace(
        state,
        graph=dataclasses.replace(g, vertex_mask=vm, a=av),
        level=state.level.at[ids].set(_LEVEL_NEW, mode="drop"),
        w0=state.w0.at[ids].set(a.astype(jnp.float32), mode="drop"),
        w0_exact=state.w0_exact & jnp.all(_integral(a.astype(jnp.float32))),
    )
