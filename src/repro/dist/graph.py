"""Mesh-sharded incremental peeling: the dist plane's graph engine.

Dupin-style edge partitioning: a :class:`DeviceGraph`'s COO edge buffers
are block-partitioned along one mesh axis while every vertex array stays
replicated.  Each bulk-peel round is then embarrassingly parallel — every
shard segment-sums the suspiciousness its *local* edges contribute to
each vertex — followed by one ``psum`` that recovers the global
per-vertex weight deltas (plus the scalar f/edge-loss terms, fused into
the same all-reduce).  Thresholds, peel masks and the detected community
are computed from the psum'd (replicated) quantities, so every shard
takes the identical round sequence and the result matches single-device
:func:`repro.core.peel.bulk_peel` exactly for order-robust weights
(integer-valued suspiciousness sums are exact in f32) and up to
reduction-order rounding otherwise.  The 2(1+eps) guarantee carries over
unchanged: the sharded round computes the same generalized peeling step,
only the reduction is distributed.

Capacity growth stays a host-side reallocation; edge *insertion* is a
device-side sharded scatter: the batch is replicated, each shard claims
the global slot range it owns (``edge_count`` is a replicated scalar) and
writes only the batch entries that land in its block.

Entry points mirror the single-device engine one-for-one:

=============================  ========================================
single device                  sharded (``mesh=``, ``axis=``)
=============================  ========================================
``bulk_peel``                  ``sharded_bulk_peel``
``bulk_peel_warm``             ``sharded_bulk_peel_warm``
``bulk_peel_warm_workset``     ``sharded_bulk_peel_warm_workset``
``DeviceGraph.peel_weights``   ``sharded_peel_weights``
``init_state``                 ``init_sharded_state``
``insert_and_maintain``        ``sharded_insert_and_maintain``
``insert_and_maintain_auto``   ``sharded_insert_and_maintain_auto``
``insert_..._predictive``      ``sharded_insert_and_maintain_predictive``
``delete_and_maintain``        ``sharded_delete_and_maintain``
``slide_and_maintain``         ``sharded_slide_and_maintain``
``slide_and_maintain_auto``    ``sharded_slide_and_maintain_auto``
``slide_..._predictive``       ``sharded_slide_and_maintain_predictive``
``full_refresh``               ``sharded_full_refresh``
=============================  ========================================

The engines are semantics-agnostic by design: edge suspiciousness arrives
pre-weighted through one compiled :class:`repro.core.semantics.
SuspSemantics` (the service plane jits ``batch_weights`` once per
semantics), so a user-defined semantics reaches the sharded fast path
without touching this file.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.incremental import (
    BucketPredictor,
    DeviceSpadeState,
    WorksetTickInfo,
    _exact_weights,
    _next_w0,
    _predictive_dispatch_core,
    _slide_epilogue,
    _slide_prologue,
)
from repro.core.peel import (
    PeelResultDevice,
    _compact_workset,
    _run_rounds,
    div_rn,
    select_bucket,
)
from repro.graphstore.structs import DeviceGraph, compact_slots, remove_edges

__all__ = [
    "shard_graph",
    "sharded_peel_weights",
    "sharded_bulk_peel",
    "sharded_bulk_peel_warm",
    "sharded_bulk_peel_warm_workset",
    "init_sharded_state",
    "sharded_insert_and_maintain",
    "sharded_insert_and_maintain_auto",
    "sharded_insert_and_maintain_predictive",
    "sharded_delete_and_maintain",
    "sharded_slide_and_maintain",
    "sharded_slide_and_maintain_auto",
    "sharded_slide_and_maintain_predictive",
    "sharded_full_refresh",
]

_INF = jnp.float32(jnp.inf)


def _check_divisible(g: DeviceGraph, mesh: Mesh, axis: str) -> int:
    n_shards = mesh.shape[axis]
    if g.e_capacity % n_shards:
        raise ValueError(
            f"e_capacity={g.e_capacity} not divisible by mesh axis "
            f"{axis!r} ({n_shards} shards); use shard_graph() to pad+place"
        )
    return n_shards


def shard_graph(g: DeviceGraph, mesh: Mesh, axis: str = "data") -> DeviceGraph:
    """Pad ``e_capacity`` to a multiple of the shard count and place the
    graph: edge buffers block-sharded along ``axis``, vertex buffers
    replicated.  Padding slots are the standard inert self-loops
    (``src = dst = n_capacity - 1``, ``c = 0``, mask False) appended at
    the tail, after the free region the edge counter grows into."""
    n_shards = mesh.shape[axis]
    e_pad = -(-g.e_capacity // n_shards) * n_shards
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    c, em = np.asarray(g.c), np.asarray(g.edge_mask)
    if e_pad != g.e_capacity:
        extra = e_pad - g.e_capacity
        pad_idx = np.full(extra, g.n_capacity - 1, np.int32)
        src = np.concatenate([src, pad_idx])
        dst = np.concatenate([dst, pad_idx])
        c = np.concatenate([c, np.zeros(extra, np.float32)])
        em = np.concatenate([em, np.zeros(extra, bool)])
    esh = NamedSharding(mesh, P(axis))
    vsh = NamedSharding(mesh, P())
    # vertex arrays round-trip through host: device_put of a live device
    # array can alias its buffer into the replicated copy, which a later
    # donation of the source graph would silently delete
    return DeviceGraph(
        src=jax.device_put(jnp.asarray(src), esh),
        dst=jax.device_put(jnp.asarray(dst), esh),
        c=jax.device_put(jnp.asarray(c), esh),
        edge_mask=jax.device_put(jnp.asarray(em), esh),
        a=jax.device_put(jnp.asarray(np.asarray(g.a)), vsh),
        vertex_mask=jax.device_put(jnp.asarray(np.asarray(g.vertex_mask)), vsh),
        n_capacity=g.n_capacity,
        e_capacity=e_pad,
    )


# ---------------------------------------------------------------------------
# sharded peel rounds (runs inside shard_map; one fused psum per round)
# ---------------------------------------------------------------------------


class _ShardState(NamedTuple):
    w: jax.Array  # [V] replicated
    active: jax.Array  # [V] replicated
    edge_alive: jax.Array  # [E/n_shards] LOCAL
    f: jax.Array
    n_act: jax.Array
    level: jax.Array
    best_g: jax.Array
    best_level: jax.Array
    round_: jax.Array


def _shard_round(axis, eps, src, dst, c, a, s: _ShardState) -> _ShardState:
    """One psum-reduced bulk round over per-shard COO arrays — the dist
    twin of :func:`repro.core.peel._round_step`, shared by the full-buffer
    and workset shard peels so the two cannot drift.  Vertex-shaped state
    (``s.w`` etc.) is replicated; edge arrays are a shard's local block
    (full buffers or a gathered workset alike)."""
    V = s.w.shape[0]
    g_cur = div_rn(s.f, jnp.maximum(s.n_act, 1))
    improved = (g_cur > s.best_g) & (s.n_act > 0)
    best_g = jnp.where(improved, g_cur, s.best_g)
    best_level = jnp.where(improved, s.round_, s.best_level)
    thresh = 2.0 * (1.0 + eps) * g_cur
    peel = s.active & (s.w <= thresh)
    # f32-drift progress fallback, mirroring core.peel._round_step
    # (w is replicated, so every shard picks the same vertices)
    wmin = jnp.min(jnp.where(s.active, s.w, _INF))
    peel = jnp.where(jnp.any(peel), peel, s.active & (s.w <= wmin))
    e_ps = peel[src]
    e_pd = peel[dst]
    cm = jnp.where(s.edge_alive, c, 0.0)
    dw_l = jax.ops.segment_sum(
        jnp.where(e_ps & ~e_pd, cm, 0.0), dst, num_segments=V
    ) + jax.ops.segment_sum(
        jnp.where(e_pd & ~e_ps, cm, 0.0), src, num_segments=V
    )
    edge_alive = s.edge_alive & ~(e_ps | e_pd)
    mass_l = jnp.sum(jnp.where(edge_alive, c, 0.0))
    dw, e_mass = jax.lax.psum((dw_l, mass_l), axis)
    active = s.active & ~peel
    # f afresh from the survivors, as core.peel._set_mass does
    return _ShardState(
        w=s.w - dw,
        active=active,
        edge_alive=edge_alive,
        f=jnp.sum(jnp.where(active, a, 0.0)) + e_mass,
        n_act=s.n_act - jnp.sum(peel),
        level=jnp.where(peel, s.round_, s.level),
        best_g=best_g,
        best_level=best_level,
        round_=s.round_ + 1,
    )


def _local_peel_fn(axis: str, V: int, eps: float, max_rounds: int, warm: bool):
    """Build the per-shard peel body.  ``warm`` restricts to the ``keep``
    suffix exactly like :func:`repro.core.peel.bulk_peel_warm`; cold start
    mirrors ``bulk_peel`` (same init, best tracker seeded by prior_g)."""

    def fn(src, dst, c, emask, a, vmask, keep, prior_g):
        if warm:
            live = keep & vmask
            alive0 = live[src] & live[dst] & emask
            w_base = jnp.where(live, a, 0.0)
        else:
            live = vmask
            alive0 = emask
            w_base = jnp.where(vmask, a, 0.0)
        cm0 = jnp.where(alive0, c, 0.0)
        inc = jax.ops.segment_sum(cm0, src, num_segments=V) + jax.ops.segment_sum(
            cm0, dst, num_segments=V
        )
        inc, e_sum = jax.lax.psum((inc, jnp.sum(cm0)), axis)
        init = _ShardState(
            w=w_base + inc,
            active=live,
            edge_alive=alive0,
            f=jnp.sum(w_base) + e_sum,
            n_act=jnp.sum(live),
            level=jnp.full(V, -1, jnp.int32),
            best_g=prior_g.astype(jnp.float32),
            best_level=jnp.int32(0),
            round_=jnp.int32(0),
        )
        s = _run_rounds(
            partial(_shard_round, axis, eps, src, dst, c, a), init, max_rounds
        )
        return s.level, s.best_level, s.best_g, s.round_, s.w

    return fn


def _sharded_peel(
    g: DeviceGraph,
    keep: jax.Array,
    prior_g: jax.Array,
    mesh: Mesh,
    axis: str,
    eps: float,
    max_rounds: int,
    warm: bool,
) -> PeelResultDevice:
    _check_divisible(g, mesh, axis)
    es, rs = P(axis), P()
    fn = _local_peel_fn(axis, g.n_capacity, eps, max_rounds, warm)
    level, best_level, best_g, n_rounds, w = shard_map(
        fn,
        mesh=mesh,
        in_specs=(es, es, es, es, rs, rs, rs, rs),
        out_specs=(rs,) * 5,
        check_vma=False,
    )(g.src, g.dst, g.c, g.edge_mask, g.a, g.vertex_mask, keep, prior_g)
    return PeelResultDevice(
        level=level,
        best_level=best_level,
        best_g=best_g,
        n_rounds=n_rounds,
        order=jnp.zeros(g.n_capacity, jnp.int32),
        delta=w,
    )


@partial(jax.jit, static_argnames=("mesh", "axis", "eps", "max_rounds"))
def sharded_bulk_peel(
    g: DeviceGraph,
    mesh: Mesh,
    axis: str = "data",
    eps: float = 0.1,
    max_rounds: int = 0,
) -> PeelResultDevice:
    """Edge-sharded twin of :func:`repro.core.peel.bulk_peel`."""
    return _sharded_peel(
        g, g.vertex_mask, -_INF, mesh, axis, eps, max_rounds, warm=False
    )


@partial(jax.jit, static_argnames=("mesh", "axis", "eps", "max_rounds"))
def sharded_bulk_peel_warm(
    g: DeviceGraph,
    keep: jax.Array,
    prior_best_g: jax.Array,
    mesh: Mesh,
    axis: str = "data",
    eps: float = 0.1,
    max_rounds: int = 0,
) -> PeelResultDevice:
    """Edge-sharded twin of :func:`repro.core.peel.bulk_peel_warm`."""
    return _sharded_peel(
        g, keep, prior_best_g, mesh, axis, eps, max_rounds, warm=True
    )


# ---------------------------------------------------------------------------
# sharded workset peel (DESIGN.md §8): each shard gathers the affected
# suffix's LOCAL live edges into a bucket-sized buffer; vertex compaction
# is replicated math, so every shard agrees on the local id map and the
# round sequence, and one psum per round reduces the workset deltas.
# ---------------------------------------------------------------------------


def _local_workset_peel_fn(
    axis: str, V: int, eps: float, max_rounds: int, v_bucket: int, e_bucket: int
):
    def fn(src, dst, c, emask, a, vmask, keep, prior_g):
        # the gather is core.peel._compact_workset verbatim on this shard's
        # local edge block; vertex compaction is replicated math, so every
        # shard computes the identical vid/local-id map and the round
        # sequence cannot diverge
        live = keep & vmask
        ws = _compact_workset(src, dst, c, emask, a, live, v_bucket, e_bucket)

        cm0 = jnp.where(ws.alive, ws.c, 0.0)
        inc = jax.ops.segment_sum(cm0, ws.src, num_segments=v_bucket) + (
            jax.ops.segment_sum(cm0, ws.dst, num_segments=v_bucket)
        )
        inc, e_sum = jax.lax.psum((inc, jnp.sum(cm0)), axis)
        init = _ShardState(
            w=ws.a + inc,
            active=ws.active,
            edge_alive=ws.alive,
            f=jnp.sum(ws.a) + e_sum,
            n_act=jnp.sum(ws.active),
            level=jnp.full(v_bucket, -1, jnp.int32),
            best_g=prior_g.astype(jnp.float32),
            best_level=jnp.int32(0),
            round_=jnp.int32(0),
        )
        s = _run_rounds(
            partial(_shard_round, axis, eps, ws.src, ws.dst, ws.c, ws.a),
            init, max_rounds,
        )
        # scatter the workset level back to full width (replicated output)
        level = jnp.full(V, -1, jnp.int32).at[ws.vid].set(s.level, mode="drop")
        w_full = jnp.zeros(V, jnp.float32).at[ws.vid].set(s.w, mode="drop")
        return level, s.best_level, s.best_g, s.round_, w_full

    return fn


@partial(
    jax.jit,
    static_argnames=("mesh", "axis", "eps", "max_rounds", "v_bucket", "e_bucket"),
)
def sharded_bulk_peel_warm_workset(
    g: DeviceGraph,
    keep: jax.Array,
    prior_best_g: jax.Array,
    mesh: Mesh,
    axis: str = "data",
    eps: float = 0.1,
    max_rounds: int = 0,
    *,
    v_bucket: int,
    e_bucket: int,
) -> PeelResultDevice:
    """Edge-sharded twin of :func:`repro.core.peel.bulk_peel_warm_workset`.

    ``e_bucket`` bounds the *per-shard* workset (callers size it from the
    max local suffix-edge count, :func:`sharded_workset_sizes`).  Matches
    the single-device workset and the full-buffer warm peel bit-exactly on
    integer weights: all per-vertex/per-set quantities are the same
    integer sums, only the reduction is distributed.
    """
    _check_divisible(g, mesh, axis)
    es, rs = P(axis), P()
    fn = _local_workset_peel_fn(axis, g.n_capacity, eps, max_rounds,
                                v_bucket, e_bucket)
    level, best_level, best_g, n_rounds, w = shard_map(
        fn,
        mesh=mesh,
        in_specs=(es, es, es, es, rs, rs, rs, rs),
        out_specs=(rs,) * 5,
        check_vma=False,
    )(g.src, g.dst, g.c, g.edge_mask, g.a, g.vertex_mask, keep, prior_best_g)
    return PeelResultDevice(
        level=level,
        best_level=best_level,
        best_g=best_g,
        n_rounds=n_rounds,
        order=jnp.zeros(g.n_capacity, jnp.int32),
        delta=w,
    )


@partial(jax.jit, static_argnames=("mesh", "axis"))
def sharded_workset_sizes(
    g: DeviceGraph, keep: jax.Array, mesh: Mesh, axis: str = "data"
) -> tuple[jax.Array, jax.Array]:
    """(live suffix vertices, MAX per-shard suffix-induced live edges) —
    the bucket-selection counts for the sharded workset path."""
    _check_divisible(g, mesh, axis)

    def fn(src, dst, c, emask, vmask, keep):
        live = keep & vmask
        both = live[src] & live[dst] & emask
        ne = jax.lax.pmax(jnp.sum(both).astype(jnp.int32), axis)
        return jnp.sum(live).astype(jnp.int32), ne

    es, rs = P(axis), P()
    return shard_map(
        fn, mesh=mesh, in_specs=(es, es, es, es, rs, rs), out_specs=(rs, rs),
        check_vma=False,
    )(g.src, g.dst, g.c, g.edge_mask, g.vertex_mask, keep)


@partial(jax.jit, static_argnames=("mesh", "axis"))
def sharded_peel_weights(g: DeviceGraph, mesh: Mesh, axis: str = "data") -> jax.Array:
    """Edge-sharded ``DeviceGraph.peel_weights`` (one psum)."""
    _check_divisible(g, mesh, axis)
    V = g.n_capacity

    def fn(src, dst, c, emask, a, vmask):
        cm = jnp.where(emask, c, 0.0)
        inc = jax.ops.segment_sum(cm, src, num_segments=V) + jax.ops.segment_sum(
            cm, dst, num_segments=V
        )
        return jnp.where(vmask, a, 0.0) + jax.lax.psum(inc, axis)

    es, rs = P(axis), P()
    return shard_map(
        fn, mesh=mesh, in_specs=(es, es, es, es, rs, rs), out_specs=rs,
        check_vma=False,
    )(g.src, g.dst, g.c, g.edge_mask, g.a, g.vertex_mask)


# ---------------------------------------------------------------------------
# sharded streaming maintenance
# ---------------------------------------------------------------------------


def _sharded_append(
    g: DeviceGraph,
    offset: jax.Array,
    src: jax.Array,
    dst: jax.Array,
    c: jax.Array,
    valid: jax.Array,
    mesh: Mesh,
    axis: str,
) -> DeviceGraph:
    """Sharded scatter-append: the batch is replicated; each shard writes
    the entries whose global compacted slot falls in its block."""
    n_shards = mesh.shape[axis]
    e_local = g.e_capacity // n_shards

    def append_local(ls, ld, lc, lm, bs, bd, bc, valid_b, off):
        lo = jax.lax.axis_index(axis).astype(jnp.int32) * e_local
        idx, ok = compact_slots(off, valid_b, g.e_capacity)
        li = idx - lo
        li = jnp.where(ok & (li >= 0) & (li < e_local), li, e_local)
        return (
            ls.at[li].set(bs.astype(jnp.int32), mode="drop"),
            ld.at[li].set(bd.astype(jnp.int32), mode="drop"),
            lc.at[li].set(bc.astype(jnp.float32), mode="drop"),
            lm.at[li].set(True, mode="drop"),
        )

    es, rs = P(axis), P()
    nsrc, ndst, nc, nmask = shard_map(
        append_local,
        mesh=mesh,
        in_specs=(es, es, es, es, rs, rs, rs, rs, rs),
        out_specs=(es,) * 4,
        check_vma=False,
    )(g.src, g.dst, g.c, g.edge_mask, src, dst, c, valid, offset)
    return dataclasses.replace(g, src=nsrc, dst=ndst, c=nc, edge_mask=nmask)


def _sharded_remove(
    g: DeviceGraph, drop: jax.Array, mesh: Mesh, axis: str
) -> tuple[DeviceGraph, jax.Array]:
    """``remove_edges`` over sharded buffers: the compaction scatter runs
    as plain jnp ops (GSPMD inserts the collectives) and the compacted
    buffers are constrained back onto ``axis``."""
    g, n_removed = remove_edges(g, drop)
    esh = NamedSharding(mesh, P(axis))
    return (
        dataclasses.replace(
            g,
            src=jax.lax.with_sharding_constraint(g.src, esh),
            dst=jax.lax.with_sharding_constraint(g.dst, esh),
            c=jax.lax.with_sharding_constraint(g.c, esh),
            edge_mask=jax.lax.with_sharding_constraint(g.edge_mask, esh),
        ),
        n_removed,
    )


def init_sharded_state(
    g: DeviceGraph, mesh: Mesh, axis: str = "data", eps: float = 0.1
) -> DeviceSpadeState:
    """Sharded twin of :func:`repro.core.incremental.init_state`; ``g``
    should come from :func:`shard_graph`."""
    res = sharded_bulk_peel(g, mesh, axis=axis, eps=eps)
    w0 = sharded_peel_weights(g, mesh, axis=axis)
    return DeviceSpadeState(
        graph=g,
        level=res.level,
        best_g=res.best_g,
        community=res.community_mask() & g.vertex_mask,
        edge_count=jnp.sum(g.edge_mask).astype(jnp.int32),
        w0=w0,
        w0_exact=_exact_weights(g, w0),
    )


@partial(
    jax.jit,
    static_argnames=("mesh", "axis", "eps", "max_rounds"),
    donate_argnames=("state",),
)
def sharded_insert_and_maintain(
    state: DeviceSpadeState,
    src: jax.Array,
    dst: jax.Array,
    c: jax.Array,
    valid: jax.Array,
    mesh: Mesh,
    axis: str = "data",
    eps: float = 0.1,
    max_rounds: int = 0,
) -> DeviceSpadeState:
    """Edge-sharded twin of :func:`repro.core.incremental.insert_and_maintain`.

    One fused device program: sharded append (each shard writes the batch
    entries whose global slot falls in its block) -> affected-suffix
    recovery (replicated) -> sharded warm bulk re-peel -> state merge.
    The bookkeeping is the single-device ``_slide_prologue`` /
    ``_slide_epilogue`` with the insert-only static path (no drop mask),
    exactly as in the core engine, so the two planes cannot drift.
    """
    _check_divisible(state.graph, mesh, axis)
    bk = _slide_prologue(state, None, src, dst, valid)
    g = _sharded_append(state.graph, state.edge_count, src, dst, c, valid,
                        mesh, axis)
    res = _sharded_peel(
        g, bk.keep, bk.prior_g, mesh, axis, eps, max_rounds, warm=True
    )
    return _slide_epilogue(
        state, g, res, bk, jnp.int32(0),
        *_next_w0(state, bk, src, dst, c, valid, with_drops=False))


def sharded_delete_and_maintain(
    state: DeviceSpadeState,
    drop: jax.Array,
    mesh: Mesh,
    axis: str = "data",
    eps: float = 0.1,
    max_rounds: int = 0,
) -> DeviceSpadeState:
    """Edge-sharded twin of :func:`repro.core.incremental.delete_and_maintain`
    — exactly a sharded window slide with an empty insert batch."""
    z = jnp.zeros(1, jnp.int32)
    return sharded_slide_and_maintain(
        state, drop, z, z, z.astype(jnp.float32), jnp.zeros(1, bool),
        mesh=mesh, axis=axis, eps=eps, max_rounds=max_rounds,
    )


@partial(
    jax.jit,
    static_argnames=("mesh", "axis", "eps", "max_rounds"),
    donate_argnames=("state",),
)
def sharded_slide_and_maintain(
    state: DeviceSpadeState,
    drop: jax.Array,
    src: jax.Array,
    dst: jax.Array,
    c: jax.Array,
    valid: jax.Array,
    mesh: Mesh,
    axis: str = "data",
    eps: float = 0.1,
    max_rounds: int = 0,
) -> DeviceSpadeState:
    """Edge-sharded twin of :func:`repro.core.incremental.slide_and_maintain`:
    one fused window tick — sharded compaction, sharded append, a single
    psum-reduced warm re-peel.  The suffix/density bookkeeping is the
    single-device ``_slide_prologue`` / ``_slide_epilogue`` verbatim
    (replicated math; GSPMD inserts the collectives), so the two engines
    cannot drift and the result matches the single-device path exactly on
    integer-valued suspiciousness."""
    _check_divisible(state.graph, mesh, axis)
    bk = _slide_prologue(state, drop, src, dst, valid)
    g, n_removed = _sharded_remove(state.graph, drop, mesh, axis)
    g = _sharded_append(
        g, state.edge_count - n_removed, src, dst, c, valid, mesh, axis
    )
    res = _sharded_peel(
        g, bk.keep, bk.prior_g, mesh, axis, eps, max_rounds, warm=True
    )
    return _slide_epilogue(state, g, res, bk, n_removed,
                           *_next_w0(state, bk, src, dst, c, valid))


# ---------------------------------------------------------------------------
# sharded workset dispatch (DESIGN.md §8): phase A applies the structural
# update and counts the affected suffix; the host syncs the two scalars,
# picks buckets, and dispatches phase B (per-shard workset re-peel, or the
# full-buffer sharded warm peel on fallback).
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("mesh", "axis"))
def _sharded_insert_phase_a(state, src, dst, c, valid, mesh, axis):
    bk = _slide_prologue(state, None, src, dst, valid)
    g = _sharded_append(state.graph, state.edge_count, src, dst, c, valid,
                        mesh, axis)
    nv, ne = sharded_workset_sizes(g, bk.keep, mesh, axis=axis)
    return g, bk, jnp.int32(0), nv, ne


@partial(jax.jit, static_argnames=("mesh", "axis"))
def _sharded_slide_phase_a(state, drop, src, dst, c, valid, mesh, axis):
    bk = _slide_prologue(state, drop, src, dst, valid)
    g, n_removed = _sharded_remove(state.graph, drop, mesh, axis)
    g = _sharded_append(g, state.edge_count - n_removed, src, dst, c, valid,
                        mesh, axis)
    nv, ne = sharded_workset_sizes(g, bk.keep, mesh, axis=axis)
    return g, bk, n_removed, nv, ne


@partial(
    jax.jit,
    static_argnames=("mesh", "axis", "eps", "max_rounds", "v_bucket",
                     "e_bucket", "with_drops", "d_bucket"),
    donate_argnames=("state", "g"),
)
def _sharded_phase_b(
    state, g, bk, n_removed, src, dst, c, valid,
    mesh, axis,
    eps: float = 0.1,
    max_rounds: int = 0,
    v_bucket: int = 0,
    e_bucket: int = 0,
    with_drops: bool = True,
    d_bucket: int = 0,
):
    if v_bucket and e_bucket:
        res = sharded_bulk_peel_warm_workset(
            g, bk.keep, bk.prior_g, mesh, axis=axis, eps=eps,
            max_rounds=max_rounds, v_bucket=v_bucket, e_bucket=e_bucket,
        )
    else:
        res = _sharded_peel(
            g, bk.keep, bk.prior_g, mesh, axis, eps, max_rounds, warm=True
        )
    return _slide_epilogue(
        state, g, res, bk, n_removed,
        *_next_w0(state, bk, src, dst, c, valid, with_drops=with_drops,
                 d_bucket=d_bucket))


def _sharded_dispatch_phase_b(
    state, g, bk, n_removed, src, dst, c, valid,
    nv, ne, mesh, axis, eps, max_rounds, min_bucket, with_drops=True,
) -> tuple[DeviceSpadeState, WorksetTickInfo]:
    n_cap = state.graph.n_capacity
    e_local = state.graph.e_capacity // mesh.shape[axis]
    # the tick's only device->host sync: three scalars, one transfer
    nv_i, ne_i, nd_i = (int(x) for x in np.asarray(
        jnp.stack([nv, ne, n_removed])
    ))
    bv = select_bucket(nv_i, n_cap, floor=min_bucket)
    be = select_bucket(ne_i, e_local, floor=min_bucket)
    if bv is None or be is None:
        bv = be = 0
    # statically skip the w0 decrement when nothing was actually dropped,
    # and compact it through a bucket otherwise (single-device engine ditto)
    with_drops = with_drops and nd_i > 0
    bd = 0
    if with_drops:
        bd = select_bucket(nd_i, state.graph.e_capacity,
                           floor=min_bucket) or 0
    new_state = _sharded_phase_b(
        state, g, bk, n_removed, src, dst, c, valid, mesh, axis,
        eps=eps, max_rounds=max_rounds, v_bucket=bv, e_bucket=be,
        with_drops=with_drops, d_bucket=bd,
    )
    return new_state, WorksetTickInfo(nv_i, ne_i, bv, be, not (bv and be))


def sharded_insert_and_maintain_auto(
    state: DeviceSpadeState,
    src: jax.Array,
    dst: jax.Array,
    c: jax.Array,
    valid: jax.Array,
    mesh: Mesh,
    axis: str = "data",
    eps: float = 0.1,
    max_rounds: int = 0,
    min_bucket: int = 64,
) -> tuple[DeviceSpadeState, WorksetTickInfo]:
    """Edge-sharded twin of
    :func:`repro.core.incremental.insert_and_maintain_auto`."""
    g, bk, n_removed, nv, ne = _sharded_insert_phase_a(
        state, src, dst, c, valid, mesh, axis
    )
    return _sharded_dispatch_phase_b(
        state, g, bk, n_removed, src, dst, c, valid, nv, ne, mesh, axis,
        eps, max_rounds, min_bucket, with_drops=False,
    )


def sharded_slide_and_maintain_auto(
    state: DeviceSpadeState,
    drop: jax.Array,
    src: jax.Array,
    dst: jax.Array,
    c: jax.Array,
    valid: jax.Array,
    mesh: Mesh,
    axis: str = "data",
    eps: float = 0.1,
    max_rounds: int = 0,
    min_bucket: int = 64,
) -> tuple[DeviceSpadeState, WorksetTickInfo]:
    """Edge-sharded twin of
    :func:`repro.core.incremental.slide_and_maintain_auto`."""
    g, bk, n_removed, nv, ne = _sharded_slide_phase_a(
        state, drop, src, dst, c, valid, mesh, axis
    )
    return _sharded_dispatch_phase_b(
        state, g, bk, n_removed, src, dst, c, valid, nv, ne, mesh, axis,
        eps, max_rounds, min_bucket,
    )


# ---------------------------------------------------------------------------
# sharded predictive dispatch: the core engine's BucketPredictor drives the
# mesh path too — buckets from the previous tick's (pmax'd per-shard)
# counts, fit-checked on device, counts drained after dispatch
# ---------------------------------------------------------------------------


@partial(
    jax.jit,
    static_argnames=("mesh", "axis", "eps", "max_rounds", "v_bucket",
                     "e_bucket", "with_drops", "d_bucket"),
    donate_argnames=("state", "g"),
)
def _sharded_phase_b_checked(
    state, g, bk, n_removed, nv, ne, src, dst, c, valid,
    mesh, axis,
    eps: float = 0.1,
    max_rounds: int = 0,
    v_bucket: int = 0,
    e_bucket: int = 0,
    with_drops: bool = True,
    d_bucket: int = 0,
):
    """Sharded twin of :func:`repro.core.incremental._phase_b_checked`:
    ``lax.cond`` between the per-shard workset peel and the full-buffer
    sharded warm peel, driven by the replicated count scalars."""
    fits = (nv <= jnp.int32(v_bucket)) & (ne <= jnp.int32(e_bucket))
    res = jax.lax.cond(
        fits,
        lambda: sharded_bulk_peel_warm_workset(
            g, bk.keep, bk.prior_g, mesh, axis=axis, eps=eps,
            max_rounds=max_rounds, v_bucket=v_bucket, e_bucket=e_bucket,
        ),
        lambda: _sharded_peel(
            g, bk.keep, bk.prior_g, mesh, axis, eps, max_rounds, warm=True
        ),
    )
    return _slide_epilogue(
        state, g, res, bk, n_removed,
        *_next_w0(state, bk, src, dst, c, valid, with_drops=with_drops,
                 d_bucket=d_bucket)), fits


def _sharded_predictive_dispatch(
    state, g, bk, n_removed, src, dst, c, valid, nv, ne,
    predictor: BucketPredictor, mesh, axis, eps, max_rounds,
    with_drops=True, n_dropped=None,
) -> tuple[DeviceSpadeState, WorksetTickInfo]:
    """Sharded binding of the shared predictor-driven dispatcher
    (:func:`repro.core.incremental._predictive_dispatch_core`): only the
    three phase-B callables differ from the single-device engine."""
    return _predictive_dispatch_core(
        state, nv, ne, predictor, with_drops, n_dropped,
        synced=lambda wd: _sharded_dispatch_phase_b(
            state, g, bk, n_removed, src, dst, c, valid, nv, ne, mesh, axis,
            eps, max_rounds, predictor.min_bucket, with_drops=wd,
        ),
        checked=lambda bv, be, wd, bd: _sharded_phase_b_checked(
            state, g, bk, n_removed, nv, ne, src, dst, c, valid, mesh, axis,
            eps=eps, max_rounds=max_rounds, v_bucket=bv, e_bucket=be,
            with_drops=wd, d_bucket=bd,
        ),
        full=lambda wd, bd: _sharded_phase_b(
            state, g, bk, n_removed, src, dst, c, valid, mesh, axis,
            eps=eps, max_rounds=max_rounds, v_bucket=0, e_bucket=0,
            with_drops=wd, d_bucket=bd,
        ),
    )


def sharded_insert_and_maintain_predictive(
    state: DeviceSpadeState,
    src: jax.Array,
    dst: jax.Array,
    c: jax.Array,
    valid: jax.Array,
    predictor: BucketPredictor,
    mesh: Mesh,
    axis: str = "data",
    eps: float = 0.1,
    max_rounds: int = 0,
) -> tuple[DeviceSpadeState, WorksetTickInfo]:
    """Edge-sharded twin of
    :func:`repro.core.incremental.insert_and_maintain_predictive`.
    ``predictor.e_capacity`` must be the per-shard local capacity."""
    g, bk, n_removed, nv, ne = _sharded_insert_phase_a(
        state, src, dst, c, valid, mesh, axis
    )
    return _sharded_predictive_dispatch(
        state, g, bk, n_removed, src, dst, c, valid, nv, ne, predictor,
        mesh, axis, eps, max_rounds, with_drops=False, n_dropped=0,
    )


def sharded_slide_and_maintain_predictive(
    state: DeviceSpadeState,
    drop: jax.Array,
    src: jax.Array,
    dst: jax.Array,
    c: jax.Array,
    valid: jax.Array,
    predictor: BucketPredictor,
    mesh: Mesh,
    axis: str = "data",
    n_dropped: int | None = None,
    eps: float = 0.1,
    max_rounds: int = 0,
) -> tuple[DeviceSpadeState, WorksetTickInfo]:
    """Edge-sharded twin of
    :func:`repro.core.incremental.slide_and_maintain_predictive`."""
    g, bk, n_removed, nv, ne = _sharded_slide_phase_a(
        state, drop, src, dst, c, valid, mesh, axis
    )
    return _sharded_predictive_dispatch(
        state, g, bk, n_removed, src, dst, c, valid, nv, ne, predictor,
        mesh, axis, eps, max_rounds, n_dropped=n_dropped,
    )


@partial(jax.jit, static_argnames=("mesh", "axis", "eps"))
def sharded_full_refresh(
    state: DeviceSpadeState, mesh: Mesh, axis: str = "data", eps: float = 0.1
) -> DeviceSpadeState:
    """Edge-sharded twin of :func:`repro.core.incremental.full_refresh`."""
    res = sharded_bulk_peel(state.graph, mesh, axis=axis, eps=eps)
    w0 = sharded_peel_weights(state.graph, mesh, axis=axis)
    return DeviceSpadeState(
        graph=state.graph,
        level=res.level,
        best_g=res.best_g,
        community=res.community_mask() & state.graph.vertex_mask,
        edge_count=state.edge_count,
        w0=w0,
        w0_exact=_exact_weights(state.graph, w0),
    )
