"""Device-plane (JAX/TPU) peeling algorithms.

Two formulations of the paper's Algorithm 1:

* :func:`exact_peel` — **paper-faithful sequential peel**: one vertex per
  step (masked argmin over a dense weight vector + scatter-subtract of its
  incident suspiciousness).  Bit-exact against the host oracle under the
  (weight, id) tie-break; O(V) steps of O(E) work.  This is the faithful
  baseline recorded in EXPERIMENTS.md §Perf.

* :func:`bulk_peel` — **TPU-native bulk peeling** (beyond-paper
  optimization; Bahmani et al., VLDB'12 — the paper's own reference [2]):
  each round peels *every* active vertex with
  ``w_u <= 2(1+eps) * g(S)``, converging in O(log_{1+eps} V) rounds of
  pure streaming segment-sums over the edge-partitioned COO graph.  It
  carries a ``2(1+eps)``-approximation guarantee and is the form that
  scales to multi-pod meshes: per-round work is two masked
  ``segment_sum`` passes (HBM-bandwidth-bound) + an ``all_reduce`` of
  vertex deltas when edges are sharded.

Both return a *peel level* per vertex (sequential: the step index;
bulk: the round index) from which the detected community is the suffix
``level >= best_level``.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.graphstore.structs import DeviceGraph
from repro.kernels.peel_round.ops import peel_round

__all__ = [
    "PeelResultDevice",
    "div_rn",
    "exact_peel",
    "bulk_peel",
    "bulk_peel_warm",
    "bulk_peel_warm_workset",
    "bulk_peel_warm_checked",
    "edge_ladder",
    "select_bucket",
    "workset_sizes",
]

_INF = jnp.float32(jnp.inf)


class PeelResultDevice(NamedTuple):
    """Result of a device peel.

    ``level[u]``: step/round at which u was peeled (int32; padding = -1).
    ``best_level``: community = vertices with ``level >= best_level``.
    ``best_g``: density of the detected community.
    ``n_rounds``: rounds (bulk) or steps (exact) executed.
    ``order``: exact peel only — the peeling sequence (vertex ids), else
      zeros. ``delta``: peel-time weights aligned with ``order``/vertex id.
    """

    level: jax.Array
    best_level: jax.Array
    best_g: jax.Array
    n_rounds: jax.Array
    order: jax.Array
    delta: jax.Array

    def community_mask(self) -> jax.Array:
        return self.level >= self.best_level


# ---------------------------------------------------------------------------
# exact sequential peel (paper-faithful)
# ---------------------------------------------------------------------------


def exact_peel(g: DeviceGraph) -> PeelResultDevice:
    """Algorithm 1, one vertex per step, deterministic (w, id) tie-break."""
    V, E = g.n_capacity, g.e_capacity
    cm = jnp.where(g.edge_mask, g.c, 0.0)
    w0 = g.peel_weights()
    f0 = g.f_total()
    n0 = jnp.sum(g.vertex_mask)

    def body(i, carry):
        w, active, f, n_act, order, delta, level, best_g, best_i = carry
        key = jnp.where(active, w, _INF)
        u = jnp.argmin(key)  # ties -> lowest id (matches host oracle)
        wu = key[u]
        # density of the set *before* this peel
        g_cur = jnp.where(n_act > 0, div_rn(f, jnp.maximum(n_act, 1)), -_INF)
        improved = g_cur > best_g
        best_g = jnp.where(improved, g_cur, best_g)
        best_i = jnp.where(improved, i, best_i)

        live = jnp.where(active, 1.0, 0.0)
        touch_s = (g.src == u) & g.edge_mask
        touch_d = (g.dst == u) & g.edge_mask
        dw = jax.ops.segment_sum(
            jnp.where(touch_s, cm, 0.0) * live[g.dst], g.dst, num_segments=V
        ) + jax.ops.segment_sum(
            jnp.where(touch_d, cm, 0.0) * live[g.src], g.src, num_segments=V
        )
        peel_now = n_act > 0
        w = jnp.where(peel_now, w - dw, w)
        active = active & ~((jnp.arange(V) == u) & peel_now)
        order = order.at[i].set(jnp.where(peel_now, u, -1))
        delta = delta.at[i].set(jnp.where(peel_now, wu, 0.0))
        level = level.at[u].set(jnp.where(peel_now, i, level[u]))
        f = jnp.where(peel_now, f - wu, f)
        n_act = n_act - jnp.where(peel_now, 1, 0)
        return (w, active, f, n_act, order, delta, level, best_g, best_i)

    init = (
        w0,
        g.vertex_mask,
        f0,
        n0,
        jnp.full(V, -1, jnp.int32),
        jnp.zeros(V, jnp.float32),
        jnp.full(V, -1, jnp.int32),
        -_INF,
        jnp.int32(0),
    )
    w, active, f, n_act, order, delta, level, best_g, best_i = jax.lax.fori_loop(
        0, V, body, init
    )
    return PeelResultDevice(
        level=level,
        best_level=best_i,
        best_g=best_g,
        n_rounds=n0,
        order=order,
        delta=delta,
    )


# ---------------------------------------------------------------------------
# bulk parallel peel (TPU-native; 2(1+eps)-approximation)
# ---------------------------------------------------------------------------


class _BulkState(NamedTuple):
    w: jax.Array
    active: jax.Array
    edge_alive: jax.Array
    f: jax.Array
    n_act: jax.Array
    level: jax.Array
    best_g: jax.Array
    best_level: jax.Array
    round_: jax.Array


def _split(x):
    """Veltkamp split of an f32 into two halves of at most 12 bits."""
    t = 4097.0 * x
    hi = t - (t - x)
    return hi, x - hi


def _two_prod(x, y):
    """(p, e) with p = fl(x * y) and p + e == x * y exactly (Dekker)."""
    p = x * y
    xh, xl = _split(x)
    yh, yl = _split(y)
    return p, ((xh * yh - p) + xh * yl + xl * yh) + xl * yl


def _two_sum(x, y):
    """(s, t) with s = fl(x + y) and s + t == x + y exactly (Knuth)."""
    s = x + y
    z = s - x
    return s, (x - (s - z)) + (y - z)


def _sign_diff3(d, h, e):
    """Exact sign of ``d - h - e``.  The three terms are multiples of one
    power of two and below 2^28 of it, so the rounding errors left after
    two TwoSums are small integers of that unit and one more add is
    exact, or cannot flip the sign."""
    s, t = _two_sum(d, -h)
    y, ye = _two_sum(s, -e)
    return jnp.sign(y + (t + ye))


def _div_rn_step(a, b, q):
    """Move ``q`` one ulp towards RN(a / b): compare ``a`` with the two
    midpoints ``(q +- ulp / 2) * b`` through an exact residual."""
    bits = jax.lax.bitcast_convert_type(q, jnp.int32)
    up = jax.lax.bitcast_convert_type(bits + 1, jnp.float32)
    dn = jax.lax.bitcast_convert_type(bits - 1, jnp.float32)
    p, e = _two_prod(q, b)
    d = a - p  # exact: p is within a factor 2 of a (Sterbenz)
    s_up = _sign_diff3(d, (up - q) * 0.5 * b, e)  # sign(a - m_up * b)
    s_dn = _sign_diff3(d, (dn - q) * 0.5 * b, e)  # sign(a - m_dn * b)
    even = (bits & 1) == 0
    q = jnp.where((s_up > 0) | ((s_up == 0) & ~even), up, q)
    return jnp.where((s_dn < 0) | ((s_dn == 0) & ~even), dn, q)


def div_rn(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a / b`` in f32, rounded to nearest-even on every backend.

    XLA:TPU divides through a reciprocal and can land one ulp off the
    IEEE quotient the CPU returns, so a density and every threshold built
    on it would differ between the chip and the CPU.  Here the hardware
    quotient is corrected with exact residuals (add and multiply only,
    which both backends round alike).  For finite normal ``a`` and
    ``b > 0``, as densities ``f / n`` are.
    """
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    m = jnp.abs(a)
    q = m / b
    q = _div_rn_step(m, b, _div_rn_step(m, b, q))
    return jnp.where(m == 0, a / b, jnp.where(a < 0, -q, q))


def _set_mass(active, a, edge_alive, c) -> jax.Array:
    """f(S) of the surviving set, summed afresh every round.

    Carrying f by subtraction (``f -= peeled mass``) starts from the whole
    graph's mass; above 2^24 (a Grab4-scale graph under DG) f32 rounds
    those steps in whatever order the reduction runs, and the error
    survives into the small dense set whose density is reported.  A fresh
    sum costs the same two masked reductions and is exact, in any order
    and on any number of shards, once the set's integer mass fits f32.
    """
    return jnp.sum(jnp.where(active, a, 0.0)) + jnp.sum(
        jnp.where(edge_alive, c, 0.0))


def _round_step(
    src: jax.Array,
    dst: jax.Array,
    c: jax.Array,
    a: jax.Array,
    eps: float,
    use_kernel: bool,
    s: _BulkState,
) -> _BulkState:
    """One bulk-peeling round over explicit COO arrays.

    Shared by the full-buffer round (``src/dst/c/a`` are the graph's
    capacity-padded buffers) and the workset round (the gathered affected
    suffix with locally relabeled endpoints) — one definition so the two
    engines cannot drift.

    (§Perf note: deriving edge liveness on the fly instead of carrying the
    [E] bool state was tried and REFUTED — two extra [E]-sized gathers +
    mask ops cost more HBM traffic than the stored array saves.)

    ``use_kernel`` routes the elementwise state update (threshold compare,
    weight subtract, active/level merge, peeled-mass partial sums) through
    the fused :func:`repro.kernels.peel_round.ops.peel_round` kernel
    (Pallas on TPU, pure-jnp reference elsewhere).  On integer weights the
    two paths are bit-identical; the flag exists so the kernel is exercised
    by the production round rather than staying interpret-only dead code.

    The round's work sits under three named scopes, which the device
    trace's ops carry in their ``op_name`` metadata: ``peel_gather`` (the
    [V]-by-[E] gathers of the peel mask and the edge-liveness update),
    ``peel_scatter`` (the two [E]-to-[V] segment sums, with the sorts XLA
    adds for them) and ``peel_update`` (threshold, vertex update, f(S)).
    """
    V = s.w.shape[0]
    with jax.named_scope("peel_update"):
        g_cur = div_rn(s.f, jnp.maximum(s.n_act, 1))
        improved = (g_cur > s.best_g) & (s.n_act > 0)
        best_g = jnp.where(improved, g_cur, s.best_g)
        best_level = jnp.where(improved, s.round_, s.best_level)
        thresh = 2.0 * (1.0 + eps) * g_cur
        peel = s.active & (s.w <= thresh)
        # progress guarantee: avg_u w_u <= 2 g(S), so the min-weight vertex
        # always peels *in exact arithmetic*.  Under f32 the running weights
        # (carried by subtraction) can drift above f on a nearly-drained
        # set, pushing the threshold below every remaining weight and
        # stalling the while_loop; force-peel the min-weight vertices then
        # (a no-op whenever the threshold test already fired, hence
        # invisible on integer weights).
        wmin = jnp.min(jnp.where(s.active, s.w, _INF))
        eff_thresh = jnp.where(jnp.any(peel), thresh, wmin)
        peel = jnp.where(jnp.any(peel), peel, s.active & (s.w <= wmin))
    with jax.named_scope("peel_gather"):
        e_ps = peel[src]
        e_pd = peel[dst]
        # every edge with >= 1 peeled endpoint leaves the restricted set
        edge_alive = s.edge_alive & ~(e_ps | e_pd)
    with jax.named_scope("peel_scatter"):
        cm = jnp.where(s.edge_alive, c, 0.0)
        # survivors lose suspiciousness of edges to peeled endpoints (the
        # round's SpMV: segment-sum form of the gather_segsum primitive)
        dw = jax.ops.segment_sum(
            jnp.where(e_ps & ~e_pd, cm, 0.0), dst, num_segments=V
        ) + jax.ops.segment_sum(
            jnp.where(e_pd & ~e_ps, cm, 0.0), src, num_segments=V)
    with jax.named_scope("peel_update"):
        if use_kernel:
            # fused elementwise half: recomputes the same peel mask from
            # eff_thresh and applies the state update in one VMEM pass
            w, active, level, _, partials = peel_round(
                s.w, a, s.active, s.level, dw, eff_thresh, s.round_
            )
            n_act = s.n_act - partials[2].astype(jnp.int32)
        else:
            w = s.w - dw
            active = s.active & ~peel
            level = jnp.where(peel, s.round_, s.level)
            n_act = s.n_act - jnp.sum(peel)
        f = _set_mass(active, a, edge_alive, c)
    return _BulkState(
        w=w,
        active=active,
        edge_alive=edge_alive,
        f=f,
        n_act=n_act,
        level=level,
        best_g=best_g,
        best_level=best_level,
        round_=s.round_ + 1,
    )


@partial(jax.jit, static_argnames=("eps", "max_rounds", "use_kernel"))
def bulk_peel(
    g: DeviceGraph,
    eps: float = 0.1,
    max_rounds: int = 0,
    use_kernel: bool = False,
) -> PeelResultDevice:
    """Threshold bulk peeling; guarantees ``g_best >= g* / (2(1+eps))``.

    ``max_rounds = 0`` runs to completion; a positive value bounds the
    round count (useful for fixed-cost serving ticks).  The rounds stream
    a buffer that shrinks with the live edges (:func:`_run_stages`).
    ``use_kernel`` routes the per-round elementwise update through the
    fused ``peel_round`` kernel (bit-identical on integer weights).
    """
    w0 = g.peel_weights()
    init = _BulkState(
        w=w0,
        active=g.vertex_mask,
        edge_alive=g.edge_mask,
        f=g.f_total(),
        n_act=jnp.sum(g.vertex_mask),
        level=jnp.full(g.n_capacity, -1, jnp.int32),
        best_g=-_INF,
        best_level=jnp.int32(0),
        round_=jnp.int32(0),
    )

    state, _ = _run_stages(g.src, g.dst, g.c, g.a, eps, use_kernel, init,
                           max_rounds)
    return PeelResultDevice(
        level=state.level,
        best_level=state.best_level,
        best_g=state.best_g,
        n_rounds=state.round_,
        order=jnp.zeros(g.n_capacity, jnp.int32),
        delta=state.w,
    )


def _run_rounds(round_fn, init, max_rounds: int):
    """Rounds over one fixed buffer: ``max_rounds`` of them, or until the
    set is empty (the sharded engine's; single-device peels use
    :func:`_run_stages`)."""
    if max_rounds and max_rounds > 0:
        return jax.lax.fori_loop(0, max_rounds, lambda i, s: round_fn(s), init)
    return jax.lax.while_loop(lambda s: s.n_act > 0, round_fn, init)


# The staged rounds.  A full-buffer round streams every edge slot, however
# few edges the restricted set still holds; on Grab4 the live edges fall
# about fourfold a round.  So the rounds step down a static ladder of
# buffer sizes, compacting the live edges into the next size once they fit.

_LADDER_FLOOR = 65536  # edge slots of the smallest stage


def edge_ladder(e_capacity: int) -> tuple[int, ...]:
    """The edge-buffer sizes the staged rounds step down through.

    Derived from the capacity alone, so every stage has a static shape:
    the capacity, then about a quarter of the size above, rounded up to a
    multiple of 512, down to a floor of 64K slots.  A buffer below four
    times the floor keeps one stage.
    """
    sizes = [int(e_capacity)]
    if e_capacity >= 4 * _LADDER_FLOOR:
        while sizes[-1] > _LADDER_FLOOR:
            quarter = -(-sizes[-1] // 4)
            sizes.append(max(-(-quarter // 512) * 512, _LADDER_FLOOR))
    return tuple(sizes)


def _compact_edges(src, dst, c, alive, size):
    """The live edges, in slot order, in a ``size``-slot buffer.

    The caller guarantees they fit.  One sort carries the edge arrays on
    a key that is a live edge's slot and the capacity for a dead one, so
    the live edges come first, in slot order.  (On a TPU v5e it beat a
    unique-index scatter and a gather after a key-only sort fourfold at
    32.5M -> 8.13M slots, PERF.md §3.)  Pad lanes get endpoint 0, ``c =
    0`` and ``alive = False``, as :class:`Workset` pads do.
    """
    E = alive.shape[0]
    key = jnp.where(alive, jnp.arange(E, dtype=jnp.int32), E)
    _, src, dst, c = jax.lax.sort((key, src, dst, c), num_keys=1)
    alive = jnp.arange(size, dtype=jnp.int32) < jnp.sum(alive,
                                                         dtype=jnp.int32)
    return (jnp.where(alive, src[:size], 0), jnp.where(alive, dst[:size], 0),
            jnp.where(alive, c[:size], 0.0), alive)


def _run_stages(src, dst, c, a, eps, use_kernel, init, max_rounds,
                counters=False):
    """Bulk rounds over an edge buffer that shrinks with the restricted set.

    Stage k runs :func:`_round_step` on a buffer of ``edge_ladder(E)[k]``
    slots while the set has an active vertex, a bounded peel has a round
    left, and the live edges exceed the next size down; then it compacts
    the live edges into the next stage's buffer (scope ``peel_compact``).
    Once the set is empty, or the rounds are spent, no further round or
    compaction runs.  The ``[V]`` arrays stay full width and unrelabelled,
    so each round computes what a full-buffer round would: on integer
    weights every sum is the same integer in any order, and the result is
    bit-identical.  A round on an empty set changes nothing but the round
    index, so a bounded peel still reports ``max_rounds`` rounds.

    Returns ``(state, counts)``.  With ``counters`` (a bounded peel only)
    ``counts`` is ``(round_vertices, round_edges, round_slots)``, int32
    ``[max_rounds]``: the set's active vertices and live edges at the
    start of each round, and the slots the round streamed; 0 where no
    round ran.  Otherwise ``counts`` is ``None``.
    """
    if counters and max_rounds <= 0:
        raise ValueError("round counters need a bounded peel (max_rounds > 0)")
    ladder = edge_ladder(src.shape[0])

    def more(s):
        go = s.n_act > 0
        return go & (s.round_ < max_rounds) if max_rounds else go

    zeros = jnp.zeros(max_rounds, jnp.int32)
    carry = (init, jnp.sum(init.edge_alive, dtype=jnp.int32),
             (zeros, zeros, zeros) if counters else ())
    for k, size in enumerate(ladder):
        nxt = ladder[k + 1] if k + 1 < len(ladder) else None
        edges = (src, dst, c)

        def cond(carry, nxt=nxt):
            s, n_live, _ = carry
            return more(s) if nxt is None else more(s) & (n_live > nxt)

        def body(carry, edges=edges, size=size):
            s, n_live, counts = carry
            if counters:
                i = s.round_
                rv, re, rs = counts
                counts = (rv.at[i].set(s.n_act.astype(jnp.int32)),
                          re.at[i].set(n_live), rs.at[i].set(size))
            s = _round_step(*edges, a, eps, use_kernel, s)
            return s, jnp.sum(s.edge_alive, dtype=jnp.int32), counts

        s, n_live, counts = jax.lax.while_loop(cond, body, carry)
        if nxt is not None:
            with jax.named_scope("peel_compact"):
                src, dst, c, alive = jax.lax.cond(
                    more(s),
                    partial(_compact_edges, src, dst, c, s.edge_alive, nxt),
                    lambda nxt=nxt: (jnp.zeros(nxt, jnp.int32),
                                     jnp.zeros(nxt, jnp.int32),
                                     jnp.zeros(nxt, jnp.float32),
                                     jnp.zeros(nxt, bool)))
            s = s._replace(edge_alive=alive)
        carry = (s, n_live, counts)
    if max_rounds:
        s = s._replace(round_=jnp.int32(max_rounds))
    return s, (counts if counters else None)


def bulk_peel_warm(
    g: DeviceGraph,
    keep: jax.Array,
    prior_best_g: jax.Array,
    eps: float = 0.1,
    max_rounds: int = 0,
    use_kernel: bool = False,
    counters: bool = False,
):
    """Bulk peel restricted to ``keep`` vertices (warm start).

    Used by the incremental suffix re-peel: vertices outside ``keep`` are
    treated as already peeled; weights, f and n are recovered w.r.t. the
    restricted set, so every round's threshold is valid on the current set
    and the 2(1+eps) guarantee is preserved (DESIGN.md §2).  ``prior_best_g``
    seeds the best-density tracker so the maintained best never regresses.

    This is the **full-buffer** warm path: it starts from the
    capacity-padded ``[E]`` buffer and compacts the live edges down a
    static size ladder as the set shrinks (:func:`_run_stages`); the
    ``[V]`` arrays stay full width.  The workset twin
    (:func:`bulk_peel_warm_workset`) gathers the suffix into compact
    bucketed buffers before the first round; this function is the
    fallback when the suffix exceeds the largest bucket (DESIGN.md §8).

    ``counters`` (a bounded peel only) returns ``(result, (round_vertices,
    round_edges, round_slots))``: see :func:`_run_stages`.
    """
    V = g.n_capacity
    with jax.named_scope("tick_seed"):
        live = keep & g.vertex_mask
        both = live[g.src] & live[g.dst] & g.edge_mask
        cm = jnp.where(both, g.c, 0.0)
        w0 = jnp.where(live, g.a, 0.0)
        w0 = w0 + jax.ops.segment_sum(cm, g.src, num_segments=V)
        w0 = w0 + jax.ops.segment_sum(cm, g.dst, num_segments=V)
        f0 = jnp.sum(jnp.where(live, g.a, 0.0)) + jnp.sum(cm)

    init = _BulkState(
        w=w0,
        active=live,
        edge_alive=both,
        f=f0,
        n_act=jnp.sum(live),
        level=jnp.full(V, -1, jnp.int32),
        best_g=prior_best_g.astype(jnp.float32),
        best_level=jnp.int32(0),
        round_=jnp.int32(0),
    )
    with jax.named_scope("tick_rounds"):
        state, counts = _run_stages(g.src, g.dst, g.c, g.a, eps, use_kernel,
                                    init, max_rounds, counters)
    res = PeelResultDevice(
        level=state.level,
        best_level=state.best_level,
        best_g=state.best_g,
        n_rounds=state.round_,
        order=jnp.zeros(V, jnp.int32),
        delta=state.w,
    )
    return (res, counts) if counters else res


# ---------------------------------------------------------------------------
# affected-area workset engine (the paper's §4 "affected area", materialized)
# ---------------------------------------------------------------------------
#
# A warm re-peel only ever touches the affected suffix ``keep``, yet the
# full-buffer round above streams all of ``[E]``/``[V]`` every round.  The
# workset engine gathers the suffix's live vertices and induced live edges
# into small fixed-capacity buffers once per tick, runs every round over
# those buffers only, and scatters ``level`` back — converting per-round
# work from O(E_capacity) to O(|affected suffix|).  Buffer sizes come from
# a power-of-two bucket ladder so the number of distinct jit compilations
# is O(log E), not O(E) (DESIGN.md §8).


def select_bucket(count: int, capacity: int, floor: int = 64) -> int | None:
    """Pick the power-of-two workset bucket for ``count`` elements.

    Returns the smallest power of two ``>= max(count, floor)``, or ``None``
    when ``count`` exceeds the largest bucket — the largest power of two
    ``<= max(capacity // 2, floor)``.  A workset larger than half the
    backing buffer cannot meaningfully beat streaming the buffer itself,
    so the caller falls through to the full-buffer warm path.  Host-side
    pure function: callers sync the (tiny) count scalar, pick the bucket,
    and dispatch the statically-shaped jitted variant.
    """
    if count < 0:
        raise ValueError(f"negative workset count {count}")
    largest = max(capacity // 2, floor)
    largest = 1 << (largest.bit_length() - 1)  # round DOWN to a power of two
    if count > largest:
        return None
    bucket = max(count, floor)
    return 1 << (bucket - 1).bit_length()  # round UP to a power of two


@partial(jax.jit)
def workset_sizes(g: DeviceGraph, keep: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(live suffix vertices, suffix-induced live edges) — the two counts
    bucket selection needs, as device scalars (one fused reduction pass)."""
    live = keep & g.vertex_mask
    both = live[g.src] & live[g.dst] & g.edge_mask
    return jnp.sum(live).astype(jnp.int32), jnp.sum(both).astype(jnp.int32)


class Workset(NamedTuple):
    """The gathered affected suffix (all leading dims are bucket-sized).

    ``vid[j]``: global id of local vertex ``j`` (= ``n_capacity`` on pad
    lanes, so scatter-back drops them).  Edge endpoints are local ids; pad
    edge lanes carry ``c = 0`` / ``alive = False`` and endpoint 0 (inert:
    zero suspiciousness contributes nothing to any segment).
    """

    vid: jax.Array  # int32 [Bv]
    a: jax.Array  # float32 [Bv]
    active: jax.Array  # bool [Bv]
    src: jax.Array  # int32 [Be] local
    dst: jax.Array  # int32 [Be] local
    c: jax.Array  # float32 [Be]
    alive: jax.Array  # bool [Be]


def _compact_workset(
    src: jax.Array,
    dst: jax.Array,
    c: jax.Array,
    emask: jax.Array,
    a: jax.Array,
    live: jax.Array,
    v_bucket: int,
    e_bucket: int,
) -> Workset:
    """Compact the affected suffix into bucket-sized buffers.

    The k-th live vertex (in id order) gets local id k — the same dense
    slot semantics as ``compact_slots``/``remove_edges``, so the local
    order is deterministic and shard-independent.  Like ``remove_edges``,
    the compaction is a **gather**: workset lane ``k`` locates the k-th
    live vertex/edge by binary search over a prefix sum — no [E]-sized
    scatter touches the tick's critical path.  Callers guarantee (via
    :func:`select_bucket`) that the counts fit the buckets.

    Takes raw COO arrays so the sharded engine can reuse it verbatim with
    a shard's *local* edge block (vertex arrays replicated): one
    definition of the gather for both planes.
    """
    V = a.shape[0]
    vsum = jnp.cumsum(live.astype(jnp.int32))  # [V]
    local = vsum - 1  # local id per live vertex
    nv = vsum[V - 1]
    vlane = jnp.arange(v_bucket, dtype=jnp.int32)
    vid = jnp.searchsorted(vsum, vlane + 1).astype(jnp.int32)
    active0 = vlane < nv
    vid = jnp.where(active0, vid, V)  # pad lanes dropped on scatter-back
    a_ws = a.at[vid].get(mode="fill", fill_value=0.0)

    both = live[src] & live[dst] & emask
    esum = jnp.cumsum(both.astype(jnp.int32))  # [E]
    ne = esum[src.shape[0] - 1]
    elane = jnp.arange(e_bucket, dtype=jnp.int32)
    eidx = jnp.searchsorted(esum, elane + 1).astype(jnp.int32)
    alive0 = elane < ne
    eidx = jnp.where(alive0, eidx, 0)  # clamp; pad lanes masked below
    # pad edge lanes: endpoint 0 with c = 0 is inert in every segment op
    lsrc = jnp.where(alive0, local[src[eidx]], 0)
    ldst = jnp.where(alive0, local[dst[eidx]], 0)
    c_ws = jnp.where(alive0, c[eidx], 0.0)
    return Workset(vid=vid, a=a_ws, active=active0, src=lsrc, dst=ldst,
                   c=c_ws, alive=alive0)


def _gather_workset(
    g: DeviceGraph, keep: jax.Array, v_bucket: int, e_bucket: int
) -> Workset:
    live = keep & g.vertex_mask
    return _compact_workset(g.src, g.dst, g.c, g.edge_mask, g.a, live,
                            v_bucket, e_bucket)


@partial(
    jax.jit,
    static_argnames=("eps", "max_rounds", "v_bucket", "e_bucket",
                     "use_kernel", "counters"),
)
def bulk_peel_warm_workset(
    g: DeviceGraph,
    keep: jax.Array,
    prior_best_g: jax.Array,
    eps: float = 0.1,
    max_rounds: int = 0,
    *,
    v_bucket: int,
    e_bucket: int,
    use_kernel: bool = False,
    counters: bool = False,
):
    """Workset twin of :func:`bulk_peel_warm`: gather → peel → scatter.

    Bit-identical to the full-buffer warm peel on integer weights: the
    workset holds exactly the suffix's live vertices and induced live
    edges, every per-vertex/per-set quantity is the same integer sum (f32
    sums of integers are exact in any order), and the round sequence is
    driven by those quantities only.  See DESIGN.md §8 for the correctness
    argument across the scatter-back.  The rounds are :func:`_run_stages`
    over the bucket, so they stop once the set is empty, and
    ``counters`` returns ``(result, counts)`` as :func:`bulk_peel_warm`
    does, with the bucket's ladder in ``round_slots``.
    """
    V = g.n_capacity
    ws = _gather_workset(g, keep, v_bucket, e_bucket)
    cm0 = jnp.where(ws.alive, ws.c, 0.0)
    w0 = ws.a + jax.ops.segment_sum(cm0, ws.src, num_segments=v_bucket)
    w0 = w0 + jax.ops.segment_sum(cm0, ws.dst, num_segments=v_bucket)
    f0 = jnp.sum(ws.a) + jnp.sum(cm0)

    init = _BulkState(
        w=w0,
        active=ws.active,
        edge_alive=ws.alive,
        f=f0,
        n_act=jnp.sum(ws.active),
        level=jnp.full(v_bucket, -1, jnp.int32),
        best_g=prior_best_g.astype(jnp.float32),
        best_level=jnp.int32(0),
        round_=jnp.int32(0),
    )
    state, counts = _run_stages(ws.src, ws.dst, ws.c, ws.a, eps, use_kernel,
                                init, max_rounds, counters)
    # scatter the suffix results back to full-width vertex arrays; pad
    # lanes carry vid = V and are dropped
    level = jnp.full(V, -1, jnp.int32).at[ws.vid].set(state.level, mode="drop")
    delta = jnp.zeros(V, jnp.float32).at[ws.vid].set(state.w, mode="drop")
    res = PeelResultDevice(
        level=level,
        best_level=state.best_level,
        best_g=state.best_g,
        n_rounds=state.round_,
        order=jnp.zeros(V, jnp.int32),
        delta=delta,
    )
    return (res, counts) if counters else res


@partial(
    jax.jit,
    static_argnames=("eps", "max_rounds", "v_bucket", "e_bucket", "use_kernel",
                     "counters"),
)
def bulk_peel_warm_checked(
    g: DeviceGraph,
    keep: jax.Array,
    prior_best_g: jax.Array,
    nv: jax.Array,
    ne: jax.Array,
    eps: float = 0.1,
    max_rounds: int = 0,
    *,
    v_bucket: int,
    e_bucket: int,
    use_kernel: bool = False,
    counters: bool = False,
):
    """Warm peel with a *device-side* bucket-fit check — the primitive the
    predictive workset dispatcher builds on.

    ``v_bucket/e_bucket`` come from the host's *prediction* (previous-tick
    suffix counts), not from this tick's synced counts; ``nv/ne`` are this
    tick's actual counts, still resident on device.  ``lax.cond`` selects
    between the workset path (counts fit the predicted buckets — the
    gather is lossless) and the full-buffer warm peel (bucket miss — the
    always-correct fallback), so the host never has to block on the count
    transfer before dispatching the re-peel.  Both branches return the
    full-width ``PeelResultDevice``; on integer weights they are
    bit-identical whenever both are applicable, so a miss costs time,
    never correctness.

    Returns ``(result, fits)`` with ``fits`` the device bool the caller
    can drain lazily for telemetry; with ``counters``, ``result`` is
    ``(result, counts)`` from whichever branch ran.
    """
    fits = (nv <= jnp.int32(v_bucket)) & (ne <= jnp.int32(e_bucket))
    res = jax.lax.cond(
        fits,
        lambda: bulk_peel_warm_workset(
            g, keep, prior_best_g, eps=eps, max_rounds=max_rounds,
            v_bucket=v_bucket, e_bucket=e_bucket, use_kernel=use_kernel,
            counters=counters,
        ),
        lambda: bulk_peel_warm(
            g, keep, prior_best_g, eps=eps, max_rounds=max_rounds,
            use_kernel=use_kernel, counters=counters,
        ),
    )
    return res, fits
