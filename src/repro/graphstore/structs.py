"""Device-resident graph structures (fixed-capacity, shard-ready).

TPU/XLA requires static shapes, so the evolving transaction graph lives in
fixed-capacity COO buffers with validity masks.  Edge insertion appends into
pre-allocated slots; capacity growth is a host-side reallocation (amortized,
off the latency path).  All fields are leading-dim shardable:

* edge arrays ``src/dst/c/edge_mask``  → partitioned over ``(pod, data)``
* vertex arrays ``a/vertex_mask``      → replicated or sharded over ``model``
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["DeviceGraph", "WindowExpiry", "device_graph_from_coo",
           "compact_slots", "append_edges", "remove_edges", "expiring_edges",
           "expire_window", "csr_sort"]


def compact_slots(
    offset: jax.Array, valid: jax.Array, capacity: int
) -> tuple[jax.Array, jax.Array]:
    """Compacted append slots: the k-th valid lane gets ``offset + k``.

    Returns ``(idx, ok)``; lanes with ``~ok`` (invalid, or past capacity)
    must be dropped by the caller.  Shared by the single-device and the
    edge-sharded append so their slot semantics cannot diverge.
    """
    slot = jnp.cumsum(valid.astype(jnp.int32)) - 1
    idx = offset + slot
    return idx, valid & (idx < capacity)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["src", "dst", "c", "edge_mask", "a", "vertex_mask"],
    meta_fields=["n_capacity", "e_capacity"],
)
@dataclasses.dataclass(frozen=True)
class DeviceGraph:
    """Fixed-capacity COO transaction graph on device.

    ``src[i] -> dst[i]`` with suspiciousness ``c[i]`` where ``edge_mask[i]``.
    Invalid slots carry ``src = dst = n_capacity - 1`` padding self-loops with
    ``c = 0`` so segment ops need no extra masking of indices.
    """

    src: jax.Array  # int32 [E_cap]
    dst: jax.Array  # int32 [E_cap]
    c: jax.Array  # float32 [E_cap]
    edge_mask: jax.Array  # bool [E_cap]
    a: jax.Array  # float32 [V_cap] vertex suspiciousness
    vertex_mask: jax.Array  # bool [V_cap]
    n_capacity: int
    e_capacity: int

    @property
    def n_vertices(self) -> jax.Array:
        return jnp.sum(self.vertex_mask)

    @property
    def n_edges(self) -> jax.Array:
        return jnp.sum(self.edge_mask)

    def f_total(self) -> jax.Array:
        """f(V): total graph suspiciousness (Eq. 1)."""
        return jnp.sum(jnp.where(self.vertex_mask, self.a, 0.0)) + jnp.sum(
            jnp.where(self.edge_mask, self.c, 0.0)
        )

    def peel_weights(self) -> jax.Array:
        """w_u(S_0) for every vertex: a_u + incident suspiciousness."""
        cm = jnp.where(self.edge_mask, self.c, 0.0)
        w = jnp.where(self.vertex_mask, self.a, 0.0)
        w = w + jax.ops.segment_sum(cm, self.src, num_segments=self.n_capacity)
        w = w + jax.ops.segment_sum(cm, self.dst, num_segments=self.n_capacity)
        return w


def device_graph_from_coo(
    n: int,
    src: np.ndarray,
    dst: np.ndarray,
    c: np.ndarray | None = None,
    a: np.ndarray | None = None,
    n_capacity: int | None = None,
    e_capacity: int | None = None,
) -> DeviceGraph:
    """Build a DeviceGraph from host COO arrays (padding to capacity)."""
    src = np.asarray(src, dtype=np.int32)
    dst = np.asarray(dst, dtype=np.int32)
    m = src.shape[0]
    c = np.ones(m, dtype=np.float32) if c is None else np.asarray(c, dtype=np.float32)
    n_cap = int(n_capacity or n)
    e_cap = int(e_capacity or max(m, 1))
    if n_cap < n or e_cap < m:
        raise ValueError("capacity smaller than graph")
    pad_e = e_cap - m
    pad_idx = np.full(pad_e, n_cap - 1, dtype=np.int32)
    av = np.zeros(n_cap, dtype=np.float32)
    if a is not None:
        av[:n] = np.asarray(a, dtype=np.float32)
    return DeviceGraph(
        src=jnp.asarray(np.concatenate([src, pad_idx])),
        dst=jnp.asarray(np.concatenate([dst, pad_idx])),
        c=jnp.asarray(np.concatenate([c, np.zeros(pad_e, np.float32)])),
        edge_mask=jnp.asarray(
            np.concatenate([np.ones(m, bool), np.zeros(pad_e, bool)])
        ),
        a=jnp.asarray(av),
        vertex_mask=jnp.asarray(np.arange(n_cap) < n),
        n_capacity=n_cap,
        e_capacity=e_cap,
    )


def append_edges(
    g: DeviceGraph,
    offset: jax.Array,
    src: jax.Array,
    dst: jax.Array,
    c: jax.Array,
    valid: jax.Array | None = None,
) -> DeviceGraph:
    """Write a batch of edges into consecutive slots from ``offset``.

    ``offset`` is the current edge count (host-tracked or device scalar);
    batch size B is static.  Valid entries are *compacted*: the k-th valid
    edge lands in slot ``offset + k``, so the slot range consumed always
    equals ``sum(valid)`` — the amount callers advance their edge counter
    by — even when invalid entries sit between valid ones.  Out-of-capacity
    writes are dropped (callers reallocate on host when the high-water mark
    approaches capacity).
    """
    B = src.shape[0]
    v = jnp.ones(B, bool) if valid is None else valid
    idx, ok = compact_slots(offset, v, g.e_capacity)
    # dropped writes go out of bounds and are discarded by mode='drop'
    idx = jnp.where(ok, idx, g.e_capacity)
    return dataclasses.replace(
        g,
        src=g.src.at[idx].set(src.astype(jnp.int32), mode="drop"),
        dst=g.dst.at[idx].set(dst.astype(jnp.int32), mode="drop"),
        c=g.c.at[idx].set(c.astype(jnp.float32), mode="drop"),
        edge_mask=g.edge_mask.at[idx].set(True, mode="drop"),
    )


def remove_edges(g: DeviceGraph, drop: jax.Array) -> tuple[DeviceGraph, jax.Array]:
    """Tombstone the slots where ``drop`` holds and compact the survivors.

    The k-th surviving edge (in slot order) moves to slot ``k`` — the same
    slot semantics as ``compact_slots`` on the append path, so insertion
    order is preserved and the live region stays a prefix.  Sliding-window
    callers exploit this: after every expiry the oldest batch again sits
    right after the base graph, where :func:`expire_window` expires it
    without reading the rest of the buffer.  Freed slots revert to the
    standard inert padding (``src = dst = n_capacity - 1``, ``c = 0``,
    mask False).

    The compaction runs as a **gather**: output slot ``k`` pulls the k-th
    survivor, located by binary search over the survivor-count prefix sum
    (``searchsorted``).  The scatter formulation (full-buffer ``.at[].set``
    with cumsum slots) was tried and REFUTED: XLA CPU scatters cost ~4x a
    sorted-search gather at 400k edges, and this pass sits on the serving
    tick's critical path.  The compacted mask is just ``slot < survivors``
    — no scatter at all.

    Returns ``(graph, n_removed)`` with ``n_removed`` the number of *live*
    edges dropped (tombstoning an already-dead slot is a no-op).
    """
    pad = jnp.int32(g.n_capacity - 1)
    E = g.e_capacity
    survive = g.edge_mask & ~drop
    csum = jnp.cumsum(survive.astype(jnp.int32))
    n_survive = csum[E - 1]
    # slot k (0-based) takes the (k+1)-th survivor: the first index whose
    # running survivor count reaches k+1
    idx = jnp.searchsorted(csum, jnp.arange(1, E + 1, dtype=jnp.int32))
    live = jnp.arange(E, dtype=jnp.int32) < n_survive
    idx = jnp.where(live, idx, E - 1)  # clamp dead lanes (values masked below)
    n_removed = jnp.sum(g.edge_mask & drop).astype(jnp.int32)
    return (
        dataclasses.replace(
            g,
            src=jnp.where(live, g.src[idx], pad),
            dst=jnp.where(live, g.dst[idx], pad),
            c=jnp.where(live, g.c[idx], 0.0),
            edge_mask=live,
        ),
        n_removed,
    )


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["start", "count"],
    meta_fields=["span"],
)
@dataclasses.dataclass(frozen=True)
class WindowExpiry:
    """The oldest tick of a sliding window: the ``count`` slots from
    ``start`` of a buffer whose live edges are a prefix.

    ``start`` and ``count`` are traced int32 scalars: the base graph's
    edge count and the oldest tick's.  ``span`` is static, the window's
    resident slots (``window_ticks * batch_edges``), so a program that
    takes an expiry compiles once per configuration, whatever edge count
    the base graph has.  The live edges end within ``span`` slots of
    ``start``, ``count <= span``, and the buffer holds ``start + count +
    span`` slots: the windowed service's capacity ``m_base +
    (window_ticks + 1) * batch_edges`` does."""

    start: jax.Array  # int32 scalar
    count: jax.Array  # int32 scalar
    span: int


def _window_slice(a: jax.Array, start: jax.Array, span: int) -> jax.Array:
    return jax.lax.dynamic_slice_in_dim(a, start, span)


def expiring_edges(
    g: DeviceGraph, x: WindowExpiry
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """``(src, dst, c, dropped)`` of the ``x.span`` slots from
    ``x.start``; ``dropped`` marks the live edges among the first
    ``x.count``, the edges that expire."""
    lane = jnp.arange(x.span, dtype=jnp.int32)
    mask = _window_slice(g.edge_mask, x.start, x.span)
    return (_window_slice(g.src, x.start, x.span),
            _window_slice(g.dst, x.start, x.span),
            _window_slice(g.c, x.start, x.span), (lane < x.count) & mask)


def expire_window(
    g: DeviceGraph, x: WindowExpiry
) -> tuple[DeviceGraph, jax.Array]:
    """``remove_edges`` of the slots ``[x.start, x.start + x.count)``, at
    the cost of the window.

    The live edges are a prefix that ends within ``x.span`` slots of
    ``x.start``, so the survivors past the expired slots are the span
    from ``x.start + x.count``: shifting it down by ``x.count`` puts each
    where ``remove_edges`` would, in the same order, and its dead lanes
    take the inert padding.  No slot before ``x.start`` or past the span
    is read or written.  Slot for slot equal to ``remove_edges`` on the
    same mask.

    Returns ``(graph, n_removed)``, the live edges the expired slots held.
    """
    shift = x.start + x.count
    alive = _window_slice(g.edge_mask, shift, x.span)
    pad = jnp.int32(g.n_capacity - 1)

    def put(a, v):
        return jax.lax.dynamic_update_slice_in_dim(a, v, x.start, 0)

    *_, dropped = expiring_edges(g, x)
    return (
        dataclasses.replace(
            g,
            src=put(g.src, jnp.where(alive, _window_slice(g.src, shift,
                                                          x.span), pad)),
            dst=put(g.dst, jnp.where(alive, _window_slice(g.dst, shift,
                                                          x.span), pad)),
            c=put(g.c, jnp.where(alive, _window_slice(g.c, shift, x.span),
                                 0.0)),
            edge_mask=put(g.edge_mask, alive),
        ),
        jnp.sum(dropped, dtype=jnp.int32),
    )


def csr_sort(g: DeviceGraph) -> DeviceGraph:
    """Sort edge slots by (src, dst) for locality (host-side utility)."""
    src = np.asarray(g.src)
    dst = np.asarray(g.dst)
    order = np.lexsort((dst, src))
    return dataclasses.replace(
        g,
        src=jnp.asarray(src[order]),
        dst=jnp.asarray(dst[order]),
        c=jnp.asarray(np.asarray(g.c)[order]),
        edge_mask=jnp.asarray(np.asarray(g.edge_mask)[order]),
    )
