"""``SpadeService``: one facade over every serving plane.

The serving surface used to be a flag soup: ``run_service`` (host oracle)
and ``run_device_service`` (12 keywords spanning single-device, mesh-
sharded, windowed, and workset modes), each with its own ``metric: str``
dispatch.  The facade collapses both into

    ``SpadeService(semantics, spec: EngineSpec).run(stream)``

where :class:`EngineSpec` is a declarative description of *where and how*
to serve (plane, mesh, window, workset, predictive buckets, grouping) and
``semantics`` is *what to measure* — a
:class:`repro.core.semantics.SuspSemantics` (or registered name) compiled
once and threaded through whichever engines the spec selects.  A
user-defined semantics therefore reaches every fast path with zero engine
edits; the legacy entrypoints remain as deprecation shims
(:mod:`repro.serve.service`, :mod:`repro.serve.device_service`).

The device serving loop here is the production tick pipeline:

* base graph seeded through the semantics' batch-seeding rule (dyadic
  snap at the protocol boundary, vertex priors included),
* per-tick weighting by the semantics' jit-compiled ``batch_weights``
  (arrival-time degrees for degree-using semantics, per-edge aux payload
  — the transaction timestamp — for aux-using ones),
* maintenance through the fused, workset, or predictive-workset engine,
  single-device or mesh-sharded,
* per-tick statistics accumulated on device and drained at shutdown; the
  single-device engines also return their per-round counters
  (:func:`repro.core.incremental.tick_counters`), fetched once after the
  loop.

Under a window the oldest resident tick sits right after the base graph,
so a single-device slide expires it as a
:class:`repro.graphstore.structs.WindowExpiry`: the base count and the
tick's count ride as traced scalars and the span is the spec's, so each
tick program compiles once per configuration whatever the base graph
holds.

Every step of the loop runs under a ``jax.profiler.TraceAnnotation``
span, so a profiler trace puts the host's steps on the device trace's
clock: ``spade.seed``, ``spade.upload`` and ``spade.initial_peel`` in
set-up; per tick ``spade.tick`` (carrying ``tick=<n>``) around
``spade.read`` (the stream slices), ``spade.prep`` (padding and
host-to-device transfers), ``spade.weigh`` (``batch_weights`` and the
benign count), ``spade.dispatch`` (the tick program's call) and
``spade.refresh``; ``spade.drain`` after the loop.  With no profiler
attached a span costs about a microsecond.

With ``workset=True, predictive=True`` (the default) the workset buckets
come from the previous tick's suffix counts and the fit check runs on
device (``bulk_peel_warm_checked``), so the serving loop issues **no
blocking device->host transfer at all**: the counts are drained after
phase B is already in flight.  A bucket miss rides the in-program
full-buffer fallback and re-anchors the predictor (DESIGN.md §8/§9).
"""

from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from jax.profiler import TraceAnnotation

from repro.core.incremental import (
    BucketPredictor,
    DeviceSpadeState,
    benign_mask,
    full_refresh,
    init_state,
    insert_and_maintain,
    insert_and_maintain_auto,
    insert_and_maintain_predictive,
    slide_and_maintain,
    slide_and_maintain_auto,
    slide_and_maintain_predictive,
    tick_counters,
)
from repro.core.metrics import DensityMetric
from repro.core.semantics import SuspSemantics, resolve
from repro.dist.graph import (
    init_sharded_state,
    shard_graph,
    sharded_full_refresh,
    sharded_insert_and_maintain,
    sharded_insert_and_maintain_auto,
    sharded_insert_and_maintain_predictive,
    sharded_slide_and_maintain,
    sharded_slide_and_maintain_auto,
    sharded_slide_and_maintain_predictive,
)
from repro.graphstore.generators import TxStream
from repro.graphstore.structs import WindowExpiry, device_graph_from_coo

__all__ = ["EngineSpec", "SpadeService", "DeviceServiceReport"]


@dataclass(frozen=True)
class EngineSpec:
    """Declarative serving-engine configuration (the *where and how*).

    Device-plane fields: ``mesh``/``shard_axis`` (edge buffers block-
    sharded, vertex state replicated), ``window_ticks`` (N-tick sliding
    window; 0 = unbounded insert-only), ``workset`` (affected-area
    engine), ``predictive`` (previous-tick bucket prediction — drops the
    serving loop's only blocking device->host sync; ignored unless
    ``workset``), ``min_bucket``, ``batch_edges`` (tick size), ``eps``,
    ``max_rounds``, ``refresh_every``, ``capacity_slack``.

    Host-plane fields: ``grouping`` (benign/urgent edge grouping, Def
    4.1), ``flush_every`` (simulated seconds between forced buffer
    flushes), ``batch_edges`` (edges per InsertBatchEdges call).

    ``batch_edges = None`` resolves per plane — 1024-edge device ticks,
    per-edge (batch 1) host reorders, the paper's deployment shape for
    each — so migrating a legacy ``run_service`` call to the facade does
    not silently change the host batch size.
    """

    plane: str = "device"  # "device" | "host"
    mesh: jax.sharding.Mesh | None = None
    shard_axis: str = "data"
    batch_edges: int | None = None
    eps: float = 0.1
    max_rounds: int = 20
    refresh_every: int = 0
    capacity_slack: float = 1.3
    window_ticks: int = 0
    workset: bool = False
    predictive: bool = True
    min_bucket: int = 64
    grouping: bool = True
    flush_every: float = 1.0

    def __post_init__(self):
        if self.plane not in ("device", "host"):
            raise ValueError(f"plane must be 'device' or 'host', got {self.plane!r}")
        if self.batch_edges is not None and self.batch_edges <= 0:
            raise ValueError("batch_edges must be positive")
        if self.plane == "host" and (self.mesh is not None or self.workset
                                     or self.window_ticks):
            raise ValueError(
                "mesh/workset/window_ticks are device-plane settings; "
                "the host oracle serves per-edge with grouping/flush_every"
            )

    @property
    def effective_batch_edges(self) -> int:
        """``batch_edges`` with the per-plane default resolved."""
        if self.batch_edges is not None:
            return self.batch_edges
        return 1024 if self.plane == "device" else 1


@dataclass
class DeviceServiceReport:
    n_edges: int
    n_ticks: int
    mean_tick_seconds: float
    mean_us_per_edge: float
    benign_fraction: float
    fraud_recall: float
    final_g: float
    n_refreshes: int
    window_ticks: int = 0  # 0 = unbounded (insert-only) service
    n_expired_edges: int = 0  # edges that slid out of the window
    live_edges: int = 0  # edges resident at shutdown
    # workset-engine telemetry (zeros when workset=False).  Edge counts
    # follow WorksetTickInfo semantics: global on a single device, max
    # PER-SHARD under a mesh — not comparable across the two modes.
    n_workset_ticks: int = 0
    n_fallback_ticks: int = 0
    max_suffix_edges: int = 0  # high-water mark of the affected suffix
    max_e_bucket: int = 0  # largest edge bucket dispatched
    # predictive-selector telemetry (zeros when predictive=False)
    n_predicted_ticks: int = 0  # ticks dispatched without a count sync
    n_bucket_miss_ticks: int = 0  # predicted buckets the suffix outgrew
    # the single-device engines' counters, one row per tick (None for the
    # mesh engines and for an unbounded peel): the restricted set's
    # active vertices and live edges at the start of each round,
    # [n_ticks, max_rounds]; a round starting with no active vertex
    # peels nothing.  ``round_slots``, [n_ticks, max_rounds]: the edge
    # slots each round streamed, a size of the rounds' ladder
    # (``core.peel.edge_ladder``; a workset tick's ladder starts at its
    # edge bucket), 0 where no round ran.  Per tick the suffix start r0
    # and the suffix's vertices and induced live edges (round 0),
    # [n_ticks]; ``edge_slots`` is the edge buffer's capacity, which the
    # first stage of a fused or fallback tick's rounds streams.
    round_vertices: np.ndarray | None = None
    round_edges: np.ndarray | None = None
    round_slots: np.ndarray | None = None
    suffix_r0: np.ndarray | None = None
    suffix_vertices: np.ndarray | None = None
    suffix_edges: np.ndarray | None = None
    edge_slots: int | None = None


class SpadeService:
    """The one serving entrypoint: a compiled semantics x an engine spec.

    ``semantics`` is a registered name, a :class:`SuspSemantics`, or (host
    plane only) a legacy :class:`DensityMetric`.  ``spec`` defaults to the
    single-device streaming engine; keyword overrides are merged into it
    (``SpadeService("FD", window_ticks=8, workset=True)``).
    """

    def __init__(
        self,
        semantics: SuspSemantics | DensityMetric | str = "DW",
        spec: EngineSpec | None = None,
        **overrides,
    ):
        if spec is None:
            spec = EngineSpec(**overrides)
        elif overrides:
            spec = dataclasses.replace(spec, **overrides)
        self.spec = spec
        if isinstance(semantics, DensityMetric):
            if spec.plane != "host":
                raise TypeError(
                    f"DensityMetric {semantics.name!r} is host-plane-only "
                    "(scalar per-edge hooks); device planes need a "
                    "SuspSemantics — see repro.core.semantics"
                )
            self.semantics: SuspSemantics | DensityMetric = semantics
        else:
            self.semantics = resolve(semantics)

    def run(self, stream: TxStream):
        """Replay ``stream`` through the configured engine.

        Returns a :class:`DeviceServiceReport` (device plane) or a
        :class:`repro.serve.service.ServiceReport` (host plane).
        """
        if self.spec.plane == "host":
            from repro.serve.service import _run_host_service

            return _run_host_service(
                stream,
                metric=self.semantics,
                edge_grouping=self.spec.grouping,
                batch_size=self.spec.effective_batch_edges,
                flush_every=self.spec.flush_every,
            )
        return _run_device_service(stream, self.semantics, self.spec)


# ---------------------------------------------------------------------------
# the device-plane serving loop
# ---------------------------------------------------------------------------


@jax.jit
def _accum_benign(acc, state: DeviceSpadeState, src, dst, c, valid):
    """Device-side benign counter (Def 4.1 against the PRE-tick state);
    padded tail lanes of a partial tick must not count toward stats."""
    return acc + jnp.sum(benign_mask(state, src, dst, c) & valid)


@jax.jit
def _accum_detected(ever, community):
    return ever | community


@partial(jax.jit, static_argnums=0)
def _batch_weights(sem: SuspSemantics, in_deg, src, dst, raw, valid, aux):
    """The semantics' streamed-tick rule, compiled once per semantics."""
    return sem.batch_weights(in_deg, src, dst, raw, valid, aux)


def _run_device_service(
    stream: TxStream, sem: SuspSemantics, spec: EngineSpec
) -> DeviceServiceReport:
    """Fixed-size batched ticks through the device engines (see module
    docstring); the single definition behind the facade's device plane and
    the legacy ``run_device_service`` shim."""
    n = stream.n_vertices
    m_base = stream.base_src.shape[0]
    m_total = m_base + stream.inc_src.shape[0]
    batch_edges = spec.effective_batch_edges
    window_ticks = spec.window_ticks
    eps, max_rounds = spec.eps, spec.max_rounds
    mesh, shard_axis = spec.mesh, spec.shard_axis
    if window_ticks:
        e_cap = m_base + (window_ticks + 1) * batch_edges
    else:
        e_cap = int(m_total * spec.capacity_slack) + batch_edges

    # the semantics' batch-seeding rule: dyadic-snapped edge weights +
    # vertex priors + the degree state the streaming ticks continue from
    with TraceAnnotation("spade.seed"):
        base_aux = np.zeros(m_base) if sem.uses_aux else None
        base_w, in_deg = sem.seed_base(
            stream.base_src, stream.base_dst, stream.base_amt, n,
            aux=base_aux,
        )
        a0 = sem.seed_vertices(n, in_deg, aux=None)

    with TraceAnnotation("spade.upload"):
        g = device_graph_from_coo(
            n, stream.base_src, stream.base_dst, base_w, a=a0,
            n_capacity=-(-n // 512) * 512, e_capacity=-(-e_cap // 512) * 512,
        )
        if mesh is not None:
            g = shard_graph(g, mesh, axis=shard_axis)
    predictive = spec.workset and spec.predictive
    predictor = None
    # the single-device engines count their rounds (a bounded peel)
    counted = mesh is None and max_rounds > 0
    if mesh is not None:
        with TraceAnnotation("spade.initial_peel"):
            state = init_sharded_state(g, mesh, axis=shard_axis, eps=eps)
        refresh = partial(sharded_full_refresh, mesh=mesh, axis=shard_axis)
        if predictive:
            predictor = BucketPredictor(
                g.n_capacity, g.e_capacity // mesh.shape[shard_axis],
                min_bucket=spec.min_bucket,
            )
            maintain = partial(sharded_insert_and_maintain_predictive,
                               predictor=predictor, mesh=mesh, axis=shard_axis)
            slide = partial(sharded_slide_and_maintain_predictive,
                            predictor=predictor, mesh=mesh, axis=shard_axis)
        elif spec.workset:
            maintain = partial(sharded_insert_and_maintain_auto, mesh=mesh,
                               axis=shard_axis, min_bucket=spec.min_bucket)
            slide = partial(sharded_slide_and_maintain_auto, mesh=mesh,
                            axis=shard_axis, min_bucket=spec.min_bucket)
        else:
            maintain = partial(sharded_insert_and_maintain, mesh=mesh,
                               axis=shard_axis)
            slide = partial(sharded_slide_and_maintain, mesh=mesh,
                            axis=shard_axis)
    else:
        with TraceAnnotation("spade.initial_peel"):
            state = init_state(g, eps=eps)
        refresh = full_refresh
        if predictive:
            predictor = BucketPredictor(g.n_capacity, g.e_capacity,
                                        min_bucket=spec.min_bucket)
            maintain = partial(insert_and_maintain_predictive,
                               predictor=predictor)
            slide = partial(slide_and_maintain_predictive,
                            predictor=predictor)
        elif spec.workset:
            maintain = partial(insert_and_maintain_auto,
                               min_bucket=spec.min_bucket)
            slide = partial(slide_and_maintain_auto,
                            min_bucket=spec.min_bucket)
        else:
            maintain = insert_and_maintain
            slide = slide_and_maintain
    with TraceAnnotation("spade.upload"):
        deg_dev = jnp.asarray(in_deg, jnp.int32)
        if deg_dev.shape[0] < g.n_capacity:
            deg_dev = jnp.pad(deg_dev, (0, g.n_capacity - deg_dev.shape[0]))

    n_inc = stream.inc_src.shape[0]
    n_ticks = 0
    n_refresh = 0
    n_expired = 0
    t_total = 0.0
    n_workset = 0
    n_fallback = 0
    n_predicted = 0
    n_miss = 0
    max_suffix_edges = 0
    max_e_bucket = 0
    ring: list[int] = []  # per-tick resident edge counts, oldest first
    benign_acc = jnp.int32(0)  # device accumulator, drained at shutdown
    ever_detected = jnp.zeros(g.n_capacity, bool)  # vertices ever in S^P
    counts: list[jax.Array] = []  # per-tick counter vectors, on device
    tick_kw = {"counters": True} if counted else {}
    if mesh is None:
        # traced, so that no program is keyed on the base graph's size
        base_count = jnp.int32(m_base)
    else:
        slot_ids = jnp.arange(g.e_capacity, dtype=jnp.int32)
    for i in range(0, n_inc, batch_edges):
        with TraceAnnotation("spade.tick", tick=n_ticks):
            j = min(i + batch_edges, n_inc)
            pad = batch_edges - (j - i)
            with TraceAnnotation("spade.read"):
                src_i = stream.inc_src[i:j]
                dst_i = stream.inc_dst[i:j]
                amt_i = stream.inc_amt[i:j]
                time_i = stream.inc_time[i:j] if sem.uses_aux else None
            with TraceAnnotation("spade.prep"):
                bs = np.concatenate([src_i, np.zeros(pad, np.int64)])
                bd = np.concatenate([dst_i, np.zeros(pad, np.int64)])
                amt = np.concatenate([amt_i, np.zeros(pad)])
                valid = np.concatenate([np.ones(j - i, bool),
                                        np.zeros(pad, bool)])
                bs_d = jnp.asarray(bs, jnp.int32)
                bd_d = jnp.asarray(bd, jnp.int32)
                valid_d = jnp.asarray(valid)
                amt_d = jnp.asarray(amt, jnp.float32)
                aux_d = None
                if sem.uses_aux:
                    aux = np.concatenate([time_i, np.zeros(pad)])
                    aux_d = jnp.asarray(aux, jnp.float32)
            with TraceAnnotation("spade.weigh"):
                w, deg_dev = _batch_weights(sem, deg_dev, bs_d, bd_d, amt_d,
                                            valid_d, aux_d)
                benign_acc = _accum_benign(benign_acc, state, bs_d, bd_d, w,
                                           valid_d)
            t0 = time.perf_counter()
            info = None
            with TraceAnnotation("spade.dispatch"):
                if window_ticks and len(ring) >= window_ticks:
                    # fused tick: expire the batch sliding out + insert the
                    # new one with a single warm re-peel.  After compaction
                    # the oldest resident batch always sits right after the
                    # base graph.
                    cnt0 = ring.pop(0)
                    kw = {}
                    if mesh is None:
                        drop = WindowExpiry(base_count, jnp.int32(cnt0),
                                            window_ticks * batch_edges)
                    else:
                        drop = (slot_ids >= m_base) \
                            & (slot_ids < m_base + cnt0)
                        kw = {"n_dropped": cnt0} if predictive else {}
                    out = slide(
                        state, drop, bs_d, bd_d, w.astype(jnp.float32),
                        valid_d, eps=eps, max_rounds=max_rounds, **kw,
                        **tick_kw,
                    )
                    n_expired += cnt0
                else:
                    out = maintain(
                        state, bs_d, bd_d, w.astype(jnp.float32), valid_d,
                        eps=eps, max_rounds=max_rounds, **tick_kw,
                    )
            if spec.workset:
                state, info = out
                if info.counts is not None:
                    counts.append(info.counts)
            elif isinstance(out, tuple):  # the counted tick: (state, counts)
                state, cnt = out
                counts.append(cnt)
            else:
                state = out
            jax.block_until_ready(state.best_g)
            t_total += time.perf_counter() - t0
            if info is not None:
                n_fallback += info.fallback
                n_workset += not info.fallback
                n_predicted += info.predicted
                n_miss += info.miss
                max_suffix_edges = max(max_suffix_edges, info.n_suffix_edges)
                max_e_bucket = max(max_e_bucket, info.e_bucket)
            if window_ticks:
                ring.append(int(valid.sum()))
                # a windowed community is transient by design (the
                # evidence expires); recall is therefore "ever detected
                # while resident", tracked as a device bool vector and
                # drained once at shutdown
                ever_detected = _accum_detected(ever_detected,
                                                state.community)
            n_ticks += 1
            if spec.refresh_every and n_ticks % spec.refresh_every == 0:
                with TraceAnnotation("spade.refresh"):
                    state = refresh(state, eps=eps)
                n_refresh += 1

    # drain the device-resident stats once, after the loop
    with TraceAnnotation("spade.drain"):
        benign_total = int(benign_acc)
        detected = np.where(np.asarray(ever_detected))[0].tolist()
        comm = set(np.where(np.asarray(state.community))[0].tolist()) \
            | set(detected)
        final_g = float(state.best_g)
        live_edges = int(state.edge_count)
        counted_fields = {}
        if counted and len(counts) == n_ticks:  # a count for every tick
            rows = np.stack(jax.device_get(counts)) if counts \
                else np.zeros((0, 1 + 3 * max_rounds), np.int64)
            counted_fields = dict(tick_counters(rows, max_rounds),
                                  edge_slots=int(g.e_capacity))
    fraud = set(stream.fraud_block.tolist())
    recall = len(fraud & comm) / len(fraud) if fraud else 1.0
    return DeviceServiceReport(
        n_edges=n_inc,
        n_ticks=n_ticks,
        mean_tick_seconds=t_total / max(n_ticks, 1),
        mean_us_per_edge=1e6 * t_total / max(n_inc, 1),
        benign_fraction=benign_total / max(n_inc, 1),
        fraud_recall=recall,
        final_g=final_g,
        n_refreshes=n_refresh,
        window_ticks=window_ticks,
        n_expired_edges=n_expired,
        live_edges=live_edges,
        n_workset_ticks=n_workset,
        n_fallback_ticks=n_fallback,
        max_suffix_edges=max_suffix_edges,
        max_e_bucket=max_e_bucket,
        n_predicted_ticks=n_predicted,
        n_bucket_miss_ticks=n_miss,
        **counted_fields,
    )
