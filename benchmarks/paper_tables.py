"""Benchmarks reproducing the paper's tables/figures (scaled to this
container; the paper's claims are *ratios*, which transfer):

* ``bench_incremental_speedup`` — Fig 10 + Table 4: static peel vs
  incremental reorder per edge, batch sizes |ΔE| ∈ {1, 10, 100, 1K}.
* ``bench_edge_grouping``       — Table 5: IncXG vs IncX-1K elapsed/edge.
* ``bench_prevention``          — Fig 9a / §5.2: prevention ratio & latency.
* ``bench_device_plane``        — TPU-native plane: bulk peel + incremental
  maintenance wall-times (CPU backend; ratios again).
* ``bench_window``              — Appendix C.3 sliding-window serving:
  steady-state warm tick (expire + insert suffix re-peels) vs a full
  from-scratch bulk re-peel per tick; emits ``BENCH_window.json``.
* ``bench_workset``             — affected-area workset engine (DESIGN.md
  §8): bucketed workset tick vs full-buffer warm tick, hot/cold, plus
  per-bucket warm re-peel rows; emits ``BENCH_workset.json``.

Every row prints ``name,us_per_call,derived`` CSV (derived = speedup /
ratio / aux metric for that row).
"""

from __future__ import annotations

import time

import numpy as np

from repro.core.metrics import make_metric
from repro.core.reference import AdjGraph, detect, insert_edges, static_peel
from repro.core.spade import Spade
from repro.graphstore.generators import make_transaction_stream
from repro.serve import EngineSpec, SpadeService

Row = tuple[str, float, float]


def _build_graph(metric, stream, frac=1.0):
    m = int(stream.base_src.shape[0] * frac)
    sp = Spade(metric=metric)
    sp.LoadGraph(stream.base_src[:m], stream.base_dst[:m], stream.base_amt[:m],
                 n_vertices=stream.n_vertices)
    return sp


def bench_incremental_speedup(
    n=16000, m=100000, n_inc=2000, batches=(1, 10, 100, 1000), seed=0
) -> list[Row]:
    """Fig 10 / Table 4 (wiki-vote-scale replica)."""
    rows: list[Row] = []
    stream = make_transaction_stream(n=n, m=m, inc_fraction=0.05, seed=seed)
    for name in ("DG", "DW", "FD"):
        sp = _build_graph(name, stream)
        # static from-scratch run (the per-insertion cost of the baseline)
        t0 = time.perf_counter()
        static_peel(sp.graph.copy())
        t_static = time.perf_counter() - t0
        rows.append((f"fig10_static_{name}", t_static * 1e6, 1.0))

        inc = list(zip(stream.inc_src.tolist(), stream.inc_dst.tolist(),
                       stream.inc_amt.tolist()))[:n_inc]
        for b in batches:
            spb = _build_graph(name, stream)
            t0 = time.perf_counter()
            i = 0
            while i < len(inc):
                spb.InsertBatchEdges(inc[i : i + b])
                i += b
            dt = time.perf_counter() - t0
            us_per_edge = dt / len(inc) * 1e6
            speedup = (t_static * 1e6) / max(us_per_edge, 1e-9)
            rows.append((f"table4_Inc{name}_batch{b}", us_per_edge, speedup))
    return rows


def bench_edge_grouping(n=16000, m=100000, n_inc=2000, seed=1) -> list[Row]:
    """Table 5: edge grouping (IncXG) vs fixed 1K batches (IncX-1K)."""
    rows: list[Row] = []
    stream = make_transaction_stream(n=n, m=m, inc_fraction=0.05, seed=seed)
    inc = list(zip(stream.inc_src.tolist(), stream.inc_dst.tolist(),
                   stream.inc_amt.tolist()))[:n_inc]
    for name in ("DG", "DW", "FD"):
        # fixed 1K batches
        sp = _build_graph(name, stream)
        t0 = time.perf_counter()
        for i in range(0, len(inc), 1000):
            sp.InsertBatchEdges(inc[i : i + 1000])
        t_batch = (time.perf_counter() - t0) / len(inc) * 1e6
        # grouping: benign edges buffer, urgent flush immediately
        spg = Spade(metric=name, edge_grouping=True)
        spg.LoadGraph(stream.base_src, stream.base_dst, stream.base_amt,
                      n_vertices=stream.n_vertices)
        t0 = time.perf_counter()
        for e in inc:
            spg.InsertEdge(*e)
        spg.FlushBuffer()
        t_group = (time.perf_counter() - t0) / len(inc) * 1e6
        rows.append((f"table5_Inc{name}-1K", t_batch, 1.0))
        rows.append((f"table5_Inc{name}G", t_group, t_batch / max(t_group, 1e-9)))
    return rows


def bench_prevention(seed=2) -> list[Row]:
    """Fig 9a / §5.2: prevention ratio + detection latency, grouping on/off."""
    rows: list[Row] = []
    for grouping in (False, True):
        stream = make_transaction_stream(n=8000, m=40000, seed=seed)
        rep = SpadeService("DW", EngineSpec(
            plane="host", grouping=grouping, batch_edges=1, flush_every=0.5,
        )).run(stream)
        tag = "grouping" if grouping else "batch1"
        rows.append((f"fig9a_prevention_{tag}", rep.mean_us_per_edge,
                     rep.prevention_ratio if rep.prevention_ratio is not None else -1.0))
        rows.append((f"fig9a_recall_{tag}", rep.mean_us_per_edge, rep.fraud_recall))
        rows.append((f"fig11_latency_{tag}", rep.mean_us_per_edge,
                     rep.detection_latency_s if rep.detection_latency_s is not None else -1.0))
    return rows


def bench_device_plane(seed=3) -> list[Row]:
    """TPU-native plane on the CPU backend: bulk peel + incremental tick."""
    import jax
    import jax.numpy as jnp

    from repro.core.incremental import init_state, insert_and_maintain
    from repro.core.peel import bulk_peel
    from repro.graphstore.structs import device_graph_from_coo

    rows: list[Row] = []
    rng = np.random.default_rng(seed)
    n, m = 100_000, 400_000
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    keep = src != dst
    g = device_graph_from_coo(n, src[keep], dst[keep],
                              np.ones(keep.sum(), np.float32),
                              e_capacity=keep.sum() + 65536)
    t0 = time.perf_counter()
    res = jax.block_until_ready(bulk_peel(g, eps=0.1))
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = jax.block_until_ready(bulk_peel(g, eps=0.1))
    t_bulk = time.perf_counter() - t0
    rows.append(("device_bulk_peel_100k", t_bulk * 1e6, float(res.n_rounds)))
    rows.append(("device_bulk_peel_compile", t_first * 1e6, t_first / max(t_bulk, 1e-9)))

    state = init_state(g, eps=0.1)
    B = 1024
    bs = jnp.asarray(rng.integers(0, n, B), jnp.int32)
    bd = jnp.asarray(rng.integers(0, n, B), jnp.int32)
    bc = jnp.ones(B, jnp.float32)
    valid = bs != bd
    state = jax.block_until_ready(
        insert_and_maintain(state, bs, bd, bc, valid, eps=0.1)
    )  # compile
    t0 = time.perf_counter()
    reps = 5
    for _ in range(reps):
        state = insert_and_maintain(state, bs, bd, bc, valid, eps=0.1)
    jax.block_until_ready(state.best_g)
    t_inc = (time.perf_counter() - t0) / reps
    rows.append(("device_incremental_1024", t_inc * 1e6, t_inc / B * 1e6))
    return rows


class _WindowBenchEnv:
    """Shared harness for the sliding-window benches (``bench_window`` /
    ``bench_workset``): base graph factory, hot-pool probe, and a regime
    runner.  Every regime re-seeds its own batch stream, so any two
    regimes (and both engines) replay IDENTICAL transaction sequences —
    suffix sizes drive the tick cost, so comparing different streams
    would compare unlike workloads."""

    def __init__(self, n, m, batch, window, seed):
        self.n, self.batch, self.window, self.seed = n, batch, window, seed
        rng = np.random.default_rng(seed)
        src = rng.integers(0, n, m)
        dst = rng.integers(0, n, m)
        keep = src != dst
        self.m_base = int(keep.sum())
        self._coo = (src[keep], dst[keep])
        # hot pool: the vertices the last peel removed in the final rounds
        probe = self.fresh_state()
        lv = np.asarray(probe.level)
        lv = np.where(np.asarray(probe.graph.vertex_mask), lv, -1)
        self.hot_pool = np.argsort(lv)[-max(batch // 2, 64):]

    def fresh_state(self):
        from repro.core.incremental import init_state
        from repro.graphstore.structs import device_graph_from_coo

        g = device_graph_from_coo(
            self.n, *self._coo, np.ones(self.m_base, np.float32),
            e_capacity=self.m_base + (self.window + 1) * self.batch,
        )
        return init_state(g, eps=0.1)

    def run_regime(self, hot_pool, workset=False, reps=5):
        """Steady-state mean tick seconds for one traffic regime.

        Returns ``(tick_seconds, final_state, telemetry)``; telemetry is
        all zeros for the fused full-buffer engine."""
        import jax
        import jax.numpy as jnp

        from repro.core.incremental import (
            slide_and_maintain,
            slide_and_maintain_auto,
        )

        state = self.fresh_state()
        n, batch, window, m_base = self.n, self.batch, self.window, self.m_base
        slot_ids = jnp.arange(state.graph.e_capacity, dtype=jnp.int32)
        ring: list[int] = []
        telemetry = {"workset": 0, "fallback": 0, "max_e_bucket": 0}
        rng = np.random.default_rng(self.seed + 100)  # per-regime stream

        def make_batch():
            if hot_pool is None:
                bs, bd = rng.integers(0, n, batch), rng.integers(0, n, batch)
            else:
                bs, bd = rng.choice(hot_pool, batch), rng.choice(hot_pool, batch)
            bs = jnp.asarray(bs, jnp.int32)
            bd = jnp.asarray(bd, jnp.int32)
            return bs, bd, jnp.ones(batch, jnp.float32), bs != bd

        def tick(state):
            cnt0 = ring.pop(0) if len(ring) >= window else 0
            drop = (slot_ids >= m_base) & (slot_ids < m_base + cnt0)
            bs, bd, bc, valid = make_batch()
            if workset:
                state, info = slide_and_maintain_auto(
                    state, drop, bs, bd, bc, valid, eps=0.1
                )
                telemetry["fallback" if info.fallback else "workset"] += 1
                telemetry["max_e_bucket"] = max(
                    telemetry["max_e_bucket"], info.e_bucket
                )
            else:
                state = slide_and_maintain(state, drop, bs, bd, bc, valid,
                                           eps=0.1)
            ring.append(int(jnp.sum(valid)))
            return state

        for _ in range(window + 1):  # fill the window + warm compile caches
            state = tick(state)
        jax.block_until_ready(state.best_g)
        t0 = time.perf_counter()
        for _ in range(reps):
            state = tick(state)
        jax.block_until_ready(state.best_g)
        return (time.perf_counter() - t0) / reps, state, telemetry


def bench_window(
    n=100_000,
    m=400_000,
    batch=1024,
    window=8,
    seed=4,
    out_json="BENCH_window.json",
) -> list[Row]:
    """Sliding-window serving (paper Appendix C.3, device plane): the fused
    warm tick (``slide_and_maintain``: expire + insert + one suffix
    re-peel) vs the naive alternative of a full from-scratch bulk re-peel
    per tick, in two traffic regimes:

    * **cold** — uniform random endpoints: some endpoint almost surely
      peeled in round 0, so ``r0 = 0`` and the warm tick degenerates to a
      full re-peel plus compaction overhead (the honest worst case).
    * **hot**  — the paper's fraud-burst case study: traffic concentrated
      on the currently-densest vertices (high peel level), so the
      re-peeled suffix is small and the warm tick wins on round count.

    Writes ``out_json`` so the perf trajectory is recorded per commit."""
    import json

    import jax

    from repro.core.peel import bulk_peel

    env = _WindowBenchEnv(n, m, batch, window, seed)
    t_cold, state, _ = env.run_regime(None)
    t_hot, _, _ = env.run_regime(env.hot_pool)

    # naive alternative: full bulk re-peel of the resident graph per tick
    res = jax.block_until_ready(bulk_peel(state.graph, eps=0.1))  # compile
    reps = 5
    t0 = time.perf_counter()
    for _ in range(reps):
        res = bulk_peel(state.graph, eps=0.1)
    jax.block_until_ready(res.best_g)
    t_scratch = (time.perf_counter() - t0) / reps

    rows: list[Row] = [
        ("window_slide_tick_cold", t_cold * 1e6, t_scratch / max(t_cold, 1e-9)),
        ("window_slide_tick_hot", t_hot * 1e6, t_scratch / max(t_hot, 1e-9)),
        ("window_full_repeel", t_scratch * 1e6, float(res.n_rounds)),
    ]
    if out_json:
        with open(out_json, "w") as f:
            json.dump(
                {
                    "n": int(n), "m": int(m), "batch": int(batch),
                    "window": int(window),
                    "rows": {r[0]: {"us": r[1], "derived": r[2]} for r in rows},
                },
                f, indent=1,
            )
    return rows


def bench_workset(
    n=100_000,
    m=400_000,
    batch=1024,
    window=8,
    seed=4,
    out_json="BENCH_workset.json",
) -> list[Row]:
    """Affected-area workset engine (DESIGN.md §8) vs the full-buffer warm
    tick, same setup as :func:`bench_window`:

    * **hot ticks** — fraud-burst traffic on the densest vertices: the
      affected suffix is small, the workset engine gathers it into
      bucket-sized buffers and every re-peel round touches O(|suffix|)
      instead of O(E_capacity).
    * **cold ticks** — uniform traffic: the suffix swallows the graph and
      the engine falls back to the full-buffer path (tick ≈ full tick +
      the one-transfer count sync).
    * **per-bucket rows** — the warm suffix re-peel alone (no structural
      update), workset vs full-buffer, across suffix sizes landing in
      successive power-of-two buckets.

    Writes ``out_json`` so the perf trajectory is recorded per commit."""
    import json

    import jax
    import jax.numpy as jnp

    from repro.core.peel import (
        bulk_peel_warm,
        bulk_peel_warm_workset,
        select_bucket,
        workset_sizes,
    )

    env = _WindowBenchEnv(n, m, batch, window, seed)
    t_full_hot, state_hot, _ = env.run_regime(env.hot_pool, workset=False)
    t_ws_hot, _, tel_hot = env.run_regime(env.hot_pool, workset=True)
    t_full_cold, _, _ = env.run_regime(None, workset=False)
    t_ws_cold, _, tel_cold = env.run_regime(None, workset=True)

    rows: list[Row] = [
        ("workset_tick_hot", t_ws_hot * 1e6, t_full_hot / max(t_ws_hot, 1e-9)),
        ("workset_tick_cold", t_ws_cold * 1e6,
         t_full_cold / max(t_ws_cold, 1e-9)),
        ("workset_full_tick_hot", t_full_hot * 1e6, 1.0),
        ("workset_full_tick_cold", t_full_cold * 1e6, 1.0),
    ]

    # per-bucket rows: the warm re-peel alone over suffixes of growing size
    g = state_hot.graph
    lv = np.where(np.asarray(g.vertex_mask), np.asarray(state_hot.level), -1)
    order = np.argsort(lv)
    seen: set[int] = set()
    for k in (max(batch // 4, 64), batch, 4 * batch, 16 * batch):
        if k > n:
            continue
        kmask = jnp.zeros(g.n_capacity, bool).at[
            jnp.asarray(order[-k:], jnp.int32)
        ].set(True)
        nv, ne = workset_sizes(g, kmask)
        bv = select_bucket(int(nv), g.n_capacity)
        be = select_bucket(int(ne), g.e_capacity)
        if bv is None or be is None or be in seen:
            continue
        seen.add(be)
        reps = 3

        def timed(f):
            out = jax.block_until_ready(f())  # compile
            t0 = time.perf_counter()
            for _ in range(reps):
                out = f()
            jax.block_until_ready(out)
            return (time.perf_counter() - t0) / reps

        t_ws = timed(lambda: bulk_peel_warm_workset(
            g, kmask, prior_best_g=state_hot.best_g, eps=0.1, max_rounds=20,
            v_bucket=bv, e_bucket=be,
        ))
        t_fb = timed(lambda: bulk_peel_warm(
            g, kmask, prior_best_g=state_hot.best_g, eps=0.1, max_rounds=20,
            w_full=state_hot.w0, w_exact=state_hot.w0_exact,
        ))
        rows.append((f"workset_peel_b{be}", t_ws * 1e6,
                     t_fb / max(t_ws, 1e-9)))

    if out_json:
        with open(out_json, "w") as f:
            json.dump(
                {
                    "n": int(n), "m": int(m), "batch": int(batch),
                    "window": int(window),
                    "hot_ticks": tel_hot, "cold_ticks": tel_cold,
                    "rows": {r[0]: {"us": r[1], "derived": r[2]} for r in rows},
                },
                f, indent=1,
            )
    return rows


def bench_sharded_peel(
    n=100_000,
    m=400_000,
    n_devices=8,
    seed=3,
    batch=1024,
    out_json="BENCH_dist.json",
) -> list[Row]:
    """Dist plane: bulk peel + incremental tick, single device vs an
    n-device edge-sharded mesh.
    Writes ``out_json`` so the perf trajectory is recorded per commit."""
    import json

    import jax
    import jax.numpy as jnp

    from repro.core.incremental import init_state, insert_and_maintain
    from repro.core.peel import bulk_peel
    from repro.dist.graph import (
        init_sharded_state,
        shard_graph,
        sharded_bulk_peel,
        sharded_insert_and_maintain,
    )
    from repro.graphstore.structs import device_graph_from_coo
    from repro.launch.mesh import make_mesh

    if len(jax.devices()) < n_devices:
        raise ValueError(
            f"bench_sharded_peel asks for {n_devices} devices; "
            f"{len(jax.devices())} exist"
        )
    nd = n_devices
    mesh = make_mesh((nd,), ("data",))
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    keep = src != dst
    g = device_graph_from_coo(
        n, src[keep], dst[keep], np.ones(keep.sum(), np.float32),
        e_capacity=keep.sum() + 65536,
    )
    gs = shard_graph(g, mesh)

    def timed(f, reps=3):
        out = jax.block_until_ready(f())  # compile
        t0 = time.perf_counter()
        for _ in range(reps):
            out = f()
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / reps, out

    t1, res1 = timed(lambda: bulk_peel(g, eps=0.1))
    tn, resn = timed(lambda: sharded_bulk_peel(gs, mesh, eps=0.1))
    assert float(resn.best_g) == float(res1.best_g)  # unit weights: exact
    rows: list[Row] = [
        ("sharded_bulk_peel_1dev", t1 * 1e6, float(res1.n_rounds)),
        (f"sharded_bulk_peel_{nd}dev", tn * 1e6, t1 / max(tn, 1e-9)),
    ]

    bs = jnp.asarray(rng.integers(0, n, batch), jnp.int32)
    bd = jnp.asarray(rng.integers(0, n, batch), jnp.int32)
    bc = jnp.ones(batch, jnp.float32)
    valid = bs != bd
    reps = 5

    state = init_state(g, eps=0.1)
    state = insert_and_maintain(state, bs, bd, bc, valid, eps=0.1, max_rounds=20)
    t0 = time.perf_counter()
    for _ in range(reps):
        state = insert_and_maintain(state, bs, bd, bc, valid, eps=0.1, max_rounds=20)
    jax.block_until_ready(state.best_g)
    t_i1 = (time.perf_counter() - t0) / reps

    state = init_sharded_state(gs, mesh, eps=0.1)
    state = sharded_insert_and_maintain(
        state, bs, bd, bc, valid, mesh=mesh, eps=0.1, max_rounds=20
    )
    t0 = time.perf_counter()
    for _ in range(reps):
        state = sharded_insert_and_maintain(
            state, bs, bd, bc, valid, mesh=mesh, eps=0.1, max_rounds=20
        )
    jax.block_until_ready(state.best_g)
    t_in = (time.perf_counter() - t0) / reps
    rows.append(("sharded_tick_1dev", t_i1 * 1e6, t_i1 / batch * 1e6))
    rows.append((f"sharded_tick_{nd}dev", t_in * 1e6, t_i1 / max(t_in, 1e-9)))

    if out_json:
        with open(out_json, "w") as f:
            json.dump(
                {
                    "n": int(n), "m": int(m), "devices": int(nd),
                    "batch": int(batch),
                    "rows": {r[0]: {"us": r[1], "derived": r[2]} for r in rows},
                },
                f, indent=1,
            )
    return rows
