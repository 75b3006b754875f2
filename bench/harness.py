"""Drive one cell: set-up, the measured window, the metrics, the check.

The window drives the served entry, ``SpadeService(semantics,
EngineSpec(...)).run(stream)`` on one device, with the stream's
streamed arrays behind :class:`bench.source.Arrivals`.

* Backlog mixes: a warm-up ``run()`` over ``warmup_ticks`` ticks of the
  cell's own traffic compiles every tick program and times the steady
  (last) tick; the measured ``run()`` then carries
  ``max(1, round(seconds / tick))`` ticks, all due when the window
  opens, so the window stays near ``seconds`` however fast the program
  is and no offered load caps the rate.
* Open-loop mixes: one ``run()``; warm-up ticks due at once, then the
  arrivals of ``seconds`` at the mix's rate, on the mix's schedule.

The window opens when the device has finished set-up (the barrier of
the first read of a window edge) and closes when ``run()`` has returned.
"""

from __future__ import annotations

import dataclasses
import math
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path

import jax
import numpy as np

from bench import generator
from bench.check import compare
from bench.devices import peaks
from bench.source import Arrivals, Barriers
from bench.spec import Bench, Cell
from bench.tracing import Window, load

__all__ = ["NoDevice", "RunView", "as_report", "device_check", "engine_spec",
           "measured_stream", "replay", "run_cell", "tx_stream",
           "warmup_ticks"]

TRACE_DIR = ".bench_trace"  # under the checkout, removed after reading


class NoDevice(RuntimeError):
    """The machine lacks the accelerator or the chips the cell asks for."""


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Backend compiles JAX reports (cache reads included), with when."""

    def __init__(self):
        self.events: list[tuple[float, float, str]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, fun_name: str = "?",
                  **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.perf_counter(), duration, fun_name))

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)

    @property
    def seconds(self) -> float:
        return sum(d for _, d, _ in self.events)

    def between(self, t0: float, t1: float) -> list[str]:
        """The programs compiled (or read from the cache) in [t0, t1]."""
        return [f for t, _, f in self.events if t0 <= t <= t1]


@dataclass
class RunView:
    """What a per-layer reader reads: the traced window and the report."""

    report: object
    window_ticks: int  # ticks whose edges the window holds
    trace: Window | None = None


def device_check(chips: int, require_tpu: bool) -> list[jax.Device]:
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoDevice(f"JAX's first device is {devs[0].platform!r}, "
                       "not a TPU")
    if len(devs) < chips:
        raise NoDevice(f"{chips} chips asked for, {len(devs)} present")
    return devs[:chips]


def engine_spec(cfg: dict):
    from repro.serve import EngineSpec

    e = cfg["engine"]
    return EngineSpec(
        batch_edges=int(e["batch_edges"]), eps=float(e["eps"]),
        max_rounds=int(e["max_rounds"]),
        window_ticks=int(e["window_ticks"]), workset=bool(e["workset"]),
        predictive=bool(e["predictive"]),
        refresh_every=int(e["refresh_every"]),
    )


def _pinned(spec, cfg: dict, m_total: int):
    """Pin an unbounded run's edge buffer to the deployment's capacity,
    whatever this run streams: the service sizes it as
    ``int(m_total * capacity_slack) + batch`` rounded up to 512."""
    cap = cfg.get("capacity_edges")
    if spec.window_ticks or cap is None:
        return spec
    target = int(cap) - 256 - spec.effective_batch_edges
    if target < m_total or cap % 512:
        raise ValueError(f"capacity_edges {cap} cannot hold {m_total} edges "
                         "or is not a multiple of 512")
    return dataclasses.replace(spec, capacity_slack=target / m_total)


def tx_stream(base: generator.Base, streamed: generator.Streamed,
              arrivals: Arrivals):
    """The program's ``TxStream`` with the streamed arrays behind
    ``arrivals`` (``inc_time`` is the arrival order; DG never reads it)."""
    from repro.graphstore.generators import TxStream

    k = streamed.src.shape[0]
    src, dst, amt, t = arrivals.columns(
        streamed.src, streamed.dst, streamed.amt, np.arange(k, dtype=float))
    return TxStream(
        n_vertices=base.n_vertices, base_src=base.src, base_dst=base.dst,
        base_amt=base.amt, inc_src=src, inc_dst=dst, inc_amt=amt,
        inc_time=t, fraud_label=np.zeros(k, bool),
        fraud_block=base.fraud_accounts,
    )


def warmup_ticks(mix: dict, spec) -> int:
    w = mix["warmup_ticks"]
    if w == "window+2":
        return spec.window_ticks + 2
    return int(w)


def measured_stream(cell: Cell, base, spec, seconds: float, seed: int,
                    n_ticks: int | None = None) -> generator.Streamed:
    """What the measured ``run()`` streams: ``n_ticks`` backlogged ticks,
    or an open-loop mix's warm-up ticks and the arrivals of ``seconds``."""
    mix = cell.traffic
    batch = spec.effective_batch_edges
    if mix["arrivals"] == "backlog":
        n = n_ticks * batch
    else:
        n = (warmup_ticks(mix, spec) * batch
             + generator.window_edges(mix, seconds))
    return cell.streams.make_streamed(
        base, mix, n, generator.seeded(seed, generator.WINDOW))


@dataclass
class Measured:
    report: object
    arrivals: Arrivals
    streamed: generator.Streamed
    open: float
    close: float
    window_ticks: int


def _serve(service, base, streamed, arrivals):
    stream = tx_stream(base, streamed, arrivals)
    report = service.run(stream)
    close = arrivals.finish()
    return report, close


def _backlog(cell: Cell, base, spec, seconds, seed, barriers, warm_span):
    from repro.serve import SpadeService

    cfg, mix = cell.config, cell.traffic
    batch = spec.effective_batch_edges
    n_warm = warmup_ticks(mix, spec)
    warm = cell.streams.make_streamed(
        base, mix, n_warm * batch, generator.seeded(seed, generator.WARMUP))
    arr = Arrivals(barriers, np.zeros(n_warm * batch), n_warm * batch,
                   name="warmup")
    spec_w = _pinned(spec, cfg, base.src.shape[0] + n_warm * batch)
    with warm_span:
        _serve(SpadeService(cfg["semantics"], spec_w), base, warm, arr)
    done = arr.completed()
    tick = float(done[-1] - done[-2]) if len(done) > 1 else float(done[-1])
    n_ticks = max(1, round(seconds / tick))
    cap = cfg.get("capacity_edges")
    if cap is not None:  # what the deployment's edge buffer holds
        room = (int(cap) - 256 - batch - base.src.shape[0]) // batch
        n_ticks = max(1, min(n_ticks, room))
    log(f"warm-up: {n_warm} ticks, steady tick {tick:.6f} s; the window "
        f"carries {n_ticks} ticks")
    streamed = measured_stream(cell, base, spec, seconds, seed, n_ticks)
    arr = Arrivals(barriers, np.zeros(n_ticks * batch), 0, name="window")
    spec_m = _pinned(spec, cfg, base.src.shape[0] + n_ticks * batch)
    report, close = _serve(SpadeService(cfg["semantics"], spec_m), base,
                           streamed, arr)
    return Measured(report, arr, streamed, arr.window_open(), close,
                    n_ticks)


def _open_loop(cell: Cell, base, spec, seconds, seed, barriers, warm_span):
    from repro.serve import SpadeService

    cfg, mix = cell.config, cell.traffic
    batch = spec.effective_batch_edges
    n_warm = warmup_ticks(mix, spec) * batch
    k = generator.window_edges(mix, seconds)
    streamed = measured_stream(cell, base, spec, seconds, seed)
    due = np.concatenate([np.zeros(n_warm), cell.schedule.arrival_offsets(
        mix, k, seconds, generator.seeded(seed, generator.ARRIVALS))])
    # the warm-up span ends where the window begins
    arr = Arrivals(barriers, due, n_warm, name="window",
                   on_open=lambda: warm_span.__exit__(None, None, None))
    spec = _pinned(spec, cfg, base.src.shape[0] + n_warm + k)
    warm_span.__enter__()
    report, close = _serve(SpadeService(cfg["semantics"], spec), base,
                           streamed, arr)
    return Measured(report, arr, streamed, arr.window_open(), close,
                    math.ceil(k / batch))


def replay(bench: Bench, cell: Cell, base, streamed, spec,
           precision: str = "float32"):
    """Replay a measured ``run()``'s stream through the reference."""
    ref_mod = bench.reference(cell.config)
    ref = ref_mod.SpadeDG(base.n_vertices, base.src, base.dst, eps=spec.eps,
                          max_rounds=spec.max_rounds,
                          window_ticks=spec.window_ticks,
                          precision=precision)
    batch = spec.effective_batch_edges
    for i in range(0, streamed.src.shape[0], batch):
        ref.tick(streamed.src[i:i + batch], streamed.dst[i:i + batch])
    return ref.result()


def run_cell(bench: Bench, name: str, seed: int, seconds: float,
             trace: bool, t_start: float, require_tpu: bool = True,
             control: str | None = None,
             keep_trace: Path | None = None) -> dict:
    """One run of cell ``name``; returns the result line's object.

    ``control`` (tests and the control script only) puts the reference in
    the stated lower precision in the program's place for the check.
    ``keep_trace`` copies the raw trace there before it is removed.
    """
    cell = bench.cell(name)
    devs = device_check(cell.chips, require_tpu)
    dev = devs[0]
    if require_tpu:
        peaks(dev.device_kind)  # an unknown chip is an error
    clock = CompileClock()
    barriers = Barriers(dev)
    trace_dir = bench.root / TRACE_DIR
    try:
        t0 = time.perf_counter()
        base = generator.make_base(cell.config, seed)
        gen_s = time.perf_counter() - t0
        log(f"generation: {base.n_vertices} accounts, {base.src.shape[0]} "
            f"base edges in {gen_s:.6f} s of host time")
        spec = engine_spec(cell.config)
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # no per-call Python events
            opts.host_tracer_level = 1  # the harness's spans, not JAX's
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        warm_span = jax.profiler.TraceAnnotation("warmup")
        drive = _backlog if cell.traffic["arrivals"] == "backlog" \
            else _open_loop
        c0 = clock.seconds
        with jax.default_device(dev):
            m = drive(cell, base, spec, seconds, seed, barriers, warm_span)
        if trace:
            jax.profiler.stop_trace()
        mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")
        in_window = clock.between(m.open, m.close)
        n_in_window = len(in_window)
        setup_s = m.open - t_start
        window_s = m.close - m.open
        log(f"set-up: {setup_s:.6f} s (generation {gen_s:.6f} s, compile "
            f"{clock.seconds - c0:.6f} s); window {window_s:.6f} s, "
            f"{m.window_ticks} ticks, compiles inside it {n_in_window} "
            f"{in_window}; "
            f"source waited {m.arrivals.waited:.6f} s")
        log(f"report: {m.report}")
        done = m.arrivals.completed()
        ticks = np.diff(np.concatenate([[m.open], done[-m.window_ticks:]]))
        log(f"window ticks: completion intervals (s) "
            f"{ticks.round(6).tolist()}; read lag (s) "
            f"{m.arrivals.read_lag().round(6).tolist()}")

        view = RunView(report=m.report, window_ticks=m.window_ticks)
        if trace:
            view.trace = _read_trace(trace_dir, m, keep_trace)
            shutil.rmtree(trace_dir, ignore_errors=True)

        t_ref = time.perf_counter()
        ref = replay(bench, cell, base, m.streamed, spec)
        n_streamed = m.streamed.src.shape[0]
        checked = m.report
        if control is not None:
            checked = as_report(
                replay(bench, cell, base, m.streamed, spec,
                       precision=control), base, n_streamed)
        checks = compare(checked, ref, base.fraud_accounts, n_streamed,
                         spec.workset)
        log(f"reference: {time.perf_counter() - t_ref:.6f} s, {ref}")
        correct = all(c.ok for c in checks)

        metrics = {}
        if not trace:
            metrics = _end_to_end(cell, m, setup_s, window_s)
        else:
            for metric in cell.per_layer:
                v = bench.layer_reader(metric.name).read(view)
                if v is not None:
                    metrics[metric.name] = {"value": float(v),
                                            "unit": metric.unit}
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devs), "memory_peak_bytes": mem}
        out = {"correct": correct, "attempted": _attempted(m),
               "failed": 0, "metrics": metrics, "device": device}
        if trace:
            device["busy_s"] = view.trace.busy_s
            device["window_s"] = view.trace.window_s
            out["breakdown"] = {"device_ops": view.trace.top_ops(10),
                                "idle_gaps": view.trace.idle_gaps(10)}
            log(f"trace: modules {view.trace.top_modules(10)}; barriers out "
                f"of order {view.trace.barriers_out_of_order('_barrier')}")
        out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                         for c in checks}
        for c in checks:
            print(f"check {c.name}: {c.value!r} (limit {c.limit!r})"
                  f"{'' if c.ok else ' FAILED'}", file=sys.stderr, flush=True)
        return out
    finally:
        clock.close()
        barriers.close()
        shutil.rmtree(trace_dir, ignore_errors=True)


def _attempted(m: Measured) -> int:
    return int(sum(hi - max(lo, m.arrivals.window_start)
                   for lo, hi in m.arrivals.bounds
                   if hi > m.arrivals.window_start))


def _end_to_end(cell: Cell, m: Measured, setup_s: float,
                window_s: float) -> dict:
    values = {"setup_s": setup_s}
    names = {x.name for x in cell.end_to_end}
    if "edges_per_s" in names:
        values["edges_per_s"] = _attempted(m) / window_s
    if "latency_p95_ms" in names:
        values["latency_p95_ms"] = 1e3 * float(
            np.percentile(m.arrivals.latencies(), 95))
    missing = names - values.keys()
    if missing:
        raise KeyError(f"no measurement for {sorted(missing)}")
    return {x.name: {"value": values[x.name], "unit": x.unit}
            for x in cell.end_to_end}


def _read_trace(trace_dir: Path, m: Measured,
                keep: Path | None = None) -> Window:
    files = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    if keep is not None:
        shutil.copy(files[-1], keep)
    origin = m.arrivals.origin
    return Window(load(files[-1]), open_delay=m.open - origin,
                  close_delay=m.close - origin)


def as_report(ref, base, n_streamed: int):
    """The reference's answers in the fields of a service report."""
    from types import SimpleNamespace

    fraud = set(int(x) for x in base.fraud_accounts)
    return SimpleNamespace(
        final_g=ref.final_g, live_edges=ref.live_edges,
        n_expired_edges=ref.n_expired_edges, n_ticks=ref.n_ticks,
        fraud_recall=len(fraud & ref.detected) / len(fraud),
        benign_fraction=ref.benign / n_streamed,
        max_suffix_edges=ref.max_suffix_edges,
        n_workset_ticks=ref.n_ticks, n_fallback_ticks=0,
    )
