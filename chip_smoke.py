#!/usr/bin/env python3
"""Smoke run of the served fraud-detection path on a TPU.

    python chip_smoke.py            # one chip: device, kernel, answers, grab4
    python chip_smoke.py --mesh 4   # four chips: the edge-sharded phase only

Phases on one chip, each through the user entry points (``SpadeService``,
``EngineSpec``, ``configs/spade_grab.py``, ``graphstore/generators.py``):

* device:  JAX's first device must be a TPU; the script never falls back
  to the CPU.
* kernel:  the Pallas ``peel_round`` kernel at Grab4 width equals its jnp
  reference exactly on integer inputs.
* division: every density is ``core.peel.div_rn(f, n)``; on the chip it
  must equal numpy's IEEE f32 quotient bit for bit on 4M (mass, count)
  pairs.  How often the chip's plain ``f / n`` differs is printed.
* answers: DG (every transaction weighs 1, so f32 sums are exact) served
  at a small size on the chip and, in this process, pinned to the CPU
  backend; the reports must agree exactly.  One unbounded run, one
  windowed run through the predictive workset engine on a stream of the
  planted actor's burst alone, whose ticks touch only late-peeled
  vertices: some ticks must take the workset and some the full-buffer
  fallback, so both branches are compared.
* grab4:   the Grab4 stream at full width (6,023,000 accounts, a ~27.5M
  edge base, ~8 streamed ticks of 4096 edges holding the injected join)
  served unbounded (fused engine) and windowed (workset engine); the
  planted actor must be detected and the resident edge count must be
  what the stream implies.

``--mesh N`` runs only the edge-sharded ``SpadeService`` on N chips
against the single-device engine on device 0, on the same full-width
stream, in both modes; the reports must agree exactly.  ``--accounts A``
shrinks that stream to A accounts (edges scaled with them, ticks and
engine settings unchanged) for a quicker check across chips.

Wall times printed on the way are smoke timings of one cold run, not
metrics.  The last line of stdout is the JSON verdict.  Any failed phase
raises, so the script exits non-zero and prints no verdict.

``LIBTPU_INIT_ARGS``, which the TPU host may set, is left as it is.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# the answer check runs the same calls on the CPU backend in this process
_plats = os.environ.get("JAX_PLATFORMS", "")
if _plats and "cpu" not in _plats.split(","):
    os.environ["JAX_PLATFORMS"] = _plats + ",cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.spade_grab import CONFIG as GRAB  # noqa: E402
from repro.core.peel import div_rn  # noqa: E402
from repro.graphstore.generators import make_transaction_stream  # noqa: E402
from repro.kernels.peel_round import peel_round  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.serve import EngineSpec, SpadeService  # noqa: E402

SEMANTICS = "DG"
GRAB_TICKS = 8  # streamed 4096-edge ticks after the base graph
FRAUD_EDGES = 300  # the injected join's burst (generator default)
WINDOW_TICKS = 4
SMALL = dict(n=3000, m=15000, seed=9)
SMALL_BATCH = 256
# the burst alone streams (no background increment), in small ticks
SMALL_HOT = dict(SMALL, inc_fraction=0.0)
SMALL_HOT_BATCH, SMALL_HOT_WINDOW = 32, 6
# report fields that must agree exactly between two engines
COMPARED = ("final_g", "live_edges", "fraud_recall", "n_workset_ticks",
            "n_fallback_ticks", "n_ticks", "n_expired_edges")


class SmokeFailure(AssertionError):
    pass


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Sums the backend compile time JAX reports (cache reads included)."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def say(msg: str) -> None:
    print(msg, flush=True)


def smoke_time(label: str, **secs: float) -> None:
    parts = " ".join(f"{k}={v:.3f}s" for k, v in secs.items())
    say(f"[smoke wall time, not a metric] {label}: {parts}")


def check_device(n_chips: int) -> jax.Device:
    devs = jax.devices()
    require(devs[0].platform == "tpu",
            f"JAX's first device is {devs[0].platform!r}, not a TPU")
    require(len(devs) >= n_chips,
            f"{n_chips} chips asked for, {len(devs)} present")
    say(f"device: {devs[0].device_kind} x{len(devs)}")
    return devs[0]


def check_kernel(width: int) -> None:
    """Pallas ``peel_round`` vs its reference, exact on integer inputs
    whose partial sums stay below 2^24."""
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.integers(0, 3, width).astype(np.float32))
    a = jnp.asarray(rng.integers(0, 3, width).astype(np.float32))
    active = jnp.asarray(rng.random(width) > 0.3)
    level = jnp.asarray(rng.integers(-1, 5, width).astype(np.int32))
    dw = jnp.asarray(rng.integers(0, 2, width).astype(np.float32))
    args = (w, a, active, level, dw, jnp.float32(1.0), jnp.int32(7))
    got = peel_round(*args, force="pallas")
    want = peel_round(*args, force="ref")
    for name, x, y in zip(("w", "active", "level", "peeled", "partials"),
                          got, want):
        require(np.array_equal(np.asarray(x), np.asarray(y)),
                f"peel_round kernel differs from its reference in {name}")
    say(f"kernel: peel_round Pallas == reference at V={width}")


def check_division(n: int = 1 << 22) -> None:
    """Densities are f32 quotients of integer masses by counts (DG) or
    of dyadic masses (other semantics): both kinds, against numpy."""
    rng = np.random.default_rng(1)
    f = np.concatenate([
        rng.integers(1, 2**24, n // 2).astype(np.float32),
        (rng.random(n // 2) * 2.0 ** rng.integers(-10, 30, n // 2))
        .astype(np.float32),
    ])
    cnt = rng.integers(1, GRAB.n_capacity + 2, n).astype(np.float32)
    want = (f / cnt).view(np.int32)
    raw = np.asarray(jax.jit(jnp.divide)(f, cnt)).view(np.int32)
    got = np.asarray(jax.jit(div_rn)(f, cnt)).view(np.int32)
    say(f"division: plain f32 divide differs from IEEE in "
        f"{int((raw != want).sum())} of {n} pairs on this device")
    bad = int((got != want).sum())
    require(bad == 0, f"div_rn differs from the IEEE quotient in {bad} "
            f"of {n} pairs")
    say(f"division: div_rn == IEEE quotient on {n} pairs")


def report_fields(rep) -> dict:
    return {k: getattr(rep, k) for k in COMPARED}


def require_same(label: str, a, b) -> None:
    fa, fb = report_fields(a), report_fields(b)
    require(fa == fb, f"{label}: reports differ: {fa} vs {fb}")
    say(f"{label}: identical {fa}")


def serve(stream, spec: EngineSpec, label: str, clock: CompileClock):
    c0, t0 = clock.seconds, time.perf_counter()
    rep = SpadeService(SEMANTICS, spec).run(stream)
    smoke_time(label, run=time.perf_counter() - t0,
               compile=clock.seconds - c0,
               mean_tick=rep.mean_tick_seconds)
    return rep


def tick_sizes(stream, batch: int) -> list[int]:
    n_inc = stream.inc_src.shape[0]
    return [min(batch, n_inc - i) for i in range(0, n_inc, batch)]


def check_served(rep, stream, spec: EngineSpec, label: str) -> None:
    """What any correct run of ``stream`` must report."""
    sizes = tick_sizes(stream, spec.effective_batch_edges)
    m_base = stream.base_src.shape[0]
    resident = sizes[-spec.window_ticks:] if spec.window_ticks else sizes
    require(rep.fraud_recall == 1.0,
            f"{label}: fraud_recall {rep.fraud_recall} != 1.0")
    require(math.isfinite(rep.final_g) and rep.final_g > 0,
            f"{label}: final_g {rep.final_g} not finite and positive")
    require(rep.n_ticks == len(sizes),
            f"{label}: {rep.n_ticks} ticks, stream has {len(sizes)}")
    require(rep.live_edges == m_base + sum(resident),
            f"{label}: live_edges {rep.live_edges} != "
            f"{m_base} + {sum(resident)}")
    if spec.workset:
        require(rep.n_workset_ticks + rep.n_fallback_ticks == rep.n_ticks,
                f"{label}: workset/fallback ticks do not cover the run")


def specs(batch: int, eps: float, max_rounds: int, window: int):
    base = EngineSpec(batch_edges=batch, eps=eps, max_rounds=max_rounds)
    return {
        "unbounded": base,
        "windowed-workset": windowed_workset(base, window),
    }


def windowed_workset(spec: EngineSpec, window: int) -> EngineSpec:
    return dataclasses.replace(spec, window_ticks=window, workset=True,
                               predictive=True)


def check_answers(clock: CompileClock) -> None:
    cpu = jax.devices("cpu")[0]
    cases = {
        "unbounded": (make_transaction_stream(**SMALL),
                      EngineSpec(batch_edges=SMALL_BATCH, eps=0.1,
                                 max_rounds=20)),
        "windowed-workset": (make_transaction_stream(**SMALL_HOT),
                             windowed_workset(
                                 EngineSpec(batch_edges=SMALL_HOT_BATCH,
                                            eps=0.1, max_rounds=20),
                                 SMALL_HOT_WINDOW)),
    }
    for mode, (stream, spec) in cases.items():
        on_chip = serve(stream, spec, f"answers {mode} tpu", clock)
        with jax.default_device(cpu):
            on_cpu = serve(stream, spec, f"answers {mode} cpu", clock)
        check_served(on_chip, stream, spec, f"answers {mode}")
        if spec.workset:
            require(on_chip.n_workset_ticks > 0
                    and on_chip.n_fallback_ticks > 0,
                    f"answers {mode}: {on_chip.n_workset_ticks} workset and "
                    f"{on_chip.n_fallback_ticks} fallback ticks; the check "
                    "needs both branches")
        require_same(f"answers {mode} tpu vs cpu", on_chip, on_cpu)


def grab4_stream(n_accounts: int = GRAB.n_capacity, seed: int = 0):
    """The Grab4 stream; fewer accounts scale the edges down with them."""
    t0 = time.perf_counter()
    m = GRAB.e_capacity * n_accounts // GRAB.n_capacity
    stream = make_transaction_stream(
        n=n_accounts, m=m,
        inc_fraction=(GRAB_TICKS * GRAB.batch_edges - FRAUD_EDGES) / m,
        fraud_edges=FRAUD_EDGES, seed=seed,
    )
    smoke_time("grab4 stream generation", setup=time.perf_counter() - t0)
    m_base, n_inc = stream.base_src.shape[0], stream.inc_src.shape[0]
    if n_accounts == GRAB.n_capacity:
        require(m_base >= 25_000_000,
                f"grab4 base holds only {m_base} edges")
    say(f"grab4 stream: {stream.n_vertices} vertices, {m_base} base edges, "
        f"{n_inc} streamed in "
        f"{len(tick_sizes(stream, GRAB.batch_edges))} ticks")
    return stream


def grab4_specs():
    return specs(GRAB.batch_edges, GRAB.eps, GRAB.max_rounds, WINDOW_TICKS)


def check_grab4(clock: CompileClock) -> None:
    stream = grab4_stream()
    for mode, spec in grab4_specs().items():
        rep = serve(stream, spec, f"grab4 {mode}", clock)
        check_served(rep, stream, spec, f"grab4 {mode}")
        say(f"grab4 {mode}: {report_fields(rep)}")


def check_mesh(n_chips: int, n_accounts: int, clock: CompileClock) -> None:
    mesh = make_mesh((n_chips,), ("data",), devices=jax.devices()[:n_chips])
    stream = grab4_stream(n_accounts)
    for mode, spec in grab4_specs().items():
        single = serve(stream, spec, f"mesh {mode} device 0", clock)
        sharded = serve(stream, dataclasses.replace(spec, mesh=mesh),
                        f"mesh {mode} {n_chips} chips", clock)
        check_served(single, stream, spec, f"mesh {mode} device 0")
        check_served(sharded, stream, spec, f"mesh {mode} {n_chips} chips")
        require_same(f"mesh {mode} sharded vs single", sharded, single)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="run only the edge-sharded phase on N chips")
    ap.add_argument("--accounts", type=int, default=GRAB.n_capacity,
                    metavar="A",
                    help="with --mesh: accounts in the stream (default: "
                         "Grab4 width)")
    args = ap.parse_args(argv)
    if args.accounts != GRAB.n_capacity and not args.mesh:
        ap.error("--accounts applies to --mesh only")

    dev = check_device(max(args.mesh, 1))
    say(f"compile cache: {use_compile_cache()}")
    clock = CompileClock()
    if args.mesh:
        check_mesh(args.mesh, args.accounts, clock)
    else:
        check_kernel(-(-(GRAB.n_capacity + 1) // 512) * 512)
        check_division()
        check_answers(clock)
        check_grab4(clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
