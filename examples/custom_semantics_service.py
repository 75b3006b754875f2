"""A user-defined suspiciousness semantics on every engine — the paper's
§6 API promise, end to end.

We define a HoloScope-flavored **time-decayed** semantics: a transaction's
suspiciousness is its amount discounted by how far it sits from the
stream's detection horizon (``2^-(age / half_life)``), so evidence
concentrated in a recent burst dominates stale background mass — the
temporal-spike intuition behind HoloScope's weighting, expressed as a
Spade semantics in ~10 lines.  Spade incrementalizes it for free: the same
definition runs through

* the single-device sliding-window engine,
* the affected-area workset engine with the predictive bucket selector,
* the mesh-sharded engine over every device of the host (8 forced host
  devices on the CPU backend),

with **zero engine-file edits** — the hooks are compiled at the protocol
boundary (``seed_base`` / ``batch_weights``), never dispatched by name
inside an engine.

    PYTHONPATH=src python examples/custom_semantics_service.py
"""

import os

# on the CPU backend the mesh plane below runs on 8 forced host devices;
# must be set before jax init (accelerator backends ignore the flag)
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8 "
        + os.environ.get("XLA_FLAGS", "")
    ).strip()

import jax

from repro.core.semantics import SuspSemantics, register
from repro.graphstore.generators import make_transaction_stream
from repro.launch.mesh import make_mesh
from repro.serve import EngineSpec, SpadeService

stream = make_transaction_stream(n=5000, m=25000, seed=12)

# ---------------------------------------------------------------------------
# the custom semantics: amount x recency decay toward the stream horizon.
# `xp` is numpy on the host/seeding paths (float64, dyadic-snapped at the
# protocol boundary) and jax.numpy inside the jitted tick — one definition,
# every plane.  `aux` is the per-edge transaction timestamp the bundled
# services feed (base-graph edges carry t = 0).
# ---------------------------------------------------------------------------

HORIZON = float(stream.inc_time.max())
HALF_LIFE = 0.25 * HORIZON


def _decayed_amount(xp, src, dst, raw, in_deg_dst, t):
    age = HORIZON - (0.0 if t is None else t)
    return xp.maximum(raw, 1e-12) * 2.0 ** (-age / HALF_LIFE)


TDW = register(SuspSemantics(name="TDW", esusp=_decayed_amount, uses_aux=True))

# ---------------------------------------------------------------------------
# the same semantics through three engines (and DW as the undecayed control)
# ---------------------------------------------------------------------------

n_dev = len(jax.devices())
mesh = make_mesh((n_dev,), ("data",))
CONFIGS = [
    ("DW window-4", "DW",
     EngineSpec(batch_edges=512, max_rounds=20, refresh_every=16,
                window_ticks=4)),
    ("TDW window-4", "TDW",
     EngineSpec(batch_edges=512, max_rounds=20, refresh_every=16,
                window_ticks=4)),
    ("TDW workset-4", "TDW",
     EngineSpec(batch_edges=512, max_rounds=20, refresh_every=16,
                window_ticks=4, workset=True, predictive=True)),
    (f"TDW mesh-{n_dev}", "TDW",
     EngineSpec(batch_edges=512, max_rounds=20, refresh_every=16,
                window_ticks=4, mesh=mesh)),
]

print(f"{'engine':<14} {'recall':>7} {'final_g':>10} {'live':>7} "
      f"{'ms/tick':>8} {'ws/fb':>6} {'pred/miss':>10}")
for label, sem, spec in CONFIGS:
    rep = SpadeService(sem, spec).run(stream)
    print(f"{label:<14} {rep.fraud_recall:>7.2f} {rep.final_g:>10.1f} "
          f"{rep.live_edges:>7} {1e3 * rep.mean_tick_seconds:>8.1f} "
          f"{rep.n_workset_ticks:>3}/{rep.n_fallback_ticks:<2} "
          f"{rep.n_predicted_ticks:>5}/{rep.n_bucket_miss_ticks:<4}")

# the registry now knows the custom name everywhere a builtin works: the
# host oracle compiles the same hooks through its per-edge funnel
from repro.core import Spade  # noqa: E402

sp = Spade(metric="TDW")
sp.LoadGraph(stream.base_src[:2000], stream.base_dst[:2000],
             stream.base_amt[:2000], n_vertices=stream.n_vertices)
comm, g_best = sp.Detect()
print(f"\nhost oracle under TDW: g(S^P) = {g_best:.2f} "
      f"(community size {len(comm)})")
