"""Streaming fraud-detection serving driver (the paper's deployment),
routed through the :class:`repro.serve.SpadeService` facade — every plane
is reachable from the CLI:

    # host oracle (exact per-edge reorders, edge grouping)
    PYTHONPATH=src python -m repro.launch.serve --plane host \
        --semantics FD --edges 5000 --batch 100 --grouping

    # device plane, sliding window + predictive workset engine
    PYTHONPATH=src python -m repro.launch.serve --semantics DW \
        --batch 512 --window 8 --workset --refresh-every 32

    # mesh-sharded (force host devices on CPU):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m repro.launch.serve --mesh 8 --batch 512
"""

from __future__ import annotations

import argparse

from repro.core.semantics import available
from repro.graphstore.generators import make_transaction_stream
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.serve import EngineSpec, SpadeService


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--semantics", "--metric", dest="semantics",
                    choices=list(available()), default="DW",
                    help="registered suspiciousness semantics "
                         "(--metric is the deprecated alias)")
    ap.add_argument("--plane", choices=["device", "host"], default="device")
    ap.add_argument("--vertices", type=int, default=20000)
    ap.add_argument("--edges", type=int, default=80000)
    ap.add_argument("--batch", type=int, default=0,
                    help="edges per tick (0: plane default — 1 on host, "
                         "1024 on device)")
    ap.add_argument("--grouping", action="store_true",
                    help="host plane: benign/urgent edge grouping")
    ap.add_argument("--mesh", type=int, default=0,
                    help="shard edge buffers over N devices (device plane)")
    ap.add_argument("--window", type=int, default=0,
                    help="sliding window depth in ticks (device plane)")
    ap.add_argument("--workset", action="store_true",
                    help="affected-area workset engine (device plane)")
    ap.add_argument("--no-predictive", action="store_true",
                    help="workset: synced-scalar bucket selection instead "
                         "of the predictive selector")
    ap.add_argument("--refresh-every", type=int, default=0)
    ap.add_argument("--max-rounds", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    use_compile_cache()
    stream = make_transaction_stream(n=args.vertices, m=args.edges,
                                     seed=args.seed)
    if args.plane == "host":
        device_flags = [name for name, on in [
            ("--mesh", args.mesh), ("--window", args.window),
            ("--workset", args.workset),
            ("--no-predictive", args.no_predictive),
            ("--refresh-every", args.refresh_every),
        ] if on]
        if device_flags:
            ap.error(f"{', '.join(device_flags)} require --plane device")
        spec = EngineSpec(
            plane="host",
            grouping=args.grouping,
            batch_edges=args.batch or None,
        )
    else:
        mesh = None
        if args.mesh:
            mesh = make_mesh((args.mesh,), ("data",))
        spec = EngineSpec(
            plane="device",
            mesh=mesh,
            batch_edges=args.batch or None,
            window_ticks=args.window,
            workset=args.workset,
            predictive=not args.no_predictive,
            refresh_every=args.refresh_every,
            max_rounds=args.max_rounds,
        )
    rep = SpadeService(semantics=args.semantics, spec=spec).run(stream)
    if args.plane == "host":
        print(f"edges={rep.n_edges} reorders={rep.n_reorders} "
              f"us/edge={rep.mean_us_per_edge:.1f} "
              f"recall={rep.fraud_recall:.2f} "
              f"prevention={rep.prevention_ratio} "
              f"latency_s={rep.detection_latency_s}")
    else:
        print(f"edges={rep.n_edges} ticks={rep.n_ticks} "
              f"us/edge={rep.mean_us_per_edge:.1f} "
              f"recall={rep.fraud_recall:.2f} g={rep.final_g:.1f} "
              f"live={rep.live_edges} "
              f"ws/fb={rep.n_workset_ticks}/{rep.n_fallback_ticks} "
              f"pred/miss={rep.n_predicted_ticks}/{rep.n_bucket_miss_ticks}")
        if rep.round_vertices is not None and rep.round_vertices.size:
            # the single-device engines' counters: rounds that started
            # with an empty restricted set, and live edges and streamed
            # slots over the slots full-buffer rounds would read
            dead = int((rep.round_vertices == 0).sum())
            full = rep.round_edges.size * rep.edge_slots
            live = rep.round_edges.sum() / full
            streamed = rep.round_slots.sum() / full
            print(f"rounds={rep.round_vertices.size} dead_rounds={dead} "
                  f"live_slot_share={100 * live:.2f}% "
                  f"streamed_slot_share={100 * streamed:.2f}%")


if __name__ == "__main__":
    main()
