"""Benchmark entry point: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick]

Prints ``name,us_per_call,derived`` CSV (derived: speedup/ratio per row).
Every row runs in this one process.  The sharded rows use every device
the backend has; on the CPU backend (``JAX_PLATFORMS=cpu``) the host is
first split into ``--devices`` forced host devices.
The dry-run artifacts are produced separately by ``repro.launch.dryrun``
(512 host devices).
"""

from __future__ import annotations

import argparse
import os


def _force_host_devices(n: int) -> None:
    """Split the CPU backend into ``n`` devices; must precede jax backend
    init.  Accelerator platforms are left as they are."""
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0] != "cpu":
        return
    if "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", ""):
        return
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n} "
        + os.environ.get("XLA_FLAGS", "")
    ).strip()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="smaller graphs")
    ap.add_argument("--devices", type=int, default=8,
                    help="forced host devices on the CPU backend")
    ap.add_argument("--sharded-only", action="store_true",
                    help="only the dist-plane rows (BENCH_dist.json)")
    ap.add_argument("--workset-only", action="store_true",
                    help="only the workset-engine rows (BENCH_workset.json; "
                         "the CI smoke lane)")
    args = ap.parse_args()

    _force_host_devices(args.devices)
    import jax

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    from benchmarks.paper_tables import bench_sharded_peel

    skw = dict(n=20_000, m=80_000) if args.quick else {}
    skw["n_devices"] = len(jax.devices())

    rows = []
    if args.workset_only:
        from benchmarks.paper_tables import bench_workset

        wskw = (dict(n=20_000, m=80_000, batch=512, window=4)
                if args.quick else {})
        rows += bench_workset(**wskw)
    elif args.sharded_only:
        rows += bench_sharded_peel(**skw)
    else:
        from benchmarks.paper_tables import (
            bench_device_plane,
            bench_edge_grouping,
            bench_incremental_speedup,
            bench_prevention,
            bench_window,
            bench_workset,
        )

        kw = dict(n=4000, m=20000, n_inc=600) if args.quick else {}
        rows += bench_incremental_speedup(**kw)
        rows += bench_edge_grouping(**kw)
        rows += bench_prevention()
        rows += bench_device_plane()
        wkw = dict(n=20_000, m=80_000, batch=512, window=4) if args.quick else {}
        rows += bench_window(**wkw)
        rows += bench_workset(**wkw)
        rows += bench_sharded_peel(**skw)

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.2f},{derived:.4f}")


if __name__ == "__main__":
    main()
