#!/usr/bin/env python3
"""Find the highest edge rate an open-loop cell sustains: a sweep.

    python3 bench/sweep.py --workload grab4.open --seed 5 \\
        --rates 130 150 170 --segment 60

One process and one ``run()``: the cell's warm-up ticks, then one
segment of ``--segment`` seconds of Poisson arrivals at each rate in
turn, slowest first.  For each segment it prints the offered rate, the
rate completed, and how the queue ahead of the system moved: the lag
between a tick's last arrival and the system's read of it.  At a rate
the system sustains the lag stays flat; above it the lag grows through
the segment.  The cell's traffic file then fixes its rate at about four
fifths of the highest rate sustained.  The benchmark's own runs never
run this.
"""

import argparse
import json
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--segment", type=float, default=30.0)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from bench import generator
    from bench.harness import (device_check, engine_spec, tx_stream,
                                warmup_ticks)
    from bench.source import Arrivals, Barriers
    from bench.spec import Bench
    from repro.launch.compile_cache import use_compile_cache
    from repro.serve import SpadeService

    use_compile_cache()
    cell = Bench(ROOT).cell(args.workload)
    dev = device_check(cell.chips, require_tpu=True)[0]
    spec = engine_spec(cell.config)
    batch = spec.effective_batch_edges
    base = generator.make_base(cell.config, args.seed)
    n_warm = warmup_ticks(cell.traffic, spec) * batch
    rng = generator.seeded(args.seed, generator.ARRIVALS)
    due, seg_of, t = [np.zeros(n_warm)], [np.full(n_warm, -1)], 0.0
    for i, rate in enumerate(sorted(args.rates)):
        k = int(rate * args.segment)
        due.append(t + np.sort(rng.uniform(0.0, args.segment, k)))
        seg_of.append(np.full(k, i))
        t += args.segment
    due, seg_of = np.concatenate(due), np.concatenate(seg_of)
    streamed = cell.streams.make_streamed(
        base, cell.traffic, due.shape[0],
        generator.seeded(args.seed, generator.WINDOW))
    barriers = Barriers(dev)
    try:
        arr = Arrivals(barriers, due, n_warm, name="sweep")
        with jax.default_device(dev):
            SpadeService(cell.config["semantics"], spec).run(
                tx_stream(base, streamed, arr))
        arr.finish()
        done = arr.completed()
        for i, rate in enumerate(sorted(args.rates)):
            reads = [r for r, (lo, hi) in enumerate(arr.bounds)
                     if hi > n_warm and seg_of[hi - 1] == i]
            lag = [arr.read_at[r] - (arr.origin + due[arr.bounds[r][1] - 1])
                   for r in reads]
            edges = sum(arr.bounds[r][1] - arr.bounds[r][0] for r in reads)
            span = done[reads[-1]] - done[reads[0] - 1] if reads else 0.0
            print(json.dumps({
                "rate_offered": rate, "ticks": len(reads),
                "rate_completed": edges / span if span > 0 else None,
                "lag_first_s": lag[0] if lag else None,
                "lag_last_s": lag[-1] if lag else None,
                "lag_max_s": max(lag) if lag else None,
            }), flush=True)
    finally:
        barriers.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
