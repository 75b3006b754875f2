#!/usr/bin/env python3
"""The control of ``correct``: the reference in bfloat16, the precision
below the float32 the configurations state, put in the program's place,
at the cell's own size.  It has to come out as not correct.

    python3 bench/control.py --workload grab4.backlog --seeds 1 2 3 --ticks 2
    python3 bench/control.py --workload grab4.open --seeds 1 2 3 --seconds 51

For each seed it builds what a run of that seed streams (``--ticks``
backlogged ticks, or an open-loop mix's warm-up and ``--seconds`` of
arrivals), replays it through the reference in float32 and in bfloat16,
and prints each compared number of the bfloat16 answers beside its
limit: the upper readings the limits in PERF.md are set against.  It
needs no accelerator; the benchmark's own runs never run it.
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--ticks", type=int, default=1,
                    help="backlogged ticks in the window (backlog mixes)")
    ap.add_argument("--precision", default="bfloat16")
    args = ap.parse_args(argv)

    from bench import generator
    from bench.check import compare
    from bench.harness import as_report, engine_spec, measured_stream, replay
    from bench.spec import Bench

    bench = Bench(ROOT)
    cell = bench.cell(args.workload)
    spec = engine_spec(cell.config)
    for seed in args.seeds:
        t0 = time.perf_counter()
        base = generator.make_base(cell.config, seed)
        streamed = measured_stream(cell, base, spec, args.seconds, seed,
                                   args.ticks)
        ref = replay(bench, cell, base, streamed, spec)
        ctl = replay(bench, cell, base, streamed, spec,
                     precision=args.precision)
        n = streamed.src.shape[0]
        checks = compare(as_report(ctl, base, n), ref, base.fraud_accounts,
                         n, spec.workset)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "precision": args.precision, "seconds": time.perf_counter() - t0,
            "correct": all(c.ok for c in checks),
            "final_g": {"float32": ref.final_g, args.precision: ctl.final_g},
            "checks": {c.name: {"value": c.value, "limit": c.limit}
                       for c in checks},
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
