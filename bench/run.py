#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator this machine holds.

    python3 bench/run.py --workload grab4.backlog --seed 7 --seconds 51 --trace 0

The cell, its configuration, its traffic mix and its per-layer metrics
are found by name through ``BENCHMARK.json`` (``bench/spec.py``).  With
``--trace 0`` the result reports the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
same run.  Progress goes to standard output first; its last line is the
result, one JSON object.  The numbers compared for ``correct`` end
standard error, each with its limit.

Exits 3 with no result when JAX's first device is not a TPU, or when the
machine holds fewer chips than the cell asks for.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="a cell of BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness import NoDevice, run_cell
    from bench.spec import Bench
    from repro.launch.compile_cache import use_compile_cache

    bench = Bench(ROOT)
    print(f"compile cache: {use_compile_cache()}", flush=True)
    try:
        result = run_cell(bench, args.workload, args.seed, args.seconds,
                          bool(args.trace), T_START)
    except NoDevice as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
