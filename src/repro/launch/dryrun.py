import os

# a CPU tool: lowers against 512 forced host devices and never takes an
# accelerator, even on a host that has one
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS_EXTRA", "")
)

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell with ShapeDtypeStruct inputs (zero allocation), print
``memory_analysis()`` (proves fit) and ``cost_analysis()`` (feeds
§Roofline), and parse the HLO for collective bytes.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral-8x7b \
      --shape train_4k --mesh multi
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
      --out results/dryrun
"""

import argparse
import json
import re
import time
import traceback

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_FAMILY, ARCHS, Skip, arch_shapes
from repro.dist.sharding import AxisEnv, tree_shardings, use_axis_env
from repro.launch.cells import Cell, build_cell
from repro.launch.mesh import make_production_mesh

# TPU v5e per-chip constants (targets; this container is CPU-only)
PEAK_FLOPS = 197e12  # bf16
HBM_BW = 819e9
ICI_BW = 50e9

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8, "s32": 4,
    "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "c64": 8,
    "c128": 16,
}


def _bytes_of_shape(txt: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(txt):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum output-shape bytes of every collective op in the (SPMD-partitioned)
    HLO.  Shapes in the post-SPMD module are per-shard; multiplying by the
    participating device count happens in the roofline (we report per-chip
    link bytes, so per-shard is what we want)."""
    out: dict[str, int] = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        # match "<name> = <shape> <op>(" forms, e.g. "%ag = bf16[2,4]{...} all-gather("
        for c in _COLLECTIVES:
            # count -start (async) or plain (sync) forms once; skip -done
            # (the wait handle, not a second transfer)
            if re.search(rf"(?:^|\s){c}(?:-start)?\(", s) and f"{c}-done" not in s:
                lhs = s.split("=", 1)
                shape_txt = lhs[1] if len(lhs) > 1 else s
                shape_txt = shape_txt.split(c)[0]
                out[c] += _bytes_of_shape(shape_txt)
                break
    return out


def run_cell(arch: str, shape: str, mesh_kind: str, verbose: bool = True,
             roofline: bool = False, override_layers: int | None = None) -> dict:
    """One (arch, shape, mesh) lowering.  ``roofline=True`` compiles the
    unrolled analysis variant (single-pod only) whose cost_analysis has
    exact trip-count accounting; the default production variant proves
    compilability + memory fit."""
    spec = arch_shapes(arch)[shape]
    if isinstance(spec, Skip):
        return {"arch": arch, "shape": shape, "mesh": mesh_kind,
                "status": "SKIP", "reason": spec.reason}
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    env = AxisEnv(mesh=mesh)
    try:
        with use_axis_env(env), mesh:
            cell: Cell = build_cell(arch, shape, concrete=False, roofline=roofline,
                                    override_layers=override_layers)
            in_sh = tree_shardings(cell.in_logical)
            jitted = jax.jit(
                cell.fn,
                in_shardings=in_sh,
                donate_argnums=cell.donate,
            )
            lowered = jitted.lower(*cell.args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
            coll = collective_bytes(hlo)
        n_chips = int(np.prod(mesh.devices.shape))
        # cost_analysis under SPMD reports PER-DEVICE flops/bytes (verified
        # empirically: an 8-way-sharded matmul reports 1/8 of the total);
        # collective bytes parsed from the post-SPMD HLO are per-shard too.
        flops = float(cost.get("flops", 0.0))
        bytes_acc = float(cost.get("bytes accessed", 0.0))
        coll_total = float(sum(coll.values()))
        t_compute = flops / PEAK_FLOPS
        t_memory = bytes_acc / HBM_BW
        t_coll = coll_total / ICI_BW
        dominant = max(
            [("compute", t_compute), ("memory", t_memory), ("collective", t_coll)],
            key=lambda kv: kv[1],
        )[0]
        result = {
            "arch": arch, "shape": shape, "mesh": mesh_kind, "status": "OK",
            "variant": "roofline" if roofline else "production",
            "n_chips": n_chips,
            "flops_per_chip": flops,
            "bytes_per_chip": bytes_acc,
            "collective_bytes_per_chip": coll_total,
            "collectives": coll,
            "t_compute_s": t_compute,
            "t_memory_s": t_memory,
            "t_collective_s": t_coll,
            "dominant": dominant,
            "model_flops": cell.model_flops,
            "useful_flops_ratio": (
                cell.model_flops / (flops * n_chips) if flops > 0 else 0.0
            ),
            "bytes_per_device": getattr(mem, "temp_size_in_bytes", None),
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "step_name": cell.step_name,
        }
        if verbose:
            print(f"[{arch} x {shape} x {mesh_kind}] OK "
                  f"compute={t_compute:.3e}s memory={t_memory:.3e}s "
                  f"coll={t_coll:.3e}s dominant={dominant} "
                  f"args={result['argument_bytes']} temp={result['bytes_per_device']} "
                  f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
            print(f"  memory_analysis: {mem}")
        return result
    except Exception as e:
        if verbose:
            traceback.print_exc()
        return {"arch": arch, "shape": shape, "mesh": mesh_kind,
                "status": "FAIL", "error": f"{type(e).__name__}: {e}"}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS)
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--family", choices=["lm", "gnn", "recsys", "spade"])
    ap.add_argument("--out", default=None, help="directory for per-cell JSON")
    ap.add_argument("--roofline", action="store_true",
                    help="compile the unrolled analysis variant (single-pod)")
    args = ap.parse_args()
    if args.roofline:
        args.mesh = "single"

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells: list[tuple[str, str]] = []
    if args.all or args.family:
        for arch in ARCHS:
            if args.family and ARCH_FAMILY[arch] != args.family:
                continue
            for shape in arch_shapes(arch):
                cells.append((arch, shape))
    else:
        if not (args.arch and args.shape):
            ap.error("--arch/--shape or --all/--family required")
        cells.append((args.arch, args.shape))

    failures = 0
    for arch, shape in cells:
        for mk in meshes:
            res = run_cell(arch, shape, mk, roofline=args.roofline)
            if res["status"] == "FAIL":
                failures += 1
                print(f"[{arch} x {shape} x {mk}] FAIL: {res['error']}")
            if args.out:
                os.makedirs(args.out, exist_ok=True)
                suffix = "roofline" if args.roofline else mk
                fn = os.path.join(args.out, f"{arch}__{shape}__{suffix}.json")
                with open(fn, "w") as f:
                    json.dump(res, f, indent=1)
    print(f"dry-run done: {len(cells) * len(meshes)} cells, {failures} failures")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
