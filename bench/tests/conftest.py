"""Fixtures of the benchmark's own checks (CPU, small sizes).

Run from the repository root:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

``tiny_root`` is a copy of the benchmark's data (``BENCHMARK.json`` and
the configurations, mixes, kinds, readers and references under
``bench/``) in a temporary directory, with small cells added as new
files: the fused engine under each arrival kind, and the predictive
workset engine under a sliding window, so that a whole run fits the CPU
in seconds.
"""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

DATA_DIRS = ("configs", "traffic", "layers", "references")

TINY = {
    "accounts": 3000, "background_edges": 15000, "alpha": 0.3,
    "rings": 2, "ring_size": 12, "ring_edges": 1600,
    "streamed_edges": 1500, "semantics": "DG",
    "reference": "bench/references/spade_dg.py", "reduced": [],
}


def tiny_configs() -> dict[str, dict]:
    eng = {"eps": 0.1, "max_rounds": 20, "predictive": True,
           "refresh_every": 0}
    return {
        "tiny": dict(TINY, name="tiny", actors=1,
                     capacity_edges=131072,
                     engine=dict(eng, batch_edges=512, window_ticks=0,
                                 workset=False)),
        "tiny-window": dict(TINY, name="tiny-window", actors=1,
                            capacity_edges=None,
                            engine=dict(eng, batch_edges=256, window_ticks=4,
                                        workset=True)),
    }


def copy_bench_data(dst: Path) -> None:
    (dst / "bench").mkdir(parents=True)
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    for d in DATA_DIRS:
        shutil.copytree(ROOT / "bench" / d, dst / "bench" / d)


def add_cell(root: Path, cfg: dict, mix: dict, cell: str) -> None:
    """Add a configuration, a mix and a cell as new files and entries."""
    (root / "bench" / "configs" / f"{cfg['name']}.json").write_text(
        json.dumps(cfg))
    (root / "bench" / "traffic" / f"{mix['name']}.json").write_text(
        json.dumps(mix))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    if cfg["name"] not in {c["name"] for c in spec["configs"]}:
        spec["configs"].append({
            "name": cfg["name"], "source": "test", "why": "test",
            "file": f"bench/configs/{cfg['name']}.json", "reduced": []})
    spec["workloads"].append({"name": cell, "config": cfg["name"],
                              "traffic": mix["name"], "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        wl = m.get("workloads")
        if wl is None:
            continue
        base = {"tiny.backlog": "grab4.backlog", "tiny.open": "grab4.open",
                "tiny-window.open": "grab4.open"}.get(cell)
        if base in wl:
            wl.append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def tiny_mixes() -> dict[str, dict]:
    backlog = json.loads((ROOT / "bench/traffic/backlog.json").read_text())
    open_ = json.loads((ROOT / "bench/traffic/open.json").read_text())
    return {
        "tiny-backlog": dict(backlog, name="tiny-backlog"),
        "tiny-open": dict(open_, name="tiny-open", rate_edges_per_s=2000.0,
                          warmup_ticks="window+2"),
    }


@pytest.fixture
def tiny_root(tmp_path) -> Path:
    root = tmp_path / "checkout"
    copy_bench_data(root)
    cfgs, mixes = tiny_configs(), tiny_mixes()
    add_cell(root, cfgs["tiny"], mixes["tiny-backlog"], "tiny.backlog")
    add_cell(root, cfgs["tiny"], mixes["tiny-open"], "tiny.open")
    add_cell(root, cfgs["tiny-window"], mixes["tiny-open"],
             "tiny-window.open")
    return root
