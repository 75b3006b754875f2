"""A cell is data: a configuration, a traffic mix, a stream kind, an
arrival schedule and a per-layer reader added as new files under a
checkout, and entries in ``BENCHMARK.json``, are found by name and run,
and no file that was there changes."""

from __future__ import annotations

import hashlib
import json
import time

import pytest

from bench.harness import RunView, run_cell
from bench.spec import Bench

from conftest import DATA_DIRS, ROOT, add_cell


def _digests(root):
    files = [p for d in DATA_DIRS for p in (root / "bench" / d).rglob("*")
             if p.is_file() and "__pycache__" not in p.parts]
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in files}


def test_new_files_are_found_by_name_and_nothing_existing_changes(tiny_root):
    before = _digests(ROOT)
    metric = "edges_read.tiny"
    (tiny_root / "bench" / "layers" / f"{metric}.py").write_text(
        "def read(run):\n    return float(run.report.n_edges)\n")
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["per_layer"].append({
        "name": metric, "unit": "edges", "better": "higher",
        "source": "program_counter", "layer": "served loop",
        "moves": "edges_per_s", "workloads": ["tiny.backlog"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))

    bench = Bench(tiny_root)
    cell = bench.cell("tiny.backlog")
    assert cell.config["accounts"] == 3000
    assert cell.traffic["name"] == "tiny-backlog"
    assert metric in [m.name for m in cell.per_layer]
    assert [m.name for m in cell.end_to_end] == ["edges_per_s", "setup_s"]
    reader = bench.layer_reader(metric)
    assert reader.read(RunView(report=type("R", (), {"n_edges": 7})(),
                               window_ticks=1)) == 7.0

    out = run_cell(bench, "tiny.backlog", 2**31 + 1, 0.5, False,
                   time.perf_counter(), require_tpu=False)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"edges_per_s", "setup_s"}
    assert list(out)[-1] == "checks"
    # the repository's own files are untouched, and the copies equal them
    assert _digests(ROOT) == before
    copied = _digests(tiny_root)
    assert all(copied[k] == v for k, v in before.items())


@pytest.mark.parametrize("cell", ["tiny.open", "tiny-window.open"])
def test_open_loop_cell_reports_its_tail(tiny_root, cell):
    out = run_cell(Bench(tiny_root), cell, 2**31 + 2, 1.0,
                   False, time.perf_counter(), require_tpu=False)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"latency_p95_ms", "setup_s"}
    assert out["metrics"]["latency_p95_ms"]["value"] > 0
    assert out["attempted"] == 2000  # rate 2000/s for 1 s


RING_ONLY = '''
import numpy as np
from bench.generator import Streamed, ring_pairs


def make_streamed(base, mix, n_edges, rng):
    s, d = ring_pairs(rng, base.rings[mix["ring"]], n_edges)
    return Streamed(s, d, np.ones(n_edges))
'''

EVEN = '''
import numpy as np


def arrival_offsets(mix, n_edges, seconds, rng):
    return np.arange(n_edges) * (seconds / n_edges)
'''


def test_mix_of_a_new_kind_is_new_files(tiny_root):
    """A stream kind and an arrival schedule the benchmark has never had,
    each a new file, run through a new mix and cell."""
    before = _digests(ROOT)
    kinds = tiny_root / "bench" / "traffic" / "kinds"
    (kinds / "ring_only.py").write_text(RING_ONLY)
    (kinds / "even.py").write_text(EVEN)
    mix = {"name": "tiny-ring", "stream": "ring_only", "ring": 1,
           "arrivals": "even", "rate_edges_per_s": 1000.0,
           "warmup_ticks": 1}
    cfg = json.loads((tiny_root / "bench/configs/tiny.json").read_text())
    add_cell(tiny_root, cfg, mix, "tiny.ring")
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    for m in spec["end_to_end"]:
        if m["name"] == "latency_p95_ms":
            m["workloads"].append("tiny.ring")
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))

    bench = Bench(tiny_root)
    cell = bench.cell("tiny.ring")
    assert cell.streams.__file__.endswith("ring_only.py")
    assert cell.schedule.__file__.endswith("even.py")
    out = run_cell(bench, "tiny.ring", 2**31 + 3, 1.0, False,
                   time.perf_counter(), require_tpu=False)
    assert out["correct"] is True
    assert out["attempted"] == 1000
    assert set(out["metrics"]) == {"latency_p95_ms", "setup_s"}
    assert _digests(ROOT) == before


def test_unknown_names_are_errors(tiny_root):
    bench = Bench(tiny_root)
    with pytest.raises(KeyError):
        bench.cell("no.such.cell")
    with pytest.raises(FileNotFoundError):
        bench.traffic("no-such-mix")
    with pytest.raises(FileNotFoundError):
        bench.kind("no_such_kind")
