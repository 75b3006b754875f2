"""What ``BENCHMARK.json`` names, found by name under the checkout.

A cell names a configuration and a traffic mix; each per-layer metric
names a reader.  Every one of them is a file of its own, so a later cell,
mix or metric is a new file and an entry in ``BENCHMARK.json``, and no
existing file changes:

* ``<root>/<config.file>``: the configuration (sizes, engine, reference),
* ``<root>/bench/traffic/<mix>.json``: the traffic mix (data),
* ``<root>/bench/traffic/kinds/<kind>.py``: the stream kind and the
  arrival schedule a mix names (``bench/generator.py`` says what each
  defines),
* ``<root>/bench/layers/<metric>.py``: a reader with ``read(run)``.
"""

from __future__ import annotations

import importlib.util
import json
import re
import sys
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType

__all__ = ["Bench", "Cell", "Metric", "ROOT"]

ROOT = Path(__file__).resolve().parent.parent


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    workloads: tuple[str, ...] | None  # None: every cell that reports `moves`
    moves: str | None = None

    def applies(self, cell: str, e2e: "tuple[Metric, ...]") -> bool:
        if self.workloads is not None:
            return cell in self.workloads
        if self.moves is None:
            return True
        return any(m.name == self.moves and m.applies(cell, e2e) for m in e2e)


@dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: dict
    streams: ModuleType  # the mix's stream kind: ``make_streamed``
    schedule: ModuleType | None  # open loop: ``arrival_offsets``
    chips: int
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]


def _metric(entry: dict) -> Metric:
    wl = entry.get("workloads")
    return Metric(name=entry["name"], unit=entry["unit"],
                  workloads=None if wl is None else tuple(wl),
                  moves=entry.get("moves"))


class Bench:
    """``BENCHMARK.json`` under ``root`` and the files it names."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        with open(self.root / "BENCHMARK.json") as f:
            self.spec = json.load(f)
        self.end_to_end = tuple(_metric(m) for m in self.spec["end_to_end"])
        self.per_layer = tuple(_metric(m) for m in self.spec["per_layer"])

    def cell_names(self) -> list[str]:
        return [w["name"] for w in self.spec["workloads"]]

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                with open(self.root / c["file"]) as f:
                    return json.load(f)
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, mix: str) -> dict:
        with open(self.root / "bench" / "traffic" / f"{mix}.json") as f:
            return json.load(f)

    def cell(self, name: str) -> Cell:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                break
        else:
            raise KeyError(f"no cell {name!r}; BENCHMARK.json has "
                           f"{', '.join(self.cell_names())}")
        e2e = tuple(m for m in self.end_to_end if m.applies(name, ()))
        mix = self.traffic(w["traffic"])
        return Cell(
            name=name, config=self.config(w["config"]), traffic=mix,
            streams=self.kind(mix["stream"]),
            schedule=None if mix["arrivals"] == "backlog"
            else self.kind(mix["arrivals"]),
            chips=int(w["chips"]),
            end_to_end=e2e,
            per_layer=tuple(m for m in self.per_layer
                            if m.applies(name, self.end_to_end)),
        )

    def kind(self, name: str) -> ModuleType:
        """A stream kind or an arrival schedule, by the name a mix gives."""
        return _load(self.root / "bench" / "traffic" / "kinds" / f"{name}.py",
                     "bench_kind")

    def layer_reader(self, metric: str) -> ModuleType:
        return _load(self.root / "bench" / "layers" / f"{metric}.py",
                     "bench_layer")

    def reference(self, cfg: dict) -> ModuleType:
        return _load(self.root / cfg["reference"], "bench_reference")


def _load(path: Path, kind: str) -> ModuleType:
    """Import a file by its path, under a name of its own."""
    name = f"{kind}_{re.sub(r'[^0-9A-Za-z_]', '_', path.stem)}"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod
