"""Elastic scaling + straggler mitigation.

*Elastic re-mesh*: on node loss, rebuild the largest valid mesh from the
surviving device count and reshard the latest checkpoint onto it
(``load_pytree(..., shardings=new)``).  Meshes are required to keep the
'model' axis intact (TP groups are not survivable); capacity changes are
absorbed by the 'data'/'pod' axes — the global batch is then re-split.

*Straggler mitigation*: a step-commit watchdog — if a step exceeds
``timeout x median(step_time)``, the driver marks the step lost, restores
from the last committed checkpoint, and (on a real cluster) excludes the
straggler host via the cluster agent hook.  Here the hook is injectable so
tests can simulate hangs.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from repro.launch.mesh import make_mesh

__all__ = ["best_mesh_for", "StepWatchdog", "ElasticPlan", "replan"]


def best_mesh_for(
    n_devices: int, model_axis: int, axis_names=("data", "model"), devices=None
) -> Mesh:
    """Largest (data, model) mesh with the TP axis preserved."""
    if n_devices < model_axis:
        raise ValueError(
            f"cannot preserve model axis {model_axis} with {n_devices} devices"
        )
    data = n_devices // model_axis
    devs = np.asarray(devices if devices is not None else jax.devices())[
        : data * model_axis
    ]
    return make_mesh((data, model_axis), axis_names, devices=devs)


@dataclasses.dataclass
class ElasticPlan:
    mesh: Mesh
    global_batch: int
    per_replica_batch: int


def replan(
    n_devices: int, model_axis: int, global_batch: int, devices=None
) -> ElasticPlan:
    """Recompute mesh + batch split after a capacity change; the global
    batch is preserved (gradient semantics unchanged) as long as it divides
    the new data-parallel degree."""
    mesh = best_mesh_for(n_devices, model_axis, devices=devices)
    dp = mesh.devices.shape[0]
    while global_batch % dp != 0:
        dp -= 1  # shrink dp by trimming stragglers off the mesh
        mesh = best_mesh_for(dp * model_axis, model_axis, devices=devices)
    return ElasticPlan(mesh=mesh, global_batch=global_batch,
                       per_replica_batch=global_batch // dp)


class StepWatchdog:
    """Detects straggling/hung steps by comparing against a running median."""

    def __init__(self, factor: float = 3.0, min_history: int = 5,
                 on_straggler: Callable[[int, float], None] | None = None):
        self.factor = factor
        self.min_history = min_history
        self.on_straggler = on_straggler
        self.history: list[float] = []
        self.flagged: list[int] = []

    def observe(self, step: int, seconds: float) -> bool:
        """Returns True if the step is deemed a straggler."""
        is_bad = False
        if len(self.history) >= self.min_history:
            med = float(np.median(self.history))
            if seconds > self.factor * med:
                is_bad = True
                self.flagged.append(step)
                if self.on_straggler:
                    self.on_straggler(step, seconds)
        if not is_bad:
            self.history.append(seconds)
            self.history = self.history[-128:]
        return is_bad
