"""Every device mesh of the repo is built here.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the 'pod' axis carries
pure data parallelism (params replicated across pods, FSDP within a pod)
so the slow inter-pod (DCN) hop only sees gradient all-reduces.
Edge-sharded fraud serving: ``make_mesh((n,), ("data",))``.

Axes are ``AxisType.Auto``: the engines place their arrays with
``NamedSharding`` / ``with_sharding_constraint`` and leave propagation to
GSPMD, which ``Explicit`` axes (what ``jax.make_mesh`` picks when no
types are given) refuse.

Defined as functions (never module-level constants) so importing this
module never touches jax device state.
"""

from __future__ import annotations

from typing import Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

__all__ = ["make_mesh", "make_production_mesh", "make_host_mesh",
           "SINGLE_POD", "MULTI_POD"]

SINGLE_POD = (16, 16)
MULTI_POD = (2, 16, 16)


def make_mesh(
    shape: Sequence[int], axes: Sequence[str], *, devices=None
) -> Mesh:
    """A mesh of ``shape`` over ``axes``, every axis ``AxisType.Auto``.

    ``devices`` (default: all of ``jax.devices()``, laid out by
    ``jax.make_mesh``) is taken in the given order; its length must equal
    the product of ``shape``.
    """
    types = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(tuple(shape), tuple(axes), axis_types=types)
    devs = np.asarray(devices).reshape(tuple(shape))
    return Mesh(devs, tuple(axes), axis_types=types)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh() -> Mesh:
    """Whatever fits the current host (smoke tests, examples)."""
    return make_mesh((len(jax.devices()), 1), ("data", "model"))
