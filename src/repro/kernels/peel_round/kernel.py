"""Pallas TPU fused peel-round kernel: the elementwise half of one bulk-
peeling round (threshold compare + weight/mask update), vertex-tiled.

One bulk round is  (1) peeled = active & (w <= thresh)  and  (2) the
SpMV  dw[v] = sum_{(u,v) alive, u peeled} c_uv  (which IS
``gather_segsum`` with F=1 and x = peeled-indicator).  This kernel fuses
step (1) with the state update of step (2)'s output — one VMEM pass over
the vertex arrays instead of four XLA elementwise kernels:

    peeled     = active & (w <= thresh)
    w'         = w - dw
    active'    = active & ~peeled
    level'     = peeled ? round : level
    partials   = [sum(peeled a), sum(peeled w), n_peeled]   (for f/n update)

Grid: vertex tiles of ``block`` lanes.  Each tile writes its partial-sum
triple into lanes 0..2 of row 0 of one (8, 128) f32 output tile, the
smallest block the TPU's tiling admits, and the wrapper keeps one triple
per tile.  Validated in interpret mode against ``ref.py``; compiled for
a v5e chip in ``tests/test_chip_compile.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["peel_round_update"]

_PART_TILE = (8, 128)


def _kernel(w_ref, a_ref, active_ref, level_ref, dw_ref, thresh_ref, round_ref,
            w_out, active_out, level_out, peeled_out, partial_out):
    w = w_ref[...]
    active = active_ref[...]
    thresh = thresh_ref[0]
    peeled = jnp.logical_and(active, w <= thresh)
    pf = peeled.astype(jnp.float32)
    w_out[...] = w - dw_ref[...]
    active_out[...] = jnp.logical_and(active, jnp.logical_not(peeled))
    level_out[...] = jnp.where(peeled, round_ref[0], level_ref[...])
    peeled_out[...] = peeled
    # the tile's three partial sums in lanes 0..2 of row 0 of an (8, 128)
    # tile: the smallest f32 block the TPU's (8, 128) tiling admits
    row = jax.lax.broadcasted_iota(jnp.int32, _PART_TILE, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, _PART_TILE, 1)
    tile = jnp.where(lane == 0, jnp.sum(pf * a_ref[...]), 0.0)
    tile = jnp.where(lane == 1, jnp.sum(pf * w), tile)
    tile = jnp.where(lane == 2, jnp.sum(pf), tile)
    partial_out[...] = jnp.where(row == 0, tile, 0.0)


def peel_round_update(
    w: jax.Array,  # [V] f32 peel weights
    a: jax.Array,  # [V] f32 vertex suspiciousness
    active: jax.Array,  # [V] bool
    level: jax.Array,  # [V] i32
    dw: jax.Array,  # [V] f32 (from the SpMV over peeled frontier)
    thresh: jax.Array,  # scalar f32
    round_: jax.Array,  # scalar i32
    *,
    block: int = 8 * 128 * 8,
    interpret: bool = False,
):
    """Returns (w', active', level', peeled, partials [n_tiles, 3])."""
    V = w.shape[0]
    nb = -(-V // block)
    pad = nb * block - V
    if pad:
        w = jnp.pad(w, (0, pad))
        a = jnp.pad(a, (0, pad))
        active = jnp.pad(active, (0, pad))
        level = jnp.pad(level, (0, pad))
        dw = jnp.pad(dw, (0, pad))
    thresh = jnp.reshape(thresh.astype(jnp.float32), (1,))
    round_ = jnp.reshape(round_.astype(jnp.int32), (1,))

    vec = lambda: pl.BlockSpec((block,), lambda i: (i,))
    scl = lambda: pl.BlockSpec((1,), lambda i: (0,))
    outs = pl.pallas_call(
        _kernel,
        grid=(nb,),
        in_specs=[vec(), vec(), vec(), vec(), vec(), scl(), scl()],
        out_specs=[
            vec(), vec(), vec(), vec(),
            pl.BlockSpec(_PART_TILE, lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb * block,), jnp.float32),
            jax.ShapeDtypeStruct((nb * block,), jnp.bool_),
            jax.ShapeDtypeStruct((nb * block,), jnp.int32),
            jax.ShapeDtypeStruct((nb * block,), jnp.bool_),
            jax.ShapeDtypeStruct((nb * _PART_TILE[0], _PART_TILE[1]),
                                 jnp.float32),
        ],
        interpret=interpret,
    )(w, a, active, level, dw, thresh, round_)
    w2, active2, level2, peeled, partials = outs
    partials = partials.reshape(nb, *_PART_TILE)[:, 0, :3]
    return w2[:V], active2[:V], level2[:V], peeled[:V], partials
