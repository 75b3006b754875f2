"""Pallas TPU block-sparse gather+segment-sum (SpMM): the message-passing /
embedding-bag / peel-round primitive.

TPU adaptation (DESIGN.md §2): element-wise scatter-add is hostile to the
MXU/VPU, so edges are pre-bucketed into dense 128x128 adjacency tiles
(block-CSR).  The kernel walks tiles sorted by destination block; a
*scalar-prefetch* index vector selects the source x-block and destination
out-block per step (block-level gather/scatter — the Mosaic-friendly form
of sparse indexing), and each step is one MXU matmul:

    out[tile_dst[t]] += tiles[t][128, 128] @ x[tile_src[t]]   # x-block [128, F]

Output-block revisiting across consecutive grid steps keeps the
accumulator in VMEM; a first-visit flag zero-initializes it.  Power-law
graphs give sparse tiles — the preprocessing (ops.py) reports tile
occupancy, and graph reordering (degree sort) is the documented
mitigation.  Validated in interpret mode against ``ref.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["block_spmm"]


def _kernel(src_blk_ref, dst_blk_ref, first_ref, tiles_ref, x_ref, o_ref):
    t = pl.program_id(1)  # grid = (nf, T): tiles are the inner axis

    @pl.when(first_ref[t] == 1)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = tiles_ref[0].astype(jnp.float32)  # [bs, bs]
    x = x_ref[0].astype(jnp.float32)  # [bs, f_tile]
    o_ref[...] += jax.lax.dot_general(
        a, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )[None].astype(o_ref.dtype)


def block_spmm(
    tiles: jax.Array,  # [T, bs, bs] dense tile values, A[dst_local, src_local]
    tile_src: jax.Array,  # [T] int32 source block ids
    tile_dst: jax.Array,  # [T] int32 destination block ids (sorted, gapless)
    first_visit: jax.Array,  # [T] int32, 1 where tile_dst changes
    x: jax.Array,  # [n_src_blocks * bs, F]
    n_out_blocks: int,
    *,
    f_tile: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """out[tile_dst[t]] += tiles[t] @ x[tile_src[t]] over all tiles.

    Requires tiles sorted by ``tile_dst`` with every output block visited
    at least once (ops.py inserts zero tiles for empty blocks so the
    zero-init fires everywhere).
    """
    T, bs, _ = tiles.shape
    F = x.shape[1]
    nf = -(-F // f_tile)
    pad = nf * f_tile - F
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    xb = x.reshape(-1, bs, nf * f_tile)

    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # (tile_src, tile_dst, first_visit)
            grid=(nf, T),
            in_specs=[
                pl.BlockSpec((1, bs, bs), lambda f, t, src, dst, first: (t, 0, 0)),
                pl.BlockSpec(
                    (1, bs, f_tile), lambda f, t, src, dst, first: (src[t], 0, f)
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, bs, f_tile), lambda f, t, src, dst, first: (dst[t], 0, f)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((n_out_blocks, bs, nf * f_tile), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(tile_src, tile_dst, first_visit, tiles, xb)
    out = out.reshape(n_out_blocks * bs, nf * f_tile)
    return out[:, :F]
