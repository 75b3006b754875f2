"""Pallas TPU flash-attention forward (blocked online softmax, GQA).

Grid: (B*Hq, nQ, nK); the kv axis is the innermost ("arbitrary") dimension
so the (m, l, acc) online-softmax state lives in VMEM scratch across kv
steps.  BlockSpecs tile q/k/v/o into VMEM:

  q: [1, Bq, D]   k/v: [1, Bk, D]   o: [1, Bq, D]

GQA is handled in the k/v index maps (kv head = q head // G) — no
repeat-materialization of k/v in HBM.  Causal / sliding-window masking is
applied per tile; fully-masked tiles skip the matmuls via ``pl.when``.

Targets TPU (MXU-aligned Bq/Bk/D multiples of 128); validated on CPU in
interpret mode against ``ref.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_fwd"]

_NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, causal, window, n_kv, bq, bk, seq_q, seq_kv):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = k_pos < seq_kv
    if causal:
        ok &= k_pos <= q_pos
    if window is not None:
        ok &= k_pos > q_pos - window

    # a tile is live unless every position is masked; for causal grids this
    # skips the strictly-upper-triangular tiles (real FLOP savings on TPU)
    live = jnp.logical_not(causal) | (ki * bk <= qi * bq + bq - 1)
    if window is not None:
        live &= (qi * bq - window) < ((ki + 1) * bk - 1) + bq

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32)  # [bq, d]
        k = k_ref[0].astype(jnp.float32)  # [bk, d]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        s = jnp.where(ok, s, _NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(
            o_ref.dtype
        )


def flash_attention_fwd(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """q: [B, Hq, Sq, D]; k/v: [B, Hkv, Skv, D] -> [B, Hq, Sq, D]."""
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    assert Hq % Hkv == 0
    G = Hq // Hkv
    bq, bk = min(block_q, Sq), min(block_k, Skv)
    nq, nk = -(-Sq // bq), -(-Skv // bk)
    pq, pk = nq * bq - Sq, nk * bk - Skv
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    qf = q.reshape(B * Hq, nq * bq, D)
    kf = k.reshape(B * Hkv, nk * bk, D)
    vf = v.reshape(B * Hkv, nk * bk, D)

    def kv_map(bh, qi, ki):
        return ((bh // Hq) * Hkv + (bh % Hq) // G, ki, 0)

    out = pl.pallas_call(
        functools.partial(
            _kernel,
            scale=1.0 / (D ** 0.5),
            causal=causal,
            window=window,
            n_kv=nk,
            bq=bq,
            bk=bk,
            seq_q=Sq,
            seq_kv=Skv,
        ),
        grid=(B * Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, D), kv_map),
            pl.BlockSpec((1, bk, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, nq * bq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, Hq, nq * bq, D)[:, :, :Sq]
