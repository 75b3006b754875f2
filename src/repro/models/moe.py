"""Mixture-of-Experts FFN: top-k routing, shard-local dispatch, expert
all-to-all, optional virtual-expert split.

§Perf evolution (measured in EXPERIMENTS.md):

* v0 (baseline): one global capacity buffer, token scatter/gather across
  the whole batch.  GSPMD cannot keep a data-dependent scatter across
  sharded dims local — it replicates: ~11 TB/chip/step of all-gather +
  all-reduce on mixtral train_4k.
* v1 (current): tokens are routed within **token blocks** aligned to the
  data shards (TB = pod*data = 32).  The one-hot position cumsum and both
  scatters are per-block (shard-local); the only cross-chip movement is
  the [E, TB, Cb, D] buffer's expert<->data transpose — the classic MoE
  all-to-all, which is the *minimal* traffic for expert parallelism.
* virtual experts: when E < |model| (mixtral: 8 < 16), each expert's d_ff
  is split ``virtual_split`` ways and stacked on the expert axis so
  weights stay resident (EPxTP); partial w_down products are pair-summed.

Per-block capacity Cb = ceil(cf * tokens_per_block * K / E): stricter than
global capacity under imbalance (standard trade-off; the router aux loss
pushes toward balance).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoESpec
from repro.dist.sharding import axis_env, constrain

__all__ = ["moe_ffn", "router_aux_loss"]

_TOKEN_BLOCKS = 32  # pod * data


def moe_ffn(x: jax.Array, p: dict, spec: MoESpec) -> tuple[jax.Array, jax.Array]:
    """x: [T, D] flat tokens. p: router [D, E], w_gate/w_up [Ev, D, Fv],
    w_down [Ev, Fv, D]. Returns (out [T, D], aux router loss)."""
    T, D = x.shape
    E, K, vs = spec.n_experts, spec.top_k, spec.virtual_split
    TB = _TOKEN_BLOCKS if T % _TOKEN_BLOCKS == 0 else 1
    tp = T // TB
    Cb = max(1, int(spec.capacity_factor * tp * K / E))

    logits = x.astype(jnp.float32) @ p["router"].astype(jnp.float32)  # [T, E]
    topv, topi = jax.lax.top_k(logits, K)
    gates = jax.nn.softmax(topv, axis=-1)
    aux = router_aux_loss(logits, topi, E)

    A = tp * K
    assign_e = topi.reshape(TB, A)
    gate_b = gates.reshape(TB, A)
    keep_shape = assign_e.shape
    tok_b = jnp.repeat(jnp.arange(tp), K)  # [A] block-local token ids

    # block-local positions within each expert's capacity
    onehot = jax.nn.one_hot(assign_e, E, dtype=jnp.int32)  # [TB, A, E]
    pos = (jnp.cumsum(onehot, axis=1) * onehot).sum(-1) - 1  # [TB, A]
    keep = pos < Cb
    slot = jnp.where(keep, assign_e * Cb + pos, E * Cb)  # OOB -> dropped

    xb = x.reshape(TB, tp, D)

    def scatter_blocks(xb_l, slot_l):
        """Per-shard dispatch: plain local scatter (no GSPMD guessing)."""
        tbl = xb_l.shape[0]
        gathered = xb_l[:, tok_b]  # [tbl, A, D]
        rows = jnp.arange(tbl)[:, None]
        return jnp.zeros((tbl, E * Cb, D), xb_l.dtype).at[rows, slot_l].set(
            gathered, mode="drop"
        )

    env = axis_env()
    bx = env.resolve("batch") if env is not None else None
    if bx is not None and TB > 1:
        # shard_map pins the scatter to each data shard — v1 left it to
        # GSPMD, which replicated the [TB, E*Cb, D] buffer (measured ~2.5
        # TB/chip of all-gather on mixtral train; §Perf v2)
        buf = shard_map(
            scatter_blocks, mesh=env.mesh,
            in_specs=(P(bx, None, None), P(bx, None)),
            out_specs=P(bx, None, None), check_vma=False,
        )(xb, slot)
    else:
        buf = scatter_blocks(xb, slot)
    buf = constrain(buf, "batch", None, None)
    # expert <-> data transpose: THE all-to-all
    buf = buf.reshape(TB, E, Cb, D).transpose(1, 0, 2, 3)  # [E, TB, Cb, D]

    if vs > 1:
        buf_v = jnp.broadcast_to(buf[:, None], (E, vs, TB, Cb, D)).reshape(
            E * vs, TB, Cb, D
        )
        buf_v = constrain(buf_v, "expert", "batch", None, None)
        h = jax.nn.silu(jnp.einsum("ebcd,edf->ebcf", buf_v, p["w_gate"])) * jnp.einsum(
            "ebcd,edf->ebcf", buf_v, p["w_up"]
        )
        y_v = jnp.einsum("ebcf,efd->ebcd", h, p["w_down"])  # partial over F-split
        y_v = constrain(y_v, "expert", "batch", None, None)
        y = y_v.reshape(E, vs, TB, Cb, D).sum(axis=1)
    else:
        axes = ("expert", "batch", None, None) if spec.expert_parallel else (
            None, "batch", None, None)
        buf = constrain(buf, *axes)
        h = jax.nn.silu(jnp.einsum("ebcd,edf->ebcf", buf, p["w_gate"])) * jnp.einsum(
            "ebcd,edf->ebcf", buf, p["w_up"]
        )
        y = jnp.einsum("ebcf,efd->ebcd", h, p["w_down"])
        y = constrain(y, *axes)

    y = y.transpose(1, 0, 2, 3).reshape(TB, E * Cb, D)  # back: all-to-all
    y = constrain(y, "batch", None, None)

    def gather_blocks(y_l, slot_l, gk_l):
        tbl = y_l.shape[0]
        rows = jnp.arange(tbl)[:, None]
        contrib = y_l.at[rows, slot_l].get(mode="fill", fill_value=0.0)
        contrib = contrib * gk_l[..., None]
        return jnp.zeros((tbl, tp, D), y_l.dtype).at[rows, tok_b[None, :]].add(contrib)

    gk = (gate_b * keep).astype(y.dtype)
    if bx is not None and TB > 1:
        out = shard_map(
            gather_blocks, mesh=env.mesh,
            in_specs=(P(bx, None, None), P(bx, None), P(bx, None)),
            out_specs=P(bx, None, None), check_vma=False,
        )(y, slot, gk)
    else:
        out = gather_blocks(y, slot, gk)
    return out.reshape(T, D).astype(x.dtype), aux


def router_aux_loss(logits: jax.Array, topi: jax.Array, n_experts: int) -> jax.Array:
    """Switch-style load-balancing loss: E * <frac_tokens, frac_probs>."""
    probs = jax.nn.softmax(logits, axis=-1)
    frac_probs = probs.mean(axis=0)
    counts = jnp.zeros(n_experts, jnp.float32).at[topi.reshape(-1)].add(1.0)
    frac_tokens = counts / jnp.maximum(counts.sum(), 1.0)
    return n_experts * jnp.sum(frac_probs * frac_tokens)
