"""Pallas kernel validation: interpret-mode execution vs pure-jnp oracles,
swept over shapes and dtypes (per-kernel allclose)."""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.gather_segsum.ops import build_tiles, gather_segsum
from repro.kernels.gather_segsum.ref import spmm_ref
from repro.kernels.peel_round.kernel import peel_round_update
from repro.kernels.peel_round.ref import peel_round_ref

# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

ATTN_SWEEP = [
    # (B, Hq, Hkv, Sq, Skv, D, causal, window, dtype)
    (1, 2, 2, 128, 128, 64, True, None, jnp.float32),
    (2, 4, 2, 256, 256, 64, True, None, jnp.float32),
    (1, 8, 2, 128, 128, 128, True, None, jnp.float32),
    (1, 2, 1, 256, 256, 64, False, None, jnp.float32),
    (1, 4, 4, 384, 384, 64, True, 128, jnp.float32),  # sliding window
    (1, 2, 2, 200, 200, 64, True, None, jnp.float32),  # ragged (padding)
    (1, 2, 2, 128, 128, 64, True, None, jnp.bfloat16),
]


@pytest.mark.parametrize(
    "B,Hq,Hkv,Sq,Skv,D,causal,window,dtype", ATTN_SWEEP,
    ids=[f"attn{i}" for i in range(len(ATTN_SWEEP))],
)
def test_flash_attention_interpret_vs_ref(B, Hq, Hkv, Sq, Skv, D, causal, window, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (B, Hq, Sq, D), dtype)
    k = jax.random.normal(k2, (B, Hkv, Skv, D), dtype)
    v = jax.random.normal(k3, (B, Hkv, Skv, D), dtype)
    got = flash_attention_fwd(q, k, v, causal=causal, window=window,
                              block_q=128, block_k=128, interpret=True)
    want = attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol, rtol=tol
    )


def test_flash_attention_matches_model_attention():
    """The kernel and the model's jnp flash implementation agree."""
    from repro.models.attention import flash_attention as model_flash

    B, Hq, Hkv, S, D = 1, 4, 2, 256, 64
    G = Hq // Hkv
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(k1, (B, S, Hkv, G, D), jnp.float32)
    k = jax.random.normal(k2, (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(k3, (B, S, Hkv, D), jnp.float32)
    got_model = model_flash(q, k, v, causal=True, q_block=128, kv_block=128)
    qk = q.transpose(0, 2, 3, 1, 4).reshape(B, Hq, S, D)
    got_kernel = flash_attention_fwd(qk, k.transpose(0, 2, 1, 3),
                                     v.transpose(0, 2, 1, 3),
                                     causal=True, block_q=128, block_k=128,
                                     interpret=True)
    want = got_kernel.reshape(B, Hkv, G, S, D).transpose(0, 3, 1, 2, 4)
    np.testing.assert_allclose(np.asarray(got_model), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# gather_segsum (block SpMM)
# ---------------------------------------------------------------------------

SPMM_SWEEP = [
    # (n_dst, n_src, n_edges, F, seed)
    (256, 256, 1000, 64, 0),
    (300, 200, 700, 16, 1),  # non-multiple of block
    (128, 512, 2000, 128, 2),
    (512, 512, 100, 200, 3),  # sparse, F > f_tile
]


@pytest.mark.parametrize("n_dst,n_src,m,F,seed", SPMM_SWEEP,
                         ids=[f"spmm{i}" for i in range(len(SPMM_SWEEP))])
def test_block_spmm_interpret_vs_ref(n_dst, n_src, m, F, seed):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n_src, m).astype(np.int32)
    dst = rng.integers(0, n_dst, m).astype(np.int32)
    val = rng.normal(size=m).astype(np.float32)
    x = jnp.asarray(rng.normal(size=(n_src, F)).astype(np.float32))
    bt = build_tiles(src, dst, val, n_dst, n_src)
    got = gather_segsum(bt, x, n_dst, force="interpret")
    want = spmm_ref(jnp.asarray(src), jnp.asarray(dst), jnp.asarray(val), x, n_dst)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4)


def test_block_spmm_occupancy_reported():
    rng = np.random.default_rng(0)
    src = rng.integers(0, 1024, 5000).astype(np.int32)
    dst = rng.integers(0, 1024, 5000).astype(np.int32)
    bt = build_tiles(src, dst, None, 1024, 1024)
    assert 0 < bt.occupancy <= 1


# ---------------------------------------------------------------------------
# peel_round
# ---------------------------------------------------------------------------

PEEL_SWEEP = [(1000, 0), (8192, 1), (10000, 2), (100, 3)]


@pytest.mark.parametrize("V,seed", PEEL_SWEEP,
                         ids=[f"peel{v}" for v, _ in PEEL_SWEEP])
def test_peel_round_interpret_vs_ref(V, seed):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.uniform(0, 10, V).astype(np.float32))
    a = jnp.asarray(rng.uniform(0, 2, V).astype(np.float32))
    active = jnp.asarray(rng.random(V) > 0.3)
    level = jnp.asarray(rng.integers(-1, 5, V).astype(np.int32))
    dw = jnp.asarray(rng.uniform(0, 1, V).astype(np.float32))
    thresh = jnp.float32(5.0)
    round_ = jnp.int32(7)
    w2, active2, level2, peeled, partials = peel_round_update(
        w, a, active, level, dw, thresh, round_, block=1024, interpret=True
    )
    rw2, ra2, rl2, rp, rpart = peel_round_ref(w, a, active, level, dw, thresh, round_)
    np.testing.assert_allclose(np.asarray(w2), np.asarray(rw2), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(active2), np.asarray(ra2))
    np.testing.assert_array_equal(np.asarray(level2), np.asarray(rl2))
    np.testing.assert_array_equal(np.asarray(peeled), np.asarray(rp))
    np.testing.assert_allclose(np.asarray(partials.sum(0)), np.asarray(rpart),
                               rtol=1e-5)


def test_peel_round_consistent_with_bulk_peel_semantics():
    """One fused-kernel round == one full-buffer round step (weights/masks)."""
    from repro.core.peel import _BulkState, _round_step
    from repro.graphstore.structs import device_graph_from_coo

    rng = np.random.default_rng(4)
    n, m = 200, 600
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    c = rng.integers(1, 5, src.shape[0]).astype(np.float32)
    g = device_graph_from_coo(n, src, dst, c)
    w0 = g.peel_weights()
    f0 = g.f_total()
    st = _BulkState(w=w0, active=g.vertex_mask, edge_alive=g.edge_mask, f=f0,
                    n_act=jnp.sum(g.vertex_mask),
                    level=jnp.full(n, -1, jnp.int32), best_g=jnp.float32(-1e30),
                    best_level=jnp.int32(0), round_=jnp.int32(0))
    nxt = _round_step(g.src, g.dst, g.c, g.a, 0.1, False, st)

    g_cur = f0 / jnp.maximum(st.n_act, 1)
    thresh = 2.0 * 1.1 * g_cur
    peeled_ref = np.asarray(st.active & (st.w <= thresh))
    cm = np.where(np.asarray(g.edge_mask), np.asarray(g.c), 0.0)
    e_ps, e_pd = peeled_ref[np.asarray(g.src)], peeled_ref[np.asarray(g.dst)]
    dw = np.zeros(n, np.float32)
    np.add.at(dw, np.asarray(g.dst), np.where(e_ps & ~e_pd, cm, 0.0))
    np.add.at(dw, np.asarray(g.src), np.where(e_pd & ~e_ps, cm, 0.0))
    w2, active2, level2, peeled, partials = peel_round_update(
        st.w, g.a, st.active, st.level, jnp.asarray(dw), thresh, st.round_,
        block=256, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(peeled), peeled_ref)
    np.testing.assert_allclose(np.asarray(w2), np.asarray(nxt.w), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(active2), np.asarray(nxt.active))


def test_bulk_peel_kernel_wired_round_parity():
    """Satellite check for the kernel wiring: ``use_kernel=True`` routes
    every round's elementwise update through ``peel_round`` (Pallas on
    TPU, pure-jnp reference elsewhere) and must reproduce the plain-jnp
    round bit-for-bit on integer weights — cold peel, warm suffix re-peel,
    and a max_rounds cutoff alike."""
    from repro.core.peel import bulk_peel, bulk_peel_warm
    from repro.graphstore.structs import device_graph_from_coo

    rng = np.random.default_rng(11)
    n, m = 150, 500
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    c = rng.integers(1, 6, src.shape[0]).astype(np.float32)
    a = rng.integers(0, 3, n).astype(np.float32)
    g = device_graph_from_coo(n, src, dst, c, a)

    for kwargs in ({}, {"max_rounds": 3}):
        ref = bulk_peel(g, eps=0.1, **kwargs)
        got = bulk_peel(g, eps=0.1, use_kernel=True, **kwargs)
        np.testing.assert_array_equal(np.asarray(got.level), np.asarray(ref.level))
        assert float(got.best_g) == float(ref.best_g)
        assert int(got.best_level) == int(ref.best_level)
        assert int(got.n_rounds) == int(ref.n_rounds)

    keep_mask = jnp.asarray(np.asarray(ref.level) >= 2)
    seed = dict(w_full=g.peel_weights(), w_exact=jnp.bool_(False))
    wref = bulk_peel_warm(g, keep_mask, prior_best_g=ref.best_g, eps=0.1,
                          **seed)
    wgot = bulk_peel_warm(g, keep_mask, prior_best_g=ref.best_g, eps=0.1,
                          use_kernel=True, **seed)
    np.testing.assert_array_equal(np.asarray(wgot.level), np.asarray(wref.level))
    assert float(wgot.best_g) == float(wref.best_g)
