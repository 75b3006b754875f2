"""repro.dist: logical-axis sharding, EF compression plumbing, and
mesh-sharded peeling parity against the single-device engine.

The sharded tests need >= 2 XLA host devices; conftest.py forces 8 via
``--xla_force_host_platform_device_count`` before jax initializes."""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.incremental import (
    delete_and_maintain,
    full_refresh,
    init_state,
    insert_and_maintain,
    insert_and_maintain_auto,
    slide_and_maintain_auto,
)
from repro.core.peel import bulk_peel
from repro.dist.compression import ef_compress_tree
from repro.dist.graph import (
    init_sharded_state,
    shard_graph,
    sharded_bulk_peel,
    sharded_delete_and_maintain,
    sharded_full_refresh,
    sharded_insert_and_maintain,
    sharded_insert_and_maintain_auto,
    sharded_peel_weights,
    sharded_slide_and_maintain_auto,
)
from repro.dist.sharding import (
    AxisEnv,
    axis_env,
    constrain,
    tree_shardings,
    use_axis_env,
)
from repro.graphstore.structs import device_graph_from_coo
from repro.launch.mesh import make_mesh

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs >=2 XLA host devices"
)


def data_mesh(n: int):
    return make_mesh((n,), ("data",))


def random_graph(seed: int, n: int = 200, m: int = 900, e_slack: int = 512):
    """Integer weights -> order-independent f32 sums -> exact parity."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    c = rng.integers(1, 6, src.shape[0]).astype(np.float32)
    a = rng.integers(0, 3, n).astype(np.float32)
    return device_graph_from_coo(n, src, dst, c, a, e_capacity=src.shape[0] + e_slack)


# ---------------------------------------------------------------------------
# sharding: the logical-axis layer
# ---------------------------------------------------------------------------


def test_constrain_is_noop_without_env():
    x = jnp.ones((8, 4))
    assert constrain(x, "batch", None) is x
    assert axis_env() is None


@multi_device
def test_axis_env_resolution_and_constrain():
    mesh = make_mesh((2, len(jax.devices()) // 2), ("data", "model"))
    env = AxisEnv(mesh=mesh)
    # 'pod' absent -> batch lands on data alone; expert rides model
    assert env.resolve("batch") == "data"
    assert env.resolve("expert") == "model"
    assert env.resolve("edges") == "data"
    assert env.axis_size("batch") == 2
    with use_axis_env(env):
        assert axis_env() is env

        @jax.jit
        def f(x):
            return constrain(x, "batch", "model") * 2.0

        x = jnp.ones((8, mesh.shape["model"] * 2))
        np.testing.assert_array_equal(np.asarray(f(x)), 2.0 * np.ones(x.shape))
        # non-divisible dim: constraint dropped, still works
        y = jnp.ones((3, 5))
        np.testing.assert_array_equal(
            np.asarray(jax.jit(lambda v: constrain(v, "batch", "model"))(y)),
            np.ones((3, 5)),
        )
    assert axis_env() is None


@multi_device
def test_tree_shardings_maps_logical_tuples():
    mesh = data_mesh(len(jax.devices()))
    env = AxisEnv(mesh=mesh)
    logical = {"w": ("batch", None), "scalar": (), "nested": {"e": ("edges",)}}
    with use_axis_env(env):
        sh = tree_shardings(logical)
    assert sh["w"] == NamedSharding(mesh, P("data", None))
    assert sh["scalar"] == NamedSharding(mesh, P())
    assert sh["nested"]["e"] == NamedSharding(mesh, P("data"))


def test_tree_shardings_requires_mesh():
    with pytest.raises(ValueError):
        tree_shardings({"w": ("batch",)})


def test_axis_env_rule_override_and_unknown():
    env = AxisEnv(mesh=None, rules={"batch": ()})
    assert env.resolve("batch") is None
    with pytest.raises(KeyError):
        AxisEnv().rule("no_such_axis")


def test_ef_compress_tree_initializes_err():
    g = {"w": jnp.asarray([0.1, -0.2, 0.3]), "b": jnp.asarray([1.0])}
    deq, err = ef_compress_tree(g)
    assert jax.tree.structure(deq) == jax.tree.structure(g)
    # accumulated signal tracks: g == deq + err per leaf
    for k in g:
        np.testing.assert_allclose(
            np.asarray(deq[k]) + np.asarray(err[k]), np.asarray(g[k]), atol=1e-6
        )


# ---------------------------------------------------------------------------
# graph: mesh-sharded peeling == single-device engine
# ---------------------------------------------------------------------------


@multi_device
def test_shard_graph_pads_and_places():
    g = random_graph(0, e_slack=3)  # e_capacity not divisible by 8
    mesh = data_mesh(len(jax.devices()))
    gs = shard_graph(g, mesh)
    assert gs.e_capacity % len(jax.devices()) == 0
    assert gs.n_capacity == g.n_capacity
    assert int(gs.n_edges) == int(g.n_edges)
    np.testing.assert_allclose(np.asarray(g.peel_weights()),
                               np.asarray(sharded_peel_weights(gs, mesh)))


@multi_device
@pytest.mark.parametrize("seed", range(3))
def test_sharded_bulk_peel_matches_single_device(seed):
    g = random_graph(seed)
    mesh = data_mesh(len(jax.devices()))
    ref = bulk_peel(g, eps=0.1)
    res = sharded_bulk_peel(shard_graph(g, mesh), mesh, eps=0.1)
    assert float(res.best_g) == float(ref.best_g)
    assert int(res.n_rounds) == int(ref.n_rounds)
    np.testing.assert_array_equal(np.asarray(res.level), np.asarray(ref.level))
    np.testing.assert_array_equal(
        np.asarray(res.community_mask()), np.asarray(ref.community_mask())
    )


@multi_device
def test_sharded_bulk_peel_two_way_mesh():
    g = random_graph(7)
    mesh = data_mesh(2)
    res = sharded_bulk_peel(shard_graph(g, mesh), mesh, eps=0.1)
    ref = bulk_peel(g, eps=0.1)
    assert float(res.best_g) == float(ref.best_g)
    np.testing.assert_array_equal(np.asarray(res.level), np.asarray(ref.level))


@multi_device
@pytest.mark.parametrize("n_shards,p_drop", [
    (2, 0.05), (4, 0.5), (8, 0.0), (8, 0.05), (8, 0.95), (8, 1.0),
])
def test_sharded_remove_matches_remove_edges(n_shards, p_drop):
    """The sharded compaction is slot-for-slot ``remove_edges`` and its
    output stays on the edge axis, also when the drops exceed a shard's
    block and survivors cross several shard boundaries."""
    from repro.dist.graph import _sharded_remove
    from repro.graphstore.structs import remove_edges

    g = random_graph(n_shards, e_slack=37)
    mesh = data_mesh(n_shards)
    gs = shard_graph(g, mesh)
    rng = np.random.default_rng(int(100 * p_drop))
    drop = jnp.asarray(rng.random(gs.e_capacity) < p_drop)
    want, n_want = remove_edges(gs, drop)
    got, n_got = jax.jit(_sharded_remove, static_argnums=(2, 3))(
        gs, drop, mesh, "data")
    assert int(n_got) == int(n_want)
    assert got.src.sharding.spec == P("data")
    for f in ("src", "dst", "c", "edge_mask"):
        np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                      np.asarray(getattr(want, f)), err_msg=f)


@multi_device
def test_sharded_density_exact_above_f32_integer_range():
    """The graph's mass exceeds 2^24, where f32 sums of integers round in
    reduction order; the densest set's mass does not.  Its density must be
    exact and identical on one device and on a mesh, cold and warm."""
    rng = np.random.default_rng(3)
    n, m = 300, 2000
    src, dst = rng.integers(10, n, m), rng.integers(10, n, m)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    c = np.full(src.shape[0], 10_001.0, np.float32)
    bs, bd = np.triu_indices(10, 1)  # a 10-clique of heavy edges
    src, dst = np.concatenate([src, bs]), np.concatenate([dst, bd])
    c = np.concatenate([c, np.full(bs.shape[0], 300_007.0, np.float32)])
    assert c.astype(np.float64).sum() > 2**24 > 300_007.0 * bs.shape[0]
    g = device_graph_from_coo(n, src, dst, c, e_capacity=src.shape[0] + 64)
    mesh = data_mesh(len(jax.devices()))

    def exact_density(comm):
        comm = np.asarray(comm)[:n]
        mass = c.astype(np.float64)[comm[src] & comm[dst]].sum()
        assert 0 < mass < 2**24
        return float(np.float32(mass) / np.float32(comm.sum()))

    ref = bulk_peel(g, eps=0.1)
    res = sharded_bulk_peel(shard_graph(g, mesh), mesh, eps=0.1)
    exact = exact_density(ref.community_mask())
    assert float(ref.best_g) == float(res.best_g) == exact

    st_ref = init_state(g, eps=0.1)
    st_sh = init_sharded_state(shard_graph(g, mesh), mesh, eps=0.1)
    b = jnp.asarray(rng.integers(10, n, (2, 64)), jnp.int32)
    bc = jnp.full(64, 10_001.0, jnp.float32)
    valid = b[0] != b[1]
    st_ref = insert_and_maintain(st_ref, b[0], b[1], bc, valid, eps=0.1)
    st_sh = sharded_insert_and_maintain(st_sh, b[0], b[1], bc, valid,
                                        mesh=mesh, eps=0.1)
    assert float(st_ref.best_g) == float(st_sh.best_g)
    assert float(st_ref.best_g) == exact_density(st_ref.community)


@multi_device
def test_sharded_incremental_matches_single_device():
    """Streamed batches: append, warm re-peel, w0 and community all track
    the single-device engine bit-for-bit (integer weights)."""
    n = 200
    g = random_graph(1, n=n)
    mesh = data_mesh(len(jax.devices()))
    rng = np.random.default_rng(2)
    st_ref = init_state(g, eps=0.1)
    st_sh = init_sharded_state(shard_graph(g, mesh), mesh, eps=0.1)
    for step in range(4):
        B = 64
        bs = jnp.asarray(rng.integers(0, n, B), jnp.int32)
        bd = jnp.asarray(rng.integers(0, n, B), jnp.int32)
        bc = jnp.asarray(rng.integers(1, 4, B), jnp.float32)
        valid = bs != bd
        st_ref = insert_and_maintain(st_ref, bs, bd, bc, valid, eps=0.1)
        st_sh = sharded_insert_and_maintain(
            st_sh, bs, bd, bc, valid, mesh=mesh, eps=0.1
        )
        assert float(st_sh.best_g) == float(st_ref.best_g), step
        assert int(st_sh.edge_count) == int(st_ref.edge_count)
        np.testing.assert_array_equal(
            np.asarray(st_sh.level), np.asarray(st_ref.level)
        )
        np.testing.assert_array_equal(
            np.asarray(st_sh.community), np.asarray(st_ref.community)
        )
        np.testing.assert_allclose(np.asarray(st_sh.w0), np.asarray(st_ref.w0))
        E = st_ref.graph.e_capacity  # sharded graph may be tail-padded
        np.testing.assert_array_equal(
            np.asarray(st_sh.graph.src)[:E], np.asarray(st_ref.graph.src)
        )
        np.testing.assert_array_equal(
            np.asarray(st_sh.graph.edge_mask)[:E],
            np.asarray(st_ref.graph.edge_mask),
        )
    st_ref = full_refresh(st_ref, eps=0.1)
    st_sh = sharded_full_refresh(st_sh, mesh=mesh, eps=0.1)
    assert float(st_sh.best_g) == float(st_ref.best_g)
    np.testing.assert_array_equal(
        np.asarray(st_sh.community), np.asarray(st_ref.community)
    )


@multi_device
def test_sharded_delete_matches_single_device():
    """Interleaved inserts + slot-range deletions: the compaction scatter,
    suffix recovery, w0 decrement and community bookkeeping all track the
    single-device engine bit-for-bit (integer weights)."""
    n = 200
    g = random_graph(5, n=n)
    mesh = data_mesh(len(jax.devices()))
    rng = np.random.default_rng(6)
    st_ref = init_state(g, eps=0.1)
    st_sh = init_sharded_state(shard_graph(g, mesh), mesh, eps=0.1)
    for step in range(4):
        B = 64
        bs = jnp.asarray(rng.integers(0, n, B), jnp.int32)
        bd = jnp.asarray(rng.integers(0, n, B), jnp.int32)
        bc = jnp.asarray(rng.integers(1, 4, B), jnp.float32)
        valid = bs != bd
        st_ref = insert_and_maintain(st_ref, bs, bd, bc, valid, eps=0.1)
        st_sh = sharded_insert_and_maintain(
            st_sh, bs, bd, bc, valid, mesh=mesh, eps=0.1
        )
        lo = int(rng.integers(0, 300))
        hi = lo + int(rng.integers(1, 80))
        ids_r = jnp.arange(st_ref.graph.e_capacity, dtype=jnp.int32)
        ids_s = jnp.arange(st_sh.graph.e_capacity, dtype=jnp.int32)
        st_ref = delete_and_maintain(st_ref, (ids_r >= lo) & (ids_r < hi),
                                     eps=0.1)
        st_sh = sharded_delete_and_maintain(
            st_sh, (ids_s >= lo) & (ids_s < hi), mesh=mesh, eps=0.1
        )
        assert float(st_sh.best_g) == float(st_ref.best_g), step
        assert int(st_sh.edge_count) == int(st_ref.edge_count)
        np.testing.assert_array_equal(
            np.asarray(st_sh.level), np.asarray(st_ref.level)
        )
        np.testing.assert_array_equal(
            np.asarray(st_sh.community), np.asarray(st_ref.community)
        )
        np.testing.assert_allclose(np.asarray(st_sh.w0), np.asarray(st_ref.w0))
        E = st_ref.graph.e_capacity
        np.testing.assert_array_equal(
            np.asarray(st_sh.graph.src)[:E], np.asarray(st_ref.graph.src)
        )
        np.testing.assert_array_equal(
            np.asarray(st_sh.graph.edge_mask)[:E],
            np.asarray(st_ref.graph.edge_mask),
        )


@multi_device
def test_device_service_sharded_windowed_matches_single():
    """Sliding-window serving on the mesh: every tick runs expire + insert
    through the psum-reduced engine; final state matches the single-device
    windowed service (DG metric: unit weights, order-robust sums)."""
    from repro.graphstore.generators import make_transaction_stream
    from repro.serve.device_service import run_device_service

    mesh = data_mesh(len(jax.devices()))
    stream = make_transaction_stream(n=800, m=4000, seed=13)
    rep1 = run_device_service(
        stream, metric="DG", batch_edges=128, max_rounds=10, window_ticks=3,
    )
    repn = run_device_service(
        stream, metric="DG", batch_edges=128, max_rounds=10, window_ticks=3,
        mesh=mesh,
    )
    assert repn.final_g == rep1.final_g
    assert repn.live_edges == rep1.live_edges
    assert repn.n_expired_edges == rep1.n_expired_edges
    m_base = stream.base_src.shape[0]
    assert rep1.live_edges <= m_base + 3 * 128


@multi_device
def test_sharded_workset_auto_matches_single_device():
    """Workset ticks on the mesh: per-shard local gather + psum'd workset
    rounds track both the single-device workset engine and the fused
    full-buffer engine bit-for-bit (integer weights), through hot
    (workset) and cold (fallback) ticks alike."""
    n = 200
    g = random_graph(7, n=n)
    mesh = data_mesh(len(jax.devices()))
    rng = np.random.default_rng(8)
    st_ref = init_state(g, eps=0.1)
    st_sh = init_sharded_state(shard_graph(g, mesh), mesh, eps=0.1)
    lv = np.where(np.asarray(g.vertex_mask), np.asarray(st_ref.level), -1)
    hot = np.argsort(lv)[-24:]
    E = st_ref.graph.e_capacity
    took_workset = False
    for step in range(4):
        B = 16
        pool = hot if step % 2 == 0 else np.arange(n)  # hot and cold ticks
        bs = jnp.asarray(rng.choice(pool, B), jnp.int32)
        bd = jnp.asarray(rng.choice(pool, B), jnp.int32)
        bc = jnp.asarray(rng.integers(1, 4, B), jnp.float32)
        valid = bs != bd
        if step == 3:  # one slide tick through the sharded workset path
            drop = jnp.zeros(E, bool).at[jnp.arange(3)].set(True)
            drop_sh = jnp.zeros(st_sh.graph.e_capacity, bool).at[
                jnp.arange(3)
            ].set(True)
            st_ref, i1 = slide_and_maintain_auto(
                st_ref, drop, bs, bd, bc, valid, eps=0.1, min_bucket=8
            )
            st_sh, i2 = sharded_slide_and_maintain_auto(
                st_sh, drop_sh, bs, bd, bc, valid, mesh=mesh, eps=0.1,
                min_bucket=8,
            )
        else:
            st_ref, i1 = insert_and_maintain_auto(
                st_ref, bs, bd, bc, valid, eps=0.1, min_bucket=8
            )
            st_sh, i2 = sharded_insert_and_maintain_auto(
                st_sh, bs, bd, bc, valid, mesh=mesh, eps=0.1, min_bucket=8
            )
        # the suffix is engine-independent; bucket/fallback choices may
        # differ (the sharded engine buckets the max PER-SHARD edge count)
        # yet the results below must still agree bit-for-bit
        assert i1.n_suffix_vertices == i2.n_suffix_vertices, step
        took_workset |= not i1.fallback and not i2.fallback
        assert float(st_sh.best_g) == float(st_ref.best_g), step
        assert int(st_sh.edge_count) == int(st_ref.edge_count)
        np.testing.assert_array_equal(
            np.asarray(st_sh.level), np.asarray(st_ref.level)
        )
        np.testing.assert_array_equal(
            np.asarray(st_sh.community), np.asarray(st_ref.community)
        )
        np.testing.assert_array_equal(
            np.asarray(st_sh.w0), np.asarray(st_ref.w0)
        )
        np.testing.assert_array_equal(
            np.asarray(st_sh.graph.src)[:E], np.asarray(st_ref.graph.src)
        )
    assert took_workset  # the hot ticks must actually exercise the workset


@multi_device
def test_sharded_max_rounds_cutoff_matches():
    g = random_graph(3)
    mesh = data_mesh(len(jax.devices()))
    ref = bulk_peel(g, eps=0.1, max_rounds=3)
    res = sharded_bulk_peel(shard_graph(g, mesh), mesh, eps=0.1, max_rounds=3)
    assert float(res.best_g) == float(ref.best_g)
    np.testing.assert_array_equal(np.asarray(res.level), np.asarray(ref.level))


@multi_device
def test_sharded_peel_requires_divisible_capacity():
    g = random_graph(4, e_slack=3)
    mesh = data_mesh(len(jax.devices()))
    with pytest.raises(ValueError, match="divisible"):
        sharded_bulk_peel(g, mesh)


@multi_device
def test_device_service_sharded_detects_fraud():
    from repro.graphstore.generators import make_transaction_stream
    from repro.serve.device_service import run_device_service

    mesh = data_mesh(len(jax.devices()))
    stream = make_transaction_stream(n=1000, m=5000, seed=11)
    rep = run_device_service(
        stream, metric="DW", batch_edges=256, max_rounds=10,
        refresh_every=2, mesh=mesh,
    )
    assert rep.fraud_recall >= 0.99
    assert rep.final_g > 0
    assert rep.n_refreshes >= 1
