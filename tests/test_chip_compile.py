"""Compile the fraud path for a described TPU v5e, without a chip.

The TPU compiler is installed with jax; it compiles for a topology that
is described, not attached, and refuses what the chip would refuse: a
Pallas block off the (8, 128) tiling, a program larger than HBM.  Nothing
runs here, so these tests say nothing about answers or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

from __future__ import annotations

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs.spade_grab import CONFIG
from repro.core.incremental import DeviceSpadeState, insert_and_maintain
from repro.dist.graph import (
    sharded_insert_and_maintain,
    sharded_slide_and_maintain,
)
from repro.graphstore.structs import DeviceGraph
from repro.kernels.peel_round.ops import peel_round
from repro.launch.mesh import make_mesh

HBM_BYTES = 16 * 10**9  # one v5e chip
# Grab4 width as the service lays it out: capacities rounded up to 512
V = -(-(CONFIG.n_capacity + 1) // 512) * 512  # + the streamed actor
E = -(-CONFIG.e_capacity // 512) * 512
B = CONFIG.batch_edges


@pytest.fixture(scope="module")
def topo():
    # the TPU compiler ships in libtpu; without it nothing can be compiled
    # for the chip.  Any other failure to describe the topology fails.
    pytest.importorskip("libtpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    desc = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip cannot be read back from the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return make_mesh((4,), ("data",), devices=topo.devices)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _state(edge_sh, vert_sh) -> DeviceSpadeState:
    graph = DeviceGraph(
        src=_sds((E,), jnp.int32, edge_sh),
        dst=_sds((E,), jnp.int32, edge_sh),
        c=_sds((E,), jnp.float32, edge_sh),
        edge_mask=_sds((E,), jnp.bool_, edge_sh),
        a=_sds((V,), jnp.float32, vert_sh),
        vertex_mask=_sds((V,), jnp.bool_, vert_sh),
        n_capacity=V,
        e_capacity=E,
    )
    return DeviceSpadeState(
        graph=graph,
        level=_sds((V,), jnp.int32, vert_sh),
        best_g=_sds((), jnp.float32, vert_sh),
        community=_sds((V,), jnp.bool_, vert_sh),
        edge_count=_sds((), jnp.int32, vert_sh),
        w0=_sds((V,), jnp.float32, vert_sh),
    )


def _batch(sh):
    return (_sds((B,), jnp.int32, sh), _sds((B,), jnp.int32, sh),
            _sds((B,), jnp.float32, sh), _sds((B,), jnp.bool_, sh))


def _per_device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


@pytest.mark.parametrize("width", [V, 6_023_424])
def test_peel_round_kernel_compiles_for_v5e(one_chip, width):
    vec = lambda dt: _sds((width,), dt, one_chip)  # noqa: E731
    args = (vec(jnp.float32), vec(jnp.float32), vec(jnp.bool_),
            vec(jnp.int32), vec(jnp.float32), _sds((), jnp.float32, one_chip),
            _sds((), jnp.int32, one_chip))
    compiled = jax.jit(
        lambda *a: peel_round(*a, force="pallas")
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _per_device_bytes(compiled) < HBM_BYTES


def test_insert_and_maintain_compiles_at_grab4_width(one_chip):
    compiled = insert_and_maintain.lower(
        _state(one_chip, one_chip), *_batch(one_chip),
        eps=CONFIG.eps, max_rounds=CONFIG.max_rounds,
    ).compile()
    assert _per_device_bytes(compiled) < HBM_BYTES


def test_counted_tick_compiles_at_grab4_width(one_chip):
    """The served loop's tick: the same program with its round counters,
    and the rounds' ops under their named scopes in the chip's HLO."""
    compiled = insert_and_maintain.lower(
        _state(one_chip, one_chip), *_batch(one_chip),
        eps=CONFIG.eps, max_rounds=CONFIG.max_rounds, counters=True,
    ).compile()
    text = compiled.as_text()
    assert "/peel_gather/" in text and "/peel_scatter/" in text
    assert _per_device_bytes(compiled) < HBM_BYTES


def test_sharded_insert_and_maintain_compiles_on_v5e_2x2(mesh4):
    edges, repl = NamedSharding(mesh4, P("data")), NamedSharding(mesh4, P())
    compiled = sharded_insert_and_maintain.lower(
        _state(edges, repl), *_batch(repl), mesh=mesh4,
        eps=CONFIG.eps, max_rounds=CONFIG.max_rounds,
    ).compile()
    assert "all-reduce" in compiled.as_text()
    assert _per_device_bytes(compiled) < HBM_BYTES


def test_sharded_slide_and_maintain_compiles_on_v5e_2x2(mesh4):
    """The window tick: the sharded compaction of expired edges, the
    append and the psum-reduced warm re-peel."""
    edges, repl = NamedSharding(mesh4, P("data")), NamedSharding(mesh4, P())
    compiled = sharded_slide_and_maintain.lower(
        _state(edges, repl), _sds((E,), jnp.bool_, edges), *_batch(repl),
        mesh=mesh4, eps=CONFIG.eps, max_rounds=CONFIG.max_rounds,
    ).compile()
    assert "all-reduce" in compiled.as_text()
    assert _per_device_bytes(compiled) < HBM_BYTES
