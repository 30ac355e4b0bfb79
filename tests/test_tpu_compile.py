"""Compile the main path for a described TPU v5e, with no chip attached.

What interpret mode cannot show: the TPU compiler's tiling rules for the
Pallas kernel, the tick's device-memory footprint, and the collectives of
the four-chip shard_map tick.  Nothing here runs; every test compiles.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs import get_graph_config
from repro.core import engine as E
from repro.core import programs as PR
from repro.core import trace
from repro.dist.sharding import vertex_partition
from repro.kernels.semiring_spmv import EDGE_BLOCK, SEMIRINGS, spmv_partials

HBM_BYTES = 16 * 10**9  # one v5e chip
# asymp_cc's pulled edge stream is ~4.2M edges: compile at that width
N_EDGES = 8192 * EDGE_BLOCK


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("semiring", SEMIRINGS)
def test_spmv_compiles_for_v5e(one_chip, semiring):
    dtype = jnp.int32 if semiring in ("min", "max", "or") else jnp.float32
    vals = jax.ShapeDtypeStruct((N_EDGES,), dtype, sharding=one_chip)
    dst = jax.ShapeDtypeStruct((N_EDGES,), jnp.int32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((N_EDGES,), dtype, sharding=one_chip)
    compiled = jax.jit(lambda v, d, ww: spmv_partials(
        v, d, ww, semiring=semiring, interpret=False)).lower(
        vals, dst, w).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def local_tick(one_chip):
    """``asymp_cc``'s local tick, compiled for one v5e."""
    cfg = get_graph_config("asymp_cc")
    prog = PR.get_program(cfg)
    P_ = cfg.num_shards
    vs = vertex_partition(cfg.num_vertices, P_).vs
    es = cfg.num_edges * 2 // P_  # symmetrized estimate, as the dry-run
    ep = E.derive_params(cfg, num_shards=P_, vs=vs, es=es,
                         num_vertices=cfg.num_vertices, prog=prog)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = E.EngineState(spec((P_, vs), prog.jdtype),
                          spec((P_, vs), jnp.bool_),
                          spec((P_, vs), jnp.int32), spec((), jnp.int32),
                          None)
    g = E.ShardGraph(spec((P_, vs + 1), jnp.int32),
                     spec((P_, es), jnp.int32), None)
    tick = E.make_local_tick(prog, ep, prog.weighted)
    return tick.lower(state, g).compile()


def test_local_tick_fits_one_v5e(local_tick):
    mem = local_tick.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < HBM_BYTES, total


def test_tick_phases_keep_their_scopes_on_v5e(local_tick):
    """The TPU compiler keeps each phase's ``jax.named_scope`` in the
    ``op_name`` metadata that a device trace reports per op."""
    paths = re.findall(r'op_name="([^"]*)"', local_tick.as_text())
    for scope in trace.SCOPES:  # vmapped phases read ``vmap(tick.select)``
        named = re.compile(rf"(^|[/(]){re.escape(scope)}([/)]|$)")
        assert any(named.search(p) for p in paths), scope


def test_mesh_tick_exchanges_all_to_all(topo):
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    cfg = dataclasses.replace(get_graph_config("asymp_cc"), num_shards=4)
    compiled, info = E.lower_tick_for_mesh(cfg, mesh, 4)
    assert info["workers"] == 4
    assert "all-to-all" in compiled.as_text()
