"""Compile the main path for a described TPU v5e, with no chip attached.

What interpret mode cannot show: the TPU compiler's tiling rules for the
Pallas kernel, the tick's device-memory footprint, the ops its route phase
compiles to, and the collectives of the four-chip shard_map tick.  Nothing
here runs; every test compiles.
"""
import dataclasses
import re
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.configs import get_graph_config
from repro.configs.base import GraphConfig
from repro.core import engine as E
from repro.core import programs as PR
from repro.core import trace
from repro.dist.sharding import vertex_partition
from repro.kernels.semiring_spmv import EDGE_BLOCK, SEMIRINGS, spmv_partials

HBM_BYTES = 16 * 10**9  # one v5e chip
# asymp_cc's pulled edge stream is ~4.2M edges: compile at that width
N_EDGES = 8192 * EDGE_BLOCK
# the innermost phase scope of an ``op_name`` path (``vmap(tick.route)``)
SCOPE = re.compile(r"(?:^|[/(])(tick\.[a-z]+)(?=[/)]|$)")


def _scope(op_name: str):
    found = SCOPE.findall(op_name)
    return found[-1] if found else None


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


@pytest.fixture(scope="module")
def cell_tick(one_chip):
    """The benchmark cell's local tick (``g500-s16-wcc``: 8 shards of 8,192
    vertices, the largest holding 249,911 edges), compiled for one v5e."""
    cfg = GraphConfig(name="cell", algorithm="cc", num_vertices=65536,
                      avg_degree=16, num_shards=8)
    prog = PR.get_program(cfg)
    P_, vs, es = 8, 8192, 249911
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
    return ep, tick.lower(state, g).compile().as_text()


def _scoped_ops(hlo: str) -> list[tuple[str, str, str]]:
    """``(scope, op, result type)`` of every sort, gather and
    scatter.  An instruction without an ``op_name`` inside a fusion takes
    the scope most of the fusion's instructions carry, as the benchmark
    reads a device trace."""
    comps, body = [], None
    for line in hlo.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            body = []
            comps.append(("fused_computation" in line, body))
        elif body is not None and line.startswith("  "):
            body.append(line)
    out = []
    for fused, lines in comps:
        names = [(re.search(r'op_name="([^"]*)"', ln) or [None, ""])[1]
                 for ln in lines]
        votes = Counter(v for v in map(_scope, names) if v)
        major = votes.most_common(1)[0][0] if fused and votes else None
        for ln, name in zip(lines, names):
            m = re.match(r"\s+(?:ROOT )?%\S+ = (.+?) (sort|gather|scatter)\(",
                         ln)
            if m:
                sc = _scope(name) if name else major
                out.append((sc, m.group(2), m.group(1).split("{")[0]))
    return out


def test_route_ranks_without_sort_gather_or_scatter_on_v5e(cell_tick):
    """With 8 shards the routing rank is dense prefix sums: route keeps
    only the scatters into the two send buffers, the cursor and the
    frontier, and every op of the rank stays under ``tick.route``."""
    ep, hlo = cell_tick
    ops = _scoped_ops(hlo)
    route = sorted((op, ty) for sc, op, ty in ops if sc == "tick.route")
    P_, send = ep.num_shards, ep.num_shards ** 2 * ep.route_capacity
    assert route == sorted([("scatter", f"pred[{P_ * ep.vs}]"),
                            ("scatter", f"s32[{P_ * ep.vs}]"),
                            ("scatter", f"s32[{send}]"),
                            ("scatter", f"s32[{send}]")])
    paths = re.findall(r'op_name="([^"]*route_rank[^"]*)"', hlo)
    assert paths
    assert {_scope(p) for p in paths} == {"tick.route"}


def test_receive_takes_three_widths_on_v5e(cell_tick):
    """The cell's receive is one ``conditional`` over three static widths
    of its buffers (256, 1,280 and the whole 9,761 slots of a row): in each
    branch, under ``tick.receive``, the gather of the old values reads ``P
    x width`` slots a shard and one scatter updates the values.  The
    conditional, whose device op spans its branch's ops, is under no
    phase's scope, so that the phase does not count them twice."""
    ep, hlo = cell_tick
    P_, widths = ep.num_shards, E.receive_widths(ep.route_capacity)
    assert widths == (256, 1280, ep.route_capacity)
    receive = sorted((op, ty) for sc, op, ty in _scoped_ops(hlo)
                     if sc == "tick.receive")
    assert receive == sorted(
        [("gather", f"s32[{P_},{P_ * w}]") for w in widths]
        + [("scatter", f"s32[{P_ * ep.vs}]")] * len(widths))
    conds = re.findall(r"= .+? conditional\(.*branch_computations=\{(.*?)\}"
                       r'.*op_name="([^"]*)"', hlo)
    assert [(len(b.split(",")), _scope(path)) for b, path in conds] == [
        (3, None)]


def test_route_rank_fits_the_production_mesh_on_v5e(one_chip):
    """The one-hot of the counting rank is shards times the slot plane.  At
    the 256-worker dry-run of ``asymp_cc_prod`` (rmat26) one chip's rank
    still fits in its memory beside the slot plane it ranks."""
    cfg = dataclasses.replace(get_graph_config("asymp_cc_prod"),
                              num_shards=256)
    prog = PR.get_program(cfg)
    ep = E.derive_params(cfg, num_shards=256,
                         vs=vertex_partition(cfg.num_vertices, 256).vs,
                         es=cfg.num_edges * 2 // 256,
                         num_vertices=cfg.num_vertices, prog=prog)
    slots = jax.ShapeDtypeStruct(
        (ep.max_vertices_per_tick, ep.degree_window), jnp.int32,
        sharding=one_chip)
    compiled = jax.jit(lambda d: E._route_rank(d, ep.num_shards)).lower(
        slots).compile()
    hlo = compiled.as_text()
    assert not re.search(r"= .+? (sort|gather|scatter)\(", hlo)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + 2 * slots.size * 4 < HBM_BYTES // 2


def test_mesh_tick_exchanges_all_to_all(topo):
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    cfg = dataclasses.replace(get_graph_config("asymp_cc"), num_shards=4)
    compiled, info = E.lower_tick_for_mesh(cfg, mesh, 4)
    assert info["workers"] == 4
    assert "all-to-all" in compiled.as_text()


def test_mesh_tick_crosses_chips_only_under_wire_on_v5e(topo):
    """The four-chip cell's tick (``g500-s17-wcc-x4``: 8 shards of 16,384
    vertices, the largest holding 487,573 edges, two shards on each chip of
    a 2x2 v5e): the session's tick over the mesh has its ``all-to-all``
    ops only under ``tick.wire``, and each chip's temporaries take under
    half of it."""
    mesh = Mesh(np.asarray(topo.devices), ("workers",))
    cfg = GraphConfig(name="cell", algorithm="cc", num_vertices=1 << 17,
                      avg_degree=16, num_shards=8)
    prog = PR.get_program(cfg)
    P_, vs, es = 8, 16384, 487573
    ep = E.derive_params(cfg, num_shards=P_, vs=vs, es=es,
                         num_vertices=cfg.num_vertices, prog=prog)
    assert (ep.max_vertices_per_tick, ep.route_capacity) == (7618, 19045)
    rows, whole = NamedSharding(mesh, P("workers")), NamedSharding(mesh, P())

    def spec(shape, dtype, sharding=rows):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    state = E.EngineState(spec((P_, vs), prog.jdtype),
                          spec((P_, vs), jnp.bool_),
                          spec((P_, vs), jnp.int32),
                          spec((), jnp.int32, whole), None)
    g = E.ShardGraph(spec((P_, vs + 1), jnp.int32),
                     spec((P_, es), jnp.int32), None)
    compiled = jax.jit(E.shard_local_tick(prog, ep, mesh, False)).lower(
        state, g).compile()
    hlo = compiled.as_text()
    a2a = [ln for ln in hlo.splitlines()
           if re.search(r"= \S+ all-to-all(-start)?\(", ln)]
    # the values and the ids, each [2 shards, 8 destinations, cap] a chip
    assert len(a2a) == 2, a2a
    for ln in a2a:
        assert "s32[2,8,19045]" in ln, ln
        assert _scope(re.search(r'op_name="([^"]*)"', ln)[1]) == "tick.wire"
    mem = compiled.memory_analysis()
    assert 0 < mem.temp_size_in_bytes < HBM_BYTES // 2
