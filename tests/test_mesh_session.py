"""``EngineSession`` on a ``workers`` mesh: the tick under ``shard_map`` with
the shards split over the devices, against the session on one device.

The mesh runs need several devices, which a CPU process has only if
``XLA_FLAGS`` says so before JAX starts: one subprocess on four CPU devices
(this file run as a script) runs every mesh case on Graph500 scale-10 graphs
and prints one JSON line of findings, which the tests below read.  The
combinations a mesh does not run yet are checked in this process.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
DEVICES = 4
SCALE = 10
INITIATOR = (0.57, 0.19, 0.19, 0.05)
# (program, shards, wire): one and two shards per device; an int8 wire
# sends the per-row scales of quantized distances across devices too
CASES = [(alg, shards, "none") for alg in ("cc", "sssp", "pagerank")
         for shards in (DEVICES, 2 * DEVICES)] + [("sssp", 2 * DEVICES,
                                                   "int8")]
IDS = [f"{a}-{s}" + (f"-{w}" if w != "none" else "") for a, s, w in CASES]


def _cfg(algorithm: str, shards: int, **kw):
    from repro.configs.base import GraphConfig
    return GraphConfig(name=f"mesh-{algorithm}", algorithm=algorithm,
                       num_vertices=1 << SCALE, avg_degree=16,
                       num_shards=shards,
                       weighted=algorithm == "sssp",
                       enforce_fraction=1.0 if algorithm == "pagerank"
                       else 0.5, **kw)


# ------------------------------------------------------------ the child
def _same_run(a, b, ta: dict, tb: dict) -> dict:
    va, vb = np.asarray(a.state.values), np.asarray(b.state.values)
    return {"values": va.dtype == vb.dtype and va.tobytes() == vb.tobytes(),
            "counts": all(ta[k] == tb[k] for k in ("ticks", "sent",
                                                   "accepted", "fetched")),
            "ticks": tb["ticks"], "converged": bool(tb["converged"])}


def _reference_error(alg: str, cfg, graph, edges, table) -> float:
    """How far the table is from the plain reference: 0 where it equals
    it (cc, sssp), the L1 distance otherwise (pagerank)."""
    from chipbench.reference import wcc
    from conftest import dijkstra_directed
    from repro.core import graph as G
    from repro.kernels.ops import pagerank as dense_pagerank

    n = cfg.num_vertices
    if alg == "cc":
        return float(np.count_nonzero(table != wcc.min_labels(n, edges)))
    if alg == "sssp":
        directed, w = G.edge_list(graph, with_weights=True)
        dist = dijkstra_directed(n, directed[:, 0], directed[:, 1], w,
                                 source=cfg.source)
        return float(np.max(np.abs(np.where(np.isfinite(dist),
                                            table - dist, 0.0))
                            + (np.isfinite(dist) != np.isfinite(table))))
    oracle = np.asarray(dense_pagerank(graph, iters=80, use_kernel=False,
                                       dangling="absorb"))
    return float(np.abs(table.astype(np.float64) / n - oracle).sum())


def child() -> dict:
    import jax
    from chipbench.graph500 import kronecker_edges
    from repro.core import engine as E
    from repro.core import graph as G
    from repro.core import merger
    from repro.launch import graph_mine
    from repro.launch.mesh import make_worker_mesh

    assert len(jax.devices()) == DEVICES, jax.devices()
    mesh = make_worker_mesh(DEVICES)
    edges = kronecker_edges(SCALE, 16, INITIATOR, 2**31 + 17)
    hub = int(np.bincount(edges.ravel()).argmax())  # sssp's source
    out: dict = {"cases": {}}
    for (alg, shards, wire), name in zip(CASES, IDS):
        cfg = _cfg(alg, shards, wire_compression=wire,
                   **({"source": hub} if alg == "sssp" else {}))
        graph = G.build_sharded_graph(cfg, edges=edges)
        plain = E.EngineSession(cfg, graph=graph)
        ta = plain.tick_until_quiescent()
        on_mesh = E.EngineSession(cfg, graph=graph, mesh=mesh)
        tb = on_mesh.tick_until_quiescent()
        case = _same_run(plain, on_mesh, ta, tb)
        case["reference_error"] = _reference_error(
            alg, cfg, graph, edges,
            merger.extract(on_mesh.state, graph, on_mesh.prog))
        case["sharding"] = str(on_mesh.state.values.sharding.spec)
        case["wire"] = E.wire_codec(on_mesh.prog, on_mesh.ep).compression
        out["cases"][name] = case

    # a fork shares the compiled tick; a rebound graph keeps the placement
    cfg = _cfg("cc", 2 * DEVICES)
    graph = G.build_sharded_graph(cfg, edges=edges)
    session = E.EngineSession(cfg, graph=graph, mesh=mesh)
    session.step()
    merger.extract(session.state, graph, session.prog)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, *_, **__: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    fork = session.fork()
    fork.rebind_graph(graph)
    for _ in range(3):
        fork.step()
    merger.extract(fork.state, graph, fork.prog)
    out["fork_compiles"] = len(compiles)
    out["rebound_specs"] = sorted({str(x.sharding.spec)
                                   for x in jax.tree.leaves(fork.g)})

    try:
        E.EngineSession(_cfg("cc", 6), mesh=mesh)
        out["uneven"] = None
    except ValueError as e:
        out["uneven"] = str(e)

    # the launcher, in-process, with and without the mesh
    runs = {}
    for chips in (1, DEVICES):
        run = graph_mine.main(["--config", "asymp_cc_small", "--reduced",
                               "--chips", str(chips)])
        runs[chips] = run
    a, b = runs[1], runs[DEVICES]
    out["graph_mine"] = {
        "same_table": bool(np.array_equal(a.out, b.out)),
        "same_ticks": a.totals["ticks"] == b.totals["ticks"],
        "converged": bool(b.totals["converged"]),
        "sharding": str(b.state.values.sharding.spec)}
    return out


# ------------------------------------------------------------ the tests
@pytest.fixture(scope="module")
def found(tmp_path_factory):
    # four virtual devices, each computing on one thread: the subprocess
    # shares the machine with the other test workers
    flags = (f"--xla_force_host_platform_device_count={DEVICES} "
             "--xla_cpu_multi_thread_eigen=false")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") + " "
                          + flags).strip(),
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), ROOT]),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path_factory.mktemp("jc")))
    proc = subprocess.run([sys.executable, __file__], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("alg,shards,wire", CASES, ids=IDS)
def test_mesh_session_equals_one_device_and_reference(found, alg, shards,
                                                      wire):
    case = found["cases"][IDS[CASES.index((alg, shards, wire))]]
    assert case["wire"] == wire
    assert case["values"] and case["counts"], case
    assert case["converged"] and case["ticks"] > 1, case
    assert case["sharding"] == "PartitionSpec('workers',)"
    if wire == "none":
        bound = {"cc": 0, "sssp": 1e-5, "pagerank": 1e-3}[alg]
        assert case["reference_error"] <= bound, case


def test_fork_shares_the_compiled_tick_and_keeps_the_placement(found):
    assert found["fork_compiles"] == 0
    assert found["rebound_specs"] == ["PartitionSpec('workers',)"]


def test_shards_must_split_evenly_over_the_mesh(found):
    assert found["uneven"] and "do not split evenly" in found["uneven"]


def test_graph_mine_runs_on_four_devices(found):
    run = found["graph_mine"]
    assert run["same_table"] and run["same_ticks"] and run["converged"]
    assert run["sharding"] == "PartitionSpec('workers',)"


@pytest.mark.parametrize("what", ["fault_plan", "latency", "async"])
def test_unsupported_combinations_raise(what):
    import jax
    from repro.core import engine as E
    from repro.core import graph as G
    from repro.core.faults import FaultPlan
    from repro.launch.mesh import make_worker_mesh

    cfg = _cfg("cc", 2)
    kw = {}
    if what == "fault_plan":
        kw["fault_plan"] = FaultPlan(0.5, start_tick=4, every=6)
        match = "a fault plan"
    elif what == "latency":
        cfg = _cfg("cc", 2, latency_profile="stragglers")
        match = "a latency model"
    else:
        kw["schedule"] = "async"
        match = "the async schedule"
    graph = G.build_sharded_graph(cfg)
    with pytest.raises(ValueError, match=match):
        E.EngineSession(cfg, graph=graph, mesh=make_worker_mesh(1), **kw)
    with pytest.raises(ValueError, match="device"):
        make_worker_mesh(len(jax.devices()) + 1)


if __name__ == "__main__":
    sys.path.insert(0, HERE)
    print(json.dumps(child()))
