"""Batch jobs back to back with the graph split over a mesh of chips: the way
``graph_mine --chips N`` runs a mining job.

Everything but the placement is ``jobs``: the same graphs from the
benchmark's generator, the same CSR padding, one template session per graph
whose forks share the compiled tick, the same warm-up, window and check.
The templates take a ``workers`` mesh of as many of the configuration's
chips (``"mesh": {"chips": n}``) as the process has (``make_worker_mesh``),
so the session splits state and graph over the chips by shard and runs the
tick under ``shard_map``.  The window's context also holds the mesh's size,
``mesh_devices``, and ``wire_bytes_per_chip_tick``: the bytes each chip sends
to the others each tick, the ``(n - 1) / n`` of its send buffers bound for
shards on other chips, as the wire codec encodes them.
"""
from __future__ import annotations

import jax
import numpy as np

from chipbench import spec
from chipbench.graph500 import kronecker_edges
from chipbench.loops import jobs
from repro.core import engine as E
from repro.core import graph as G
from repro.launch.mesh import make_worker_mesh


class Workload(jobs.Workload):
    def __init__(self, config: dict, traffic: dict, seed: int):
        g = config["graph"]
        self.cfg = jobs.graph_config(config, seed)
        self.reference = spec.reference(config["reference"])
        self.faults = traffic.get("faults")
        self.rng = np.random.default_rng(seed)
        order = self.rng.permutation(len(g["seeds"]))
        self.graph_seeds = [g["seeds"][i] for i in order]
        self.edges = [kronecker_edges(g["scale"], g["edgefactor"],
                                      g["initiator"], s)
                      for s in self.graph_seeds]
        graphs = [G.build_sharded_graph(self.cfg, edges=e)
                  for e in self.edges]
        width = max(graph.es for graph in graphs)
        self.mesh = make_worker_mesh(min(config["mesh"]["chips"],
                                         len(jax.devices())))
        self.templates = []
        for graph in graphs:
            graph = jobs.pad_edges(graph, width)
            if not self.templates:
                session = E.EngineSession(self.cfg, graph=graph,
                                          fault_plan=self.kill_plan(),
                                          mesh=self.mesh)
            else:  # share the first template's compiled tick
                session = self.templates[0].fork()
                session.rebind_graph(graph)
            self.templates.append(session)
        self.jobs: list[dict] = []

    def wire_bytes_per_chip_tick(self) -> int:
        session = self.templates[0]
        n = self.mesh.devices.size
        total = E.wire_codec(session.prog, session.ep).wire_bytes_per_tick()
        return total * (n - 1) // (n * n)

    def window(self, seconds: float, span) -> dict:
        ctx = super().window(seconds, span)
        ctx.update(mesh_devices=int(self.mesh.devices.size),
                   wire_bytes_per_chip_tick=self.wire_bytes_per_chip_tick())
        return ctx
