"""Batch jobs back to back: the way ``graph_mine`` runs a mining job.

Set-up generates the configuration's graphs with the benchmark's own Graph500
generator, from the graph seeds the configuration lists, builds each with the
program's ``build_sharded_graph``, and pads every shard's CSR to the width of
the largest shard among them, so that every run compiles the same tick; the
engine sizes its per-tick edge budget from that width by its own rule.
Every run mines the same graphs, so that its work does not vary with
``--seed``: the run's seed orders them and, where the traffic kills shards,
draws each job's kill plan (which shards die, in which order).
One ``EngineSession`` per graph is the template; every job is a ``fork()``
of its template, which shares the compiled tick.
Warm-up runs one fork for one tick, or through the first kill when the
traffic kills shards, and extracts its table.

In the window, each job runs ``tick_until_quiescent`` and then
``merger.extract``, as ``graph_mine`` does.  Jobs run in rounds, one job on
each of the run's graphs in order, so that every graph weighs the same;
rounds start until the window's time is up, and the last round started runs
to its end.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from chipbench import spec
from chipbench.graph500 import kronecker_edges
from repro.configs.base import GraphConfig
from repro.core import graph as G
from repro.core import merger
from repro.core.engine import EngineSession
from repro.core.faults import FaultPlan


def pad_edges(graph: G.ShardedGraph, width: int) -> G.ShardedGraph:
    """The same CSR with every shard's edge arrays padded to ``width``."""
    if graph.es > width:
        raise ValueError(f"a shard holds {graph.es} directed edges, more "
                         f"than the padded width {width}")
    pad = ((0, 0), (0, width - graph.es))
    return dataclasses.replace(
        graph, col_idx=np.pad(graph.col_idx, pad, constant_values=-1),
        weights=(None if graph.weights is None
                 else np.pad(graph.weights, pad)))


def graph_config(config: dict, seed: int) -> GraphConfig:
    g, eng = config["graph"], config["engine"]
    return GraphConfig(
        name=config["name"], algorithm=eng["algorithm"],
        num_vertices=1 << g["scale"], avg_degree=g["edgefactor"],
        rmat_abcd=tuple(g["initiator"]), num_shards=eng["num_shards"],
        priority=eng["priority"], enforce_fraction=eng["enforce_fraction"],
        max_ticks=eng["max_ticks_per_job"],
        weighted=g["weighted"], seed=seed)


class Workload:
    def __init__(self, config: dict, traffic: dict, seed: int):
        g = config["graph"]
        self.cfg = graph_config(config, seed)
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
        self.templates = []
        for graph in graphs:
            graph = pad_edges(graph, width)
            if not self.templates:
                session = EngineSession(self.cfg, graph=graph,
                                        fault_plan=self.kill_plan())
            else:  # share the first template's compiled tick
                session = self.templates[0].fork()
                session.rebind_graph(graph)
            self.templates.append(session)
        self.jobs: list[dict] = []

    def kill_plan(self) -> FaultPlan | None:
        """The traffic's fault plan, its shards drawn from the run's seed."""
        if not self.faults:
            return None
        return FaultPlan(**self.faults,
                         seed=int(self.rng.integers(2**31)))

    def kills(self, job: EngineSession) -> dict:
        """A job's kill plan, for its record: seed and ticks."""
        plan = job.fault_plan
        if plan is None:
            return {"kill_seed": None, "kill_ticks": []}
        return {"kill_seed": plan.seed,
                "kill_ticks": sorted(plan.schedule(self.cfg.num_shards))}

    def warm(self) -> None:
        session = self.templates[0].fork()
        plan = session.fault_plan
        for _ in range(1 + (plan.start_tick if plan else 0)):
            session.step()
        merger.extract(session.state, session.graph, session.prog)

    def window(self, seconds: float, span) -> dict:
        """Run rounds of jobs until ``seconds`` have passed; the context
        that the metric readers get."""
        t_end = time.perf_counter() + seconds
        while time.perf_counter() < t_end:
            for i, template in enumerate(self.templates):
                with span("session.fork"):
                    job = template.fork()
                    job.fault_plan = self.kill_plan()
                t0 = time.perf_counter()
                with span("session.tick_until_quiescent"):
                    totals = job.tick_until_quiescent()
                with span("merger.extract"):
                    table = merger.extract(job.state, job.graph, job.prog)
                wall = time.perf_counter() - t0
                self.jobs.append({
                    "graph": i, "graph_seed": self.graph_seeds[i],
                    "wall_s": wall, "table": table, **self.kills(job),
                    **{k: totals[k] for k in ("ticks", "sent", "replayed",
                                              "failures", "converged")}})
        return {"jobs": self.jobs}

    @property
    def attempted(self) -> int:
        return len(self.jobs)

    @property
    def failed(self) -> int:
        return sum(not j["converged"] for j in self.jobs)

    def check(self) -> dict:
        """Each job's table against the reference on its graph; each
        check is ``(value, limit)``."""
        ref = self.reference
        n = self.cfg.num_vertices
        truth = [ref.solve(n, e) for e in self.edges]
        return {
            ref.CHECK: (max((ref.compare(truth[j["graph"]], j["table"])
                             for j in self.jobs), default=0), ref.LIMIT),
            "unconverged_jobs": (self.failed, 0),
            "kills_missed": (sum(sum(t < j["ticks"] for t in j["kill_ticks"])
                                 - j["failures"] for j in self.jobs), 0),
        }
