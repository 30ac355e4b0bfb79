"""Shared benchmark utilities. Output convention: ``name,us_per_call,derived``
CSV rows to stdout, mirrored into the active ``benchmarks.results``
recorder so every run also produces machine-readable ``BENCH_<area>.json``
(see ``benchmarks/results.py`` for the schema and ``tools/bench_diff.py``
for the trajectory gate)."""
from __future__ import annotations

import sys
import time
from typing import NamedTuple, Optional

import numpy as np

from benchmarks import results
from repro.configs.base import GraphConfig
from repro.core import engine as E
from repro.core import graph as G
from repro.launch.compile_cache import use_compile_cache


def emit(name: str, us_per_call: float, derived: str = "", *,
         scenario=None, verdict: Optional[str] = None, units: str = "us",
         config: Optional[GraphConfig] = None, metrics=None) -> None:
    """One result row: printed as CSV (back-compat) AND recorded in the
    active results scope with module / scenario / verdict / units fields.

    ``config=cfg`` derives the scenario cell from a GraphConfig;
    ``verdict`` is "pass" / "fail" / "skip" for gate rows (None for
    plain measurements); ``derived`` ``k=v;k=v`` pairs are parsed into
    typed metrics automatically."""
    if scenario is None and config is not None:
        scenario = results.scenario_from_config(config)
    results.record(name, us_per_call, derived,
                   module=results.caller_module(2), scenario=scenario,
                   verdict=verdict, units=units, metrics=metrics)
    print(f"{name},{us_per_call:.1f},{derived}")


class Timing(NamedTuple):
    """Steady-state vs first-call timing of one measured callable."""
    steady_us: float  # per-call, AFTER warmup — the trajectory number
    compile_us: float  # first (warmup) call: includes JIT compilation
    repeats: int


def timed(fn, *args, repeats: int = 1, warmup: int = 1, **kw):
    """Time ``fn`` with an explicit warmup: the first call of a jitted
    function is dominated by compilation, so without a warmup (and with
    the old default ``repeats=1``) the reported us_per_call WAS the
    compile time.  Returns ``(out, Timing)`` — record BOTH fields in the
    emitted row so the trajectory tracks steady-state and compile cost
    separately."""
    compile_us = 0.0
    out = None
    for _ in range(max(warmup, 0)):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        compile_us = max(compile_us, (time.perf_counter() - t0) * 1e6)
    repeats = max(repeats, 1)
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args, **kw)
    steady_us = (time.perf_counter() - t0) / repeats * 1e6
    return out, Timing(steady_us, compile_us, repeats)


def bench_cli(area: str, main_fn, smoke_fn=None, argv=None) -> None:
    """Entry point shared by every ``bench_*`` module's ``__main__``:
    picks smoke vs full mode and scopes the run's rows into
    ``BENCH_<area>.json`` (``--out DIR`` overrides the destination)."""
    use_compile_cache()
    argv = sys.argv[1:] if argv is None else argv
    smoke = "--smoke" in argv and smoke_fn is not None
    out_dir = None
    if "--out" in argv:
        out_dir = argv[argv.index("--out") + 1]
    with results.collect(area, mode="smoke" if smoke else "full",
                         out_dir=out_dir):
        (smoke_fn if smoke else main_fn)()


def graph_family(sizes=(12, 14, 16), shards=8, algorithm="cc", **kw):
    for log2n in sizes:
        cfg = GraphConfig(
            name=f"rmat{log2n}", algorithm=algorithm,
            num_vertices=1 << log2n, avg_degree=16, generator="rmat",
            num_shards=shards, priority="log", enforce_fraction=0.1, **kw)
        yield cfg


def run_asymp(cfg: GraphConfig, graph=None, **kw):
    graph = graph or G.build_sharded_graph(cfg)
    t0 = time.perf_counter()
    state, totals = E.run_to_convergence(cfg, graph=graph, **kw)
    totals["wall_s"] = time.perf_counter() - t0
    return graph, state, totals


def csr_edges(g, with_weights=False):
    """Recover the (already symmetrized) edge list from a ShardedGraph —
    the oracle checks in the scenario matrix need the exact edges the
    engine ran on, not a re-generation."""
    srcs, dsts, ws = [], [], []
    for p in range(g.num_shards):
        deg = g.row_ptr[p, 1:] - g.row_ptr[p, :-1]
        cnt = int(g.edge_counts[p])
        src_local = np.repeat(np.arange(g.vs), deg)[:cnt]
        srcs.append(src_local + p * g.vs)
        dsts.append(g.col_idx[p, :cnt])
        if with_weights:
            ws.append(g.weights[p, :cnt])
    edges = np.stack([np.concatenate(srcs), np.concatenate(dsts)], axis=1)
    if with_weights:
        return edges, np.concatenate(ws)
    return edges
