"""Drive the mining and serving entry points once on a TPU and check every
answer against an independent oracle.

    python chip_smoke.py            # one chip: mining, serving, kernel
    python chip_smoke.py --chips 4  # four chips: mesh session vs one chip

One process holds the chip for the whole run; phases run in order and any
failed check, unconverged run or exception exits non-zero.  Each phase
prints one ``observation`` line (wall time, graph build, trace+compile
seconds, ticks, device peak memory): these are observations of this run,
not benchmark metrics.  The last line of a passing run is the JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.  On a machine
whose JAX finds no TPU the script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import AxisType, Mesh  # noqa: E402

from repro.configs import get_graph_config  # noqa: E402
from repro.core import engine as E  # noqa: E402
from repro.core import graph as G  # noqa: E402
from repro.core import merger  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels.semiring_spmv import EDGE_BLOCK, spmv_partials  # noqa: E402
from repro.launch import graph_mine, graph_serve  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.serve.engine import DeadlineExceeded  # noqa: E402

STORE_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke_store")
# Sized so the whole run fits its 1200 s limit on one v5e, where the
# plain tick costs 0.84 s at asymp_cc_large (rmat18, 1246 ticks to
# quiescence: over the limit alone) and 0.15 s at asymp_cc (rmat16).
# CC is mined, and ticked on four chips, at asymp_cc.  Serving pagerank
# at rmat16 takes thousands of those ticks, so the server runs the
# rmat14 pagerank graph, and every pagerank job selects its whole
# frontier each tick (enforce fraction 1.0: 2848 ticks under kills where
# the config's 0.5 takes 7206, to the same fixpoint).
MINE_CONFIG = "asymp_cc"
PAGERANK_CONFIG = "asymp_pagerank"
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def need(ok: bool, msg: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def _config(name: str, reduced: bool):
    cfg = get_graph_config(name)
    return cfg.reduced() if reduced else cfg


def _size_args(reduced: bool) -> list[str]:
    return ["--reduced"] if reduced else []


# ======================================================================
# Phases: each runs one path, checks it, returns its observations
# ======================================================================
def phase_mine_cc(reduced: bool = False) -> dict:
    """CC vs union-find on the generated edges; then half the shards
    killed (replay recovery) must give the same table."""
    base = ["--config", MINE_CONFIG, *_size_args(reduced)]
    healthy = graph_mine.main(base)
    need(healthy.totals["converged"], f"{MINE_CONFIG} did not converge")
    cfg = _config(MINE_CONFIG, reduced)
    t0 = time.time()
    oracle = G.cc_oracle(cfg.num_vertices, G.generate_edges(cfg))
    oracle_s = time.time() - t0
    need(np.array_equal(healthy.out, oracle),
         f"{MINE_CONFIG} labels differ from cc_oracle")
    faulty = graph_mine.main([*base, "--failures", "0.5"])
    need(faulty.totals["converged"], f"{MINE_CONFIG} --failures 0.5 did "
         "not converge")
    need(faulty.totals["failures"] > 0, "the fault plan killed no shard")
    need(np.array_equal(healthy.out, faulty.out),
         f"{MINE_CONFIG} --failures 0.5 table differs from the healthy run")
    return {"build_s": healthy.build_s + faulty.build_s,
            "propagate_s": healthy.propagate_s,
            "propagate_faulty_s": faulty.propagate_s,
            "ticks": healthy.totals["ticks"],
            "ticks_faulty": faulty.totals["ticks"],
            "components": int(len(np.unique(healthy.out))),
            "failures": faulty.totals["failures"],
            "replayed": faulty.totals["replayed"], "oracle_s": oracle_s}


def phase_mine_pagerank_failures(reduced: bool = False) -> dict:
    """Push-mode pagerank under kills (checkpoint restore) vs dense
    pagerank with the absorb-dangling convention."""
    run = graph_mine.main(["--config", PAGERANK_CONFIG, "--failures", "0.5",
                           "--enforce", "1.0", *_size_args(reduced)])
    need(run.totals["converged"], f"{PAGERANK_CONFIG} did not converge")
    need(run.totals["failures"] > 0, "the fault plan killed no shard")
    damping = _config(PAGERANK_CONFIG, reduced).damping
    n = run.graph.num_real_vertices
    oracle = np.asarray(ops.pagerank(run.graph, damping=damping, iters=80,
                                     dangling="absorb"), np.float64)
    l1 = float(np.abs(run.out.astype(np.float64) / n - oracle).sum())
    mass = merger.mass_balance(run.state, run.graph, damping)
    need(l1 < 1e-3, f"pagerank L1 to the dense oracle is {l1!r}")
    need(abs(mass - 1.0) < 1e-5, f"pagerank mass balance is {mass!r}")
    return {"build_s": run.build_s, "ticks": run.totals["ticks"],
            "failures": run.totals["failures"], "l1": l1,
            "mass_error": mass - 1.0}


def phase_serve(reduced: bool = False, store_dir: str = STORE_DIR) -> dict:
    """Queries and streaming deltas through the fixpoint store; after the
    deltas, component_of agrees with union-find on the patched graph."""
    shutil.rmtree(store_dir, ignore_errors=True)
    run = graph_serve.main(["--config", PAGERANK_CONFIG, "--programs",
                            "cc,pagerank", "--enforce-fraction", "1.0",
                            "--store", store_dir, *_size_args(reduced)])
    adm = run.admission
    need(adm["rejected"] == 0, f"{adm['rejected']} queries rejected")
    need(adm["served"] == adm["submitted"] == len(run.answers),
         f"answered {len(run.answers)} of {adm['submitted']} queries")
    need(not any(isinstance(a, DeadlineExceeded)
                 for a in run.answers.values()), "a query missed its deadline")
    srv = run.server
    need(srv.deltas_applied > 0, "no delta was applied")
    n = srv.graph.num_real_vertices
    edges = G.edge_list(srv.graph)
    oracle = G.cc_oracle(n, edges[edges[:, 0] < edges[:, 1]])
    ids = np.random.default_rng(0).choice(n, size=min(n, 512), replace=False)
    need(np.array_equal(srv.component_of(ids), oracle[ids]),
         "component_of after the deltas differs from cc_oracle")
    return {"build_s": run.build_s, "converge_s": run.converge_s,
            "ticks": {name: s.totals["ticks"]
                      for name, s in srv.sessions.items()},
            "queries": len(run.answers), "deltas": srv.deltas_applied,
            "epoch": srv.epoch}


def phase_kernel(reduced: bool = False) -> dict:
    """The Pallas semiring SpMV against the XLA reference on MINE_CONFIG."""
    t0 = time.time()
    g = G.build_sharded_graph(_config(MINE_CONFIG, reduced))
    pg = ops.build_pulled_graph(g)
    build_s = time.time() - t0
    n = g.num_real_vertices

    labels = jnp.arange(n, dtype=jnp.int32)
    got = ops.frontier_pull_step(labels, pg, semiring="min", use_kernel=True)
    ref = ops.frontier_pull_step(labels, pg, semiring="min", use_kernel=False)
    need(np.array_equal(np.asarray(got), np.asarray(ref)),
         "kernel min pull step differs from the reference")

    deg = np.maximum(g.degrees().reshape(-1)[:n], 1).astype(np.float32)
    contrib = jnp.asarray(np.float32(1.0 / n) / deg)  # unit total mass
    got = np.asarray(ops.frontier_pull_step(contrib, pg,
                                            semiring="plus_times",
                                            use_kernel=True), np.float64)
    ref = np.asarray(ops.frontier_pull_step(contrib, pg,
                                            semiring="plus_times",
                                            use_kernel=False), np.float64)
    rel_l1 = float(np.abs(got - ref).sum() / np.abs(ref).sum())
    need(rel_l1 <= 1e-6, f"kernel plus_times relative L1 is {rel_l1!r}")

    # the kernel is compiled, not interpreted, exactly when on the TPU
    edges = jnp.zeros((EDGE_BLOCK,), jnp.int32)
    text = jax.jit(lambda v, d: spmv_partials(v, d, None, semiring="min")
                   ).lower(edges, edges).as_text()
    compiled = "tpu_custom_call" in text
    need(compiled == (jax.default_backend() == "tpu"),
         f"kernel compiled={compiled} on backend {jax.default_backend()}")
    return {"build_s": build_s, "edges_padded": int(pg.edge_src.shape[0]),
            "plus_times_rel_l1": rel_l1, "compiled_kernel": compiled}


def phase_dist_vs_local(devices, reduced: bool = False) -> dict:
    """``EngineSession`` on a ``workers`` mesh of ``devices`` (its shards
    split over them, the tick under ``shard_map``) against the session on
    one device, on MINE_CONFIG: bitwise-equal fixpoints after the same
    ticks, both equal to union-find."""
    cfg = _config(MINE_CONFIG, reduced)
    t0 = time.time()
    graph = G.build_sharded_graph(cfg)
    build_s = time.time() - t0

    mesh = Mesh(np.asarray(devices), ("workers",),
                axis_types=(AxisType.Auto,))
    dist = E.EngineSession(cfg, graph=graph, mesh=mesh)
    if len(devices) > 1:  # a one-device axis lowers to no collective
        need("all_to_all" in dist._tick_fn.lower(dist.state,
                                                 dist.g).as_text(),
             "the mesh session's tick has no all_to_all")
    t0 = time.time()
    dist_totals = dist.tick_until_quiescent()
    dist_s = time.time() - t0

    with jax.default_device(devices[0]):
        local = E.EngineSession(cfg, graph=graph)
        t0 = time.time()
        local_totals = local.tick_until_quiescent()
    local_s = time.time() - t0

    need(dist_totals["converged"] and local_totals["converged"],
         "a session did not reach quiescence")
    dist_values = np.asarray(dist.state.values)
    need(np.array_equal(dist_values, np.asarray(local.state.values)),
         "the mesh session's fixpoint differs from the one-device one's")
    need(dist_totals["ticks"] == local_totals["ticks"]
         and dist_totals["sent"] == local_totals["sent"],
         "the mesh session ran other ticks than the one-device one")
    oracle = G.cc_oracle(cfg.num_vertices, G.generate_edges(cfg))
    need(np.array_equal(merger.extract(dist.state, graph, dist.prog),
                        oracle),
         "the mesh session's labels differ from cc_oracle")
    return {"build_s": build_s, "workers": len(devices),
            "shards": cfg.num_shards,
            "dist_ticks": dist_totals["ticks"], "dist_s": dist_s,
            "local_ticks": local_totals["ticks"], "local_s": local_s}


# ======================================================================
def _peak_bytes(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh-session phase")
    args = ap.parse_args(argv)
    use_compile_cache()

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found platform "
                         f"{platform!r} ({len(devices)} device(s))")
    need(len(devices) >= args.chips,
         f"--chips {args.chips} but JAX found {len(devices)} device(s)")

    compile_s = [0.0]

    def on_duration(event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            compile_s[0] += duration

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    if args.chips == 4:
        phases = [("dist_vs_local",
                   lambda: phase_dist_vs_local(devices[:4]))]
    else:
        phases = [("mine_cc", phase_mine_cc),
                  ("mine_pagerank_failures", phase_mine_pagerank_failures),
                  ("serve", phase_serve),
                  ("kernel", phase_kernel)]
    t_all = time.time()
    for name, fn in phases:
        compile_s[0] = 0.0
        t0 = time.time()
        obs = fn()
        obs = {"phase": name, "wall_s": time.time() - t0,
               "trace_compile_s": compile_s[0], **obs,
               "peak_bytes_in_use": _peak_bytes(devices[:args.chips])}
        print("observation " + json.dumps(obs), flush=True)
    print("observation " + json.dumps({"total_wall_s": time.time() - t_all}),
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
