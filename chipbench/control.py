"""The control of a cell's check, and the faults the check has to catch.

Each entry is a context manager that breaks the program underneath an
otherwise unchanged run of the harness.  ``CONTROLS`` break one guarantee
that the configuration states; ``FAULTS`` are the faults that any cell of
this kind can have.  The benchmark's own runs use none of them; the tests
in ``chipbench/tests`` run each at a small size on the CPU, and on the chip

    python3 -m chipbench.control --workload wcc-s16.healthy \
        --seeds 11,12,13 --seconds 10 [--control early_return]

runs the sound program and then each control at the cell's own size, all
in one process, one line of readings per run.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import time
from unittest import mock

import jax.numpy as jnp
import numpy as np

from chipbench import run, spec
from repro.core import engine as E
from repro.core import merger
from repro.core.faults import FaultManager
from repro.dist import exchange as ex_mod


@contextlib.contextmanager
def early_return():
    """Breaks "a job returns only at verified quiescence": the job stops
    after half of the ticks it needs and reports itself converged."""
    orig = E.EngineSession.tick_until_quiescent

    def half(self, budget=None):
        probe = self.fork()
        needed = orig(probe, budget)["ticks"] - self.totals["ticks"]
        out = orig(self, needed // 2)
        out["converged"] = True
        return out

    with mock.patch.object(E.EngineSession, "tick_until_quiescent", half):
        yield


@contextlib.contextmanager
def no_replay():
    """Breaks "killed shards recover to the same labels": a killed shard
    rolls back to its snapshot, but peers neither replay their logged
    messages nor re-activate the vertices with edges into it."""
    orig = FaultManager.fail_shard

    def fail_shard(self, t, state, p):
        graph = self.graph
        self.msg_log.clear()
        self.graph = dataclasses.replace(
            graph, boundary=np.zeros_like(graph.boundary))
        try:
            return orig(self, t, state, p)
        finally:
            self.graph = graph

    with mock.patch.object(FaultManager, "fail_shard", fail_shard):
        yield


@contextlib.contextmanager
def _wrap_tick(after):
    """Every plain tick built while active passes its output through
    ``after(state_in, state_out, stats, bufs)``."""
    orig = E.make_local_tick

    def make(prog, ep, weighted):
        tick = orig(prog, ep, weighted)
        return lambda state, g: after(state, *tick(state, g))

    with mock.patch.object(E, "make_local_tick", make):
        yield


def state_unchanged():
    """A tick that returns the state it was given."""
    return _wrap_tick(lambda state, new, stats, bufs: (state, stats, bufs))


@contextlib.contextmanager
def _wrap_exchange(mask):
    """Receive buffers ``[Pn, P, cap]`` lose every slot where ``mask``
    (same shape, bool) is False."""
    orig = ex_mod.exchange_local

    def exchange(codec, sv, si):
        rv, ri = orig(codec, sv, si)
        return rv, jnp.where(mask(ri.shape), ri, -1)

    with mock.patch.object(ex_mod, "exchange_local", exchange):
        yield


def half_batch():
    """Half of each tick's messages never arrive: those of the upper half
    of the senders."""
    return _wrap_exchange(
        lambda shape: (np.arange(shape[1]) < shape[1] // 2)[None, :, None])


def no_exchange():
    """The exchange between shards left out: a shard hears only its own
    messages."""
    return _wrap_exchange(
        lambda shape: np.eye(shape[0], shape[1], dtype=bool)[:, :, None])


@contextlib.contextmanager
def answer_altered():
    """One vertex's label altered where the merger produces the table."""
    orig = merger.extract

    def extract(state, graph, prog):
        table = np.array(orig(state, graph, prog))
        table[len(table) // 2] += 1
        return table

    with mock.patch.object(merger, "extract", extract):
        yield


CONTROLS = {"early_return": early_return, "no_replay": no_replay}
FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "no_exchange": no_exchange, "answer_altered": answer_altered}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", action="append", choices=sorted(CONTROLS))
    args = ap.parse_args(argv)
    bench = spec.Benchmark()
    devices = run.accelerator(bench.cell(args.workload)["chips"])
    run.use_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        for name in [None, *(args.control or [])]:
            with (CONTROLS[name]() if name else contextlib.nullcontext()):
                r = run.run_cell(bench, args.workload, seed, args.seconds,
                                 False, devices, time.perf_counter())
            print(json.dumps({"seed": seed, "control": name,
                              "correct": r["correct"],
                              "attempted": r["attempted"],
                              "checks": r["checks"]}), flush=True)


if __name__ == "__main__":
    main()
