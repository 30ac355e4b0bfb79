"""Benchmark driver: one module per paper table/figure.

Each module runs inside its own ``results.collect`` scope, so every
module writes its own ``BENCH_<area>.json`` (rows cannot leak across
modules and a mid-module failure is attributed to the module that
failed, with ``status: "failed"``).  Prints ``name,us_per_call,derived``
CSV rows as before (benchmarks.common.emit).

    PYTHONPATH=src python -m benchmarks.run                 # full sweep
    PYTHONPATH=src python -m benchmarks.run --smoke         # CI subset
    PYTHONPATH=src python -m benchmarks.run --only crowded  # one module
    PYTHONPATH=src python -m benchmarks.run --out benchmarks/baselines
"""
from __future__ import annotations

import argparse
import time
import traceback

from benchmarks import results
from repro.launch.compile_cache import use_compile_cache


def modules() -> list:
    # bench_matrix is not in this list: the scenario matrix sweeps axes
    # ACROSS figures and has its own driver (and its own CI line) —
    # ``python -m benchmarks.bench_matrix [--smoke]``
    from benchmarks import (bench_crowded, bench_evolution, bench_faults,
                            bench_kernels, bench_load, bench_messages,
                            bench_parallel, bench_priority,
                            bench_scalability, bench_serve, bench_speed)
    return [bench_speed, bench_scalability, bench_parallel, bench_faults,
            bench_crowded, bench_priority, bench_messages, bench_evolution,
            bench_kernels, bench_serve, bench_load]


def main(argv=None) -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="run each module's smoke subset (CI mode)")
    ap.add_argument("--only", default="",
                    help="substring filter on module names")
    ap.add_argument("--out", default=None,
                    help="directory for BENCH_<area>.json "
                         "(default experiments/bench)")
    ap.add_argument("only_pos", nargs="?", default="",
                    help=argparse.SUPPRESS)  # back-compat positional filter
    opts = ap.parse_args(argv)
    only = opts.only or opts.only_pos

    t0 = time.time()
    failures = 0
    for m in modules():
        if only and only not in m.__name__:
            continue
        area = getattr(m, "AREA", m.__name__.split("bench_", 1)[-1])
        smoke_fn = getattr(m, "smoke", None)
        if opts.smoke and smoke_fn is None:
            # figure-only module with no CI-sized subset: a full run in
            # smoke mode would both be slow and commit full-mode numbers
            # under a smoke baseline
            print(f"[skip] {m.__name__}: no smoke subset")
            continue
        fn = smoke_fn if opts.smoke else m.main
        mode = "smoke" if opts.smoke else "full"
        try:
            with results.collect(area, mode=mode, out_dir=opts.out):
                fn()
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"[FAIL] {m.__name__}")
            traceback.print_exc()
    print(f"\n== benchmarks done in {time.time() - t0:.0f}s, "
          f"{failures} failures ==")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
