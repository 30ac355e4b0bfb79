"""Run one benchmark cell once and print its result as the last line.

    python3 -m chipbench.run --workload wcc-s16.healthy --seed 7 \
        --seconds 40 --trace 0

The cell, its configuration, its traffic and its metrics come from
``BENCHMARK.json`` (see ``chipbench/spec.py``).  The run refuses any
platform but ``tpu``, sets up the cell from ``--seed`` (set-up: process
start to the window's start, warm-up included), measures for ``--seconds``,
checks every answer of the window against the plain reference, and prints
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``, each
compared number beside its limit.  With ``--trace 0`` the metrics are the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, read
from a profiler trace of the window.  Details go to standard error, whose
last lines are the checks.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

from chipbench import spec, trace_reduce  # noqa: E402

sys.path.insert(0, os.path.join(spec.ROOT, "src"))

import jax  # noqa: E402

from repro.launch import compile_cache  # noqa: E402

OUT = os.path.join(spec.ROOT, ".chipbench")
COMPILE_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
                  "/jax/core/compile/backend_compile_duration": "compiles"}


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def accelerator(chips: int) -> list:
    """The cell's chips; exits non-zero, printing no result, without
    a TPU or with fewer chips than the cell asks for."""
    # the TPU runtime's logs stay in the checkout (its default is in /tmp)
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(OUT, "tpu_logs"))
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} TPU chip(s); "
                         f"JAX found {len(devices)} {devices[0].platform} "
                         "device(s)")
    return devices[:chips]


def use_compile_cache() -> None:
    """The program's persistent compile cache (``<checkout>/.jax_cache``,
    or ``$JAX_COMPILATION_CACHE_DIR``), with every program kept."""
    compile_cache.use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


class CompileCounter:
    """Counts tracing and backend compiles, in all and since ``mark()``."""

    def __init__(self):
        self.total = {v: 0 for v in COMPILE_EVENTS.values()}
        self.seconds = 0.0
        self.at_mark = dict(self.total)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.total[COMPILE_EVENTS[event]] += 1
            self.seconds += duration

    def mark(self) -> None:
        self.at_mark = dict(self.total)

    def since_mark(self) -> dict:
        return {k: v - self.at_mark[k] for k, v in self.total.items()}


def span(name: str):
    """A host span in the profiler's trace (see ``trace_reduce``)."""
    return jax.profiler.TraceAnnotation(trace_reduce.PREFIX + name)


def run_cell(bench: spec.Benchmark, name: str, seed: int, seconds: float,
             trace: bool, devices: list, t_start: float) -> dict:
    """Everything but the look for a chip: set up, warm up, measure,
    check; returns the result object."""
    cell = bench.cell(name)
    config, traffic = bench.config(cell), bench.traffic(cell)
    compiles = CompileCounter()
    t_enter = time.perf_counter()
    work = spec.loop(traffic["loop"]).Workload(config, traffic, seed)
    t_built = time.perf_counter()
    work.warm()
    setup_s = time.perf_counter() - t_start
    log(f"chipbench: {name} seed {seed}: set-up {setup_s:.3f} s (to the "
        f"cell {t_enter - t_start:.3f}, data and build "
        f"{t_built - t_enter:.3f}, warm-up "
        f"{time.perf_counter() - t_built:.3f}), {compiles.total} "
        f"({compiles.seconds:.3f} s)")
    compiles.mark()

    trace_dir = os.path.join(OUT, "trace", name)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # no Python call tracing
        options.host_tracer_level = 1  # the benchmark's own spans
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    t0 = time.perf_counter()
    with span("window"):
        ctx = work.window(seconds, span)
    window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    in_window = compiles.since_mark()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    log(f"chipbench: window {window_s:.3f} s, in the window {in_window}, "
        f"peak device memory {peak} bytes")
    checks = work.check()
    checks["no_work"] = (int(work.attempted == 0), 0)

    ctx.update(setup_s=setup_s, window_s=window_s, trace=None)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {}
    if trace:
        ctx["trace"] = red = trace_reduce.read(trace_dir)
        log(f"chipbench: trace lines {red['lines']}")
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    metrics = {}
    for m in bench.metrics(cell, "per_layer" if trace else "end_to_end"):
        value = bench.reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    for job in ctx.get("jobs", []):
        log("chipbench: job", json.dumps(
            {k: v for k, v in job.items() if k != "table"}))
    for key, (value, limit) in checks.items():
        log(f"check {key} {value} limit {limit}")
    return {"correct": all(v <= lim for v, lim in checks.values()),
            "attempted": work.attempted, "failed": work.failed,
            "metrics": metrics, "device": device, **result,
            "checks": {k: {"value": v, "limit": lim}
                       for k, (v, lim) in checks.items()}}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = spec.Benchmark()
    devices = accelerator(bench.cell(args.workload)["chips"])
    use_compile_cache()
    result = run_cell(bench, args.workload, args.seed, args.seconds,
                      bool(args.trace), devices, T_START)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
