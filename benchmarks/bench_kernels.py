"""Framework kernel microbench: semiring SpMV throughput (edges/s proxy).

The kernel compiles on the TPU and runs in Pallas interpret mode on the
CPU backend, so a CPU run times the interpreter, not the kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import bench_cli, emit, timed
from repro.kernels import ref as R
from repro.kernels.semiring_spmv import EDGE_BLOCK, spmv_partials

AREA = "kernels"


def main() -> None:
    print(f"== kernels: semiring SpMV ({jax.default_backend()}) ==")
    key = jax.random.PRNGKey(0)
    n = 32 * EDGE_BLOCK
    vals = jax.random.uniform(key, (n,), jnp.float32, 0, 10)
    dst = jax.random.randint(key, (n,), -1, 128)
    w = jax.random.uniform(key, (n,), jnp.float32, 0.1, 1.0)
    for semiring in ("min", "min_plus", "plus_times"):
        f = jax.jit(lambda v, d, ww, s=semiring: spmv_partials(
            v, d, ww, semiring=s))
        _, t = timed(lambda: f(vals, dst, w).block_until_ready(), repeats=3)
        emit(f"kernels/spmv/{semiring}", t.steady_us,
             f"edges={n};Medges_per_s={n / t.steady_us:.2f};"
             f"compile_us={t.compile_us:.1f}")
        fr = jax.jit(lambda v, d, ww, s=semiring: R.spmv_partials_ref(
            v, d, ww, semiring=s))
        _, tr = timed(lambda: fr(vals, dst, w).block_until_ready(),
                      repeats=3)
        emit(f"kernels/spmv_ref/{semiring}", tr.steady_us,
             f"impl=reference;compile_us={tr.compile_us:.1f}")


if __name__ == "__main__":
    bench_cli(AREA, main)
