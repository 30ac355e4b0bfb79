"""Pallas TPU kernel: semiring edge-propagation (the ASYMP hot loop).

The paper's compute hot-spot is message creation + delivery over edges.  On
TPU we adapt it as a *pull-mode semiring SpMV* over a
destination-sorted edge stream:

    out[dst] = REDUCE over in-edges e: COMBINE(values[src_e], w_e)

with semirings (min, .) for CC, (min, +) for SSSP/BFS, (max, .) for
label propagation, (max, min) for widest path, (or, .) for reachability,
and (+, *) for PageRank.  Every idempotent REDUCE is one of the
``repro.core.semiring`` Aggregators — the kernel takes its identity and
reduce from the same definitions the engine aggregates with, so kernel
names and engine programs cannot drift.  Aggregator semirings reduce
*clamped at the identity* (the masked lanes of a tile contribute it), so
payloads are assumed to live in the aggregator's domain — at or above
the identity for MAX/OR (labels, widths >= 0), at or below for MIN;
ref.py applies the same clamp.

TPU mapping (the C2 state/edge asymmetry, one level down the hierarchy):
  * vertex values stay resident; the big edge arrays stream HBM -> VMEM in
    fixed blocks via BlockSpec — the kernel's DMA pipeline is the analogue of
    ASYMP's I/O threads overlapping its CPU threads;
  * edges are pre-sorted by destination and padded so each EDGE_BLOCK maps to
    exactly one 128-wide destination tile;
  * each grid step takes ROWS edge blocks as one [ROWS, EDGE_BLOCK] slab (the
    TPU tiling needs the last two block dimensions to be multiples of
    (8, 128)), transposes it in VMEM so edges run down the sublanes, and
    writes a [ROWS, TILE] block of partials;
  * within a block, the segment-reduce is a dense masked compare/select over
    an [EB, TILE] lane grid — branch-free VPU work, no atomics needed because
    the semiring reduce is commutative/idempotent (paper C5, locklessness);
  * the (+, *) semiring can instead use a one-hot matmul so the reduction
    runs on the MXU;
  * cross-block combination of per-block partials is a tiny segment-reduce
    done outside the kernel (ops.py).

The kernel compiles for the TPU; on the CPU backend (tests, rehearsals) it
runs in Pallas interpret mode, and ref.py is the oracle for both.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.semiring import for_semiring

TILE = 128  # destination vertices per tile (= VPU lane width)
EDGE_BLOCK = 512  # edges per destination-tile block
ROWS = 8  # edge blocks per grid step (the sublane tile)

SEMIRINGS = ("min", "min_plus", "max", "max_min", "or", "plus_times")


def _identity(semiring: str, dtype):
    agg = for_semiring(semiring)  # plus_times -> SUM ((+)-identity 0)
    kind = ("int32" if jnp.issubdtype(jnp.dtype(dtype), jnp.integer)
            else "float32")
    return jnp.array(agg.identity(kind), dtype)


def _combine(semiring: str, vals, w):
    if semiring in ("min", "max", "or"):
        return vals
    if semiring == "min_plus":
        return vals + w
    if semiring == "max_min":
        return jnp.minimum(vals, w)  # path bottleneck
    return vals * w  # plus_times


def _spmv_kernel(vals_ref, dst_ref, w_ref, out_ref, *, semiring: str,
                 dtype, use_mxu: bool):
    """ROWS edge blocks -> [ROWS, TILE] partial reductions."""
    cand = _combine(semiring, vals_ref[...], w_ref[...])  # [ROWS, EB]
    # edges down the sublanes, so each block's [EB, 1] column broadcasts
    # across the TILE lanes
    cand_t = cand.T  # [EB, ROWS]
    dst_t = dst_ref[...].T  # int32, local to the block's tile; -1 = pad
    lane = jax.lax.broadcasted_iota(jnp.int32, (EDGE_BLOCK, TILE), 1)
    agg = for_semiring(semiring)
    ident = _identity(semiring, dtype)
    rows = []
    for r in range(ROWS):
        hit = dst_t[:, r:r + 1] == lane  # [EB, TILE] — dense, branch-free
        if semiring == "plus_times" and use_mxu:
            # one-hot matmul: reduction runs on the systolic array (all
            # ROWS candidate rows ride along; row r is the one for hit)
            row = jnp.dot(cand.astype(jnp.float32), hit.astype(jnp.float32),
                          preferred_element_type=jnp.float32)[r:r + 1]
        else:
            row = agg.reduce(jnp.where(hit, cand_t[:, r:r + 1], ident),
                             axis=0, keepdims=True)
            if agg.idempotent:
                # explicit clamp at the identity: a lane fully covered by
                # hits would otherwise escape the masked fill's clamp
                row = agg.tie(row, ident)
        rows.append(row.astype(dtype))
    out_ref[...] = jnp.concatenate(rows, axis=0)


def spmv_partials(edge_vals: jnp.ndarray, edge_dst_local: jnp.ndarray,
                  edge_weights: Optional[jnp.ndarray], *, semiring: str,
                  use_mxu: bool = False,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """[n_blocks*EB] edge stream -> [n_blocks, TILE] per-block partials.

    edge_dst_local: destination index within the block's tile (-1 = padding).
    ``interpret`` defaults to the backend: the Pallas interpreter on the
    CPU, the compiled kernel anywhere else.  Interpret mode is refused off
    the CPU, so a chip run can never fall back to it.
    """
    assert semiring in SEMIRINGS, semiring
    on_cpu = jax.default_backend() == "cpu"
    if interpret is None:
        interpret = on_cpu
    elif interpret and not on_cpu:
        raise ValueError("interpret mode is for the CPU backend only; "
                         f"this backend is {jax.default_backend()!r}")
    dtype = edge_vals.dtype
    n = edge_vals.shape[0]
    assert n % EDGE_BLOCK == 0, n
    n_blocks = n // EDGE_BLOCK
    if edge_weights is None:
        edge_weights = jnp.ones((n,), dtype)
    # pad to whole ROWS-block grid steps; padded edges carry dst -1
    pad = (-n_blocks) % ROWS
    ev = jnp.pad(edge_vals, (0, pad * EDGE_BLOCK))
    ed = jnp.pad(edge_dst_local, (0, pad * EDGE_BLOCK), constant_values=-1)
    ew = jnp.pad(edge_weights.astype(dtype), (0, pad * EDGE_BLOCK))
    rows_total = n_blocks + pad
    shape2 = (rows_total, EDGE_BLOCK)

    kernel = functools.partial(_spmv_kernel, semiring=semiring, dtype=dtype,
                               use_mxu=use_mxu)
    spec = pl.BlockSpec((ROWS, EDGE_BLOCK), lambda b: (b, 0))
    out = pl.pallas_call(
        kernel,
        grid=(rows_total // ROWS,),
        in_specs=[spec, spec, spec],
        out_specs=pl.BlockSpec((ROWS, TILE), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_total, TILE), dtype),
        interpret=interpret,
    )(ev.reshape(shape2), ed.astype(jnp.int32).reshape(shape2),
      ew.reshape(shape2))
    return out[:n_blocks]
