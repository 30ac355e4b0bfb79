"""jit'd wrappers around the semiring SpMV kernel + host-side preprocessing.

``PulledGraph`` is the kernel-ready edge layout: destination-sorted edges,
tile-padded so every EDGE_BLOCK belongs to exactly one 128-destination tile.
``frontier_pull_step`` runs one full-frontier propagation (the synchronous
Pregel-equivalent iteration used as the paper's BSP baseline in benchmarks)
and is also the bulk-delivery primitive for pre-bucketed ASYMP messages.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import ShardedGraph
from repro.core.semiring import for_semiring
from repro.kernels import ref as ref_mod
from repro.kernels.semiring_spmv import (EDGE_BLOCK, TILE, _identity,
                                         spmv_partials)


@dataclasses.dataclass
class PulledGraph:
    """Destination-sorted, tile-padded edge stream (host arrays)."""
    num_vertices: int  # padded to a TILE multiple
    num_real_vertices: int
    edge_src: np.ndarray  # [E_pad] int32 (-1 = padding)
    edge_dst_local: np.ndarray  # [E_pad] int32 in [0, TILE) (-1 = padding)
    block_tile: np.ndarray  # [n_blocks] int32 — destination tile per block
    weights: Optional[np.ndarray]  # [E_pad] f32

    @property
    def n_blocks(self) -> int:
        return len(self.block_tile)

    @property
    def n_tiles(self) -> int:
        return self.num_vertices // TILE


def build_pulled_graph(graph: ShardedGraph) -> PulledGraph:
    """ShardedGraph CSR -> destination-sorted tile-padded edge stream."""
    srcs, dsts, ws = [], [], []
    for p in range(graph.num_shards):
        cnt = int(graph.edge_counts[p])
        deg = graph.row_ptr[p, 1:] - graph.row_ptr[p, :-1]
        src_local = np.repeat(np.arange(graph.vs), deg)[:cnt]
        srcs.append(src_local + p * graph.vs)
        dsts.append(graph.col_idx[p, :cnt])
        if graph.weights is not None:
            ws.append(graph.weights[p, :cnt])
    src = np.concatenate(srcs).astype(np.int64)
    dst = np.concatenate(dsts).astype(np.int64)
    w = np.concatenate(ws).astype(np.float32) if ws else None

    n_pad = -(-graph.num_vertices // TILE) * TILE
    order = np.argsort(dst, kind="stable")
    src, dst = src[order], dst[order]
    if w is not None:
        w = w[order]
    tile = dst // TILE

    # pad each tile's edge run to an EDGE_BLOCK multiple
    out_src, out_dstl, out_w, block_tile = [], [], [], []
    for t in np.unique(tile):
        sel = tile == t
        s_t, d_t = src[sel], dst[sel] - t * TILE
        w_t = w[sel] if w is not None else None
        pad = (-len(s_t)) % EDGE_BLOCK
        out_src.append(np.concatenate([s_t, np.full(pad, -1, np.int64)]))
        out_dstl.append(np.concatenate([d_t, np.full(pad, -1, np.int64)]))
        if w is not None:
            out_w.append(np.concatenate([w_t, np.zeros(pad, np.float32)]))
        block_tile += [int(t)] * ((len(s_t) + pad) // EDGE_BLOCK)

    return PulledGraph(
        num_vertices=n_pad,
        num_real_vertices=graph.num_real_vertices,
        edge_src=np.concatenate(out_src).astype(np.int32),
        edge_dst_local=np.concatenate(out_dstl).astype(np.int32),
        block_tile=np.asarray(block_tile, np.int32),
        weights=np.concatenate(out_w).astype(np.float32) if w is not None
        else None,
    )


# ======================================================================
@partial(jax.jit, static_argnames=("semiring", "n_tiles", "use_kernel",
                                   "use_mxu"))
def _pull_step(values, edge_src, edge_dst_local, block_tile, weights, *,
               semiring: str, n_tiles: int, use_kernel: bool,
               use_mxu: bool):
    ident = _identity(semiring, values.dtype)  # plus_times/SUM: 0
    safe_src = jnp.clip(edge_src, 0, values.shape[0] - 1)
    vals = jnp.where(edge_src >= 0, values[safe_src], ident)
    if use_kernel:
        partials = spmv_partials(vals, edge_dst_local, weights,
                                 semiring=semiring, use_mxu=use_mxu)
    else:
        partials = ref_mod.spmv_partials_ref(vals, edge_dst_local, weights,
                                             semiring=semiring)
    # combine per-block partials into per-tile outputs
    agg = for_semiring(semiring)
    tiles = agg.segment_reduce(partials, block_tile, num_segments=n_tiles)
    if agg.idempotent:  # clamp empty/out-of-domain lanes at the identity
        tiles = agg.tie(tiles, ident)
    return tiles.reshape(n_tiles * TILE)


def frontier_pull_step(values: jnp.ndarray, pg: PulledGraph, *,
                       semiring: str, use_kernel: bool = True,
                       use_mxu: bool = False) -> jnp.ndarray:
    """One full propagation: out[v] = reduce over in-edges combine(src, w).

    For idempotent semirings the result is further tied against the
    current values (the self-stabilizing update); the non-idempotent
    plus_times/SUM result is absolute and supersedes."""
    vpad = pg.num_vertices - values.shape[0]
    v = jnp.pad(values, (0, vpad), constant_values=_identity(semiring,
                                                             values.dtype)
                ) if vpad else values
    out = _pull_step(v, jnp.asarray(pg.edge_src),
                     jnp.asarray(pg.edge_dst_local),
                     jnp.asarray(pg.block_tile),
                     jnp.asarray(pg.weights) if pg.weights is not None else None,
                     semiring=semiring, n_tiles=pg.n_tiles,
                     use_kernel=use_kernel, use_mxu=use_mxu)
    agg = for_semiring(semiring)
    if agg.idempotent:
        out = agg.tie(out, v)
    return out[: values.shape[0]] if vpad else out


# ======================================================================
def pagerank(graph: ShardedGraph, *, damping: float = 0.85,
             iters: int = 30, use_kernel: bool = True,
             dangling: str = "redistribute"):
    """PageRank in the paper's §3.3-safe formulation.

    A push-mode asynchronous PageRank with (+) messages is NOT idempotent —
    duplicated/replayed messages double-count (the paper's caveat).  The
    self-stabilizing fix it describes (store the latest contribution of each
    neighbor) is equivalent to *pull-mode recomputation from absolute
    neighbor states*, which is what the plus_times semiring pull step
    computes: rank_v = (1-d) + d * sum_in rank_u / deg_u.  Messages are
    absolute and supersede — replay-safe by construction.

    ``dangling`` picks the zero-out-degree convention:

      * ``"redistribute"`` — a dangling vertex's damped mass teleports
        uniformly (the classic normalization; ranks sum to 1);
      * ``"absorb"`` — the damped share of a dangling vertex simply
        evaporates (a zero row in the transition matrix).  This is the
        fixpoint the engine's push-mode ``pagerank`` VertexProgram
        converges to — a push at a degree-0 vertex has no edge to send
        on — so it is the oracle the exactly-once tests validate against
        (engine ranks are unnormalized: engine/n_real == this).
    """
    assert dangling in ("redistribute", "absorb"), dangling
    pg = build_pulled_graph(graph)
    n, n_real = pg.num_vertices, graph.num_real_vertices
    deg_raw = graph.degrees().reshape(-1).astype(np.float32)
    deg_raw = np.pad(deg_raw, (0, n - len(deg_raw)))[:n]
    dangling_mask = jnp.asarray((deg_raw == 0)[:n])
    deg_j = jnp.asarray(np.maximum(deg_raw, 1.0))
    rank = jnp.full((n,), 1.0 / n_real, jnp.float32
                    ).at[n_real:].set(0.0)
    for _ in range(iters):
        contrib = rank / deg_j
        pulled = frontier_pull_step(contrib, pg, semiring="plus_times",
                                    use_kernel=use_kernel)
        if dangling == "redistribute":
            dm = jnp.sum(jnp.where(dangling_mask, rank, 0.0))
            pulled = pulled + dm / n_real
        rank = (1 - damping) / n_real + damping * pulled
        rank = rank.at[n_real:].set(0.0)
    return rank[:n_real]


# ======================================================================
def bsp_connected_components(graph: ShardedGraph, *, use_kernel: bool = True,
                             max_rounds: int = 10000):
    """Synchronous full-frontier CC (the Pregel-equivalent BSP baseline).

    Runs min-label propagation rounds until fixpoint; each round is one
    kernel-backed pull step over ALL edges — exactly the superstep model the
    paper compares against (O(diameter) rounds, all edges touched per round).
    """
    pg = build_pulled_graph(graph)
    n = graph.num_vertices
    values = jnp.arange(n, dtype=jnp.int32)
    rounds = 0
    messages = 0
    for _ in range(max_rounds):
        new = frontier_pull_step(values, pg, semiring="min",
                                 use_kernel=use_kernel)
        rounds += 1
        messages += int(pg.edge_src.shape[0])  # BSP sends on every edge
        if bool(jnp.all(new == values)):
            break
        values = new
    return values[: graph.num_real_vertices], {"rounds": rounds,
                                               "messages": messages}
