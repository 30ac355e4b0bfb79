"""The ASYMP engine: priority-driven asynchronous-style propagation ticks.

One tick per shard (Fig 1 / Fig 2 mapped to SPMD):
  select     — per-shard priority queue: bucketized priorities (linear/log,
               §3.5), enforcement fraction rho (§5.6), top-M cap
  fetch      — streamed adjacency window per selected vertex (edge cursor:
               high-degree vertices stream their list over multiple ticks —
               the tick-level analogue of the paper's on-demand edge fetch)
  create     — program.combine over the fetched edges
  route      — bucket messages by destination shard into fixed-capacity
               buffers (bounded queues); overflow => sender retries next tick
               (backpressure); one all_to_all delivers everything
  receive    — idempotent scatter-⊕ via the program's Aggregator (min for
               CC/SSSP/BFS, max for widest-path/labelprop, or for
               reachability); improved vertices join the frontier.  The
               plain tick reads only the occupied prefix of the receive
               buffers, in one of a few static widths picked per tick

One tick builder, two placements of the same per-shard code:
  one device — arrays [P, ...], vmap + transpose as the exchange
  a mesh     — shard_map over a 1-D `workers` mesh (:func:`shard_local_tick`):
               each device vmaps its block of P / devices shards, and one
               lax.all_to_all carries the exchange across devices
               (EngineSession(mesh=...); the dry-run lowers it on 256/512
               chips)
The crowded and async schedules keep shard_map builders of their own.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import GraphConfig
from repro.core import programs as prog_mod
from repro.core import trace
from repro.core.graph import ShardedGraph, build_sharded_graph
from repro.dist import exchange as ex_mod

N_BUCKETS = 32


class EngineState(NamedTuple):
    values: jnp.ndarray  # [P, vs]
    active: jnp.ndarray  # [P, vs] bool
    cursor: jnp.ndarray  # [P, vs] int32 — adjacency streaming position
    tick: jnp.ndarray  # scalar int32
    # push-mode sidecar planes [P, aux_channels, vs] (None for idempotent
    # programs): aux[:, 0] = residual (receive-side accumulation),
    # aux[:, 1] = latched mass mid-push.  Checkpoints, elastic resize and
    # fault restore must carry it with values/active/cursor — it IS
    # program state.
    aux: Optional[jnp.ndarray] = None


class ShardGraph(NamedTuple):
    row_ptr: jnp.ndarray  # [P, vs+1] int32
    col_idx: jnp.ndarray  # [P, es] int32
    weights: Optional[jnp.ndarray]  # [P, es] f32 | None


class TickStats(NamedTuple):
    active: jnp.ndarray  # vertices active after tick
    sent: jnp.ndarray  # messages sent
    accepted: jnp.ndarray  # messages that improved a value
    fetched: jnp.ndarray  # edges fetched (seek rate, Fig 10)
    # receive-buffer slots the receive processed (the plain tick only)
    recv_slots: Optional[jnp.ndarray] = None


@dataclasses.dataclass(frozen=True)
class EngineParams:
    """Static knobs (hashable: closed over by jit)."""
    num_shards: int
    vs: int
    max_vertices_per_tick: int  # M
    degree_window: int  # D_cap (edges streamed per vertex per tick)
    route_capacity: int  # per-destination-shard message slots
    enforce_fraction: float  # rho (paper: 100/10/5/2.5%)
    priority: str  # disabled | linear | log
    priority_scale: float  # normalization for bucketing
    wire_compression: str = "none"  # effective wire mode (pre-gated)
    wire_value_bound: int = 0  # int-payload bound gating lossless narrowing
    # straggler-aware scheduling (crowded-cluster emulation): bucket
    # penalty applied to frontier work activated over a slow link, so
    # settled work drains first and soon-to-be-improved values are not
    # propagated redundantly (0 = off; only the crowded tick uses it)
    straggler_demote: int = 0
    # the mesh the tick runs on (:func:`shard_local_tick`): the name of its
    # one axis and its number of devices, each running num_shards /
    # mesh_devices shards.  None: every shard in one device's arrays
    mesh_axis: Optional[str] = None
    mesh_devices: int = 1


def wire_codec(prog, ep: EngineParams) -> ex_mod.WireCodec:
    """The exchange substrate's codec for this engine configuration.

    ``ep.wire_compression`` is already the *effective* mode (gated against
    ``wire_value_bound`` and the aggregator's idempotence when the params
    were derived), so the codec re-gate is a no-op."""
    return ex_mod.make_wire_codec(
        num_shards=ep.num_shards, capacity=ep.route_capacity, vs=ep.vs,
        requested=ep.wire_compression, value_kind=prog.dtype,
        identity=prog.identity, max_int_value=ep.wire_value_bound,
        quantize_direction=prog.aggregator.quantize_direction,
        idempotent=prog.aggregator.idempotent, axis_name=ep.mesh_axis)


def derive_params(cfg: GraphConfig, *, num_shards: int, vs: int, es: int,
                  num_vertices: int, prog) -> EngineParams:
    """THE EngineParams derivation — shared by the production path
    (:func:`default_params`, from a built graph) and the dry-run
    (:func:`lower_tick_for_mesh`, from config-level estimates), so the
    dry-run compiles exactly what production runs (the two used to
    re-derive ``route_capacity``/``max_vertices_per_tick`` by hand and
    had drifted into different spellings of the same formula)."""
    budget = cfg.edge_budget or max(es // 4, 256)
    d_cap = max(min(cfg.avg_degree, 64), 4)
    m = int(min(max(budget // d_cap, 16), vs))
    # §Perf iter G1: 1.25x slack (was 2x) — wire and buffer traffic scale
    # with cap; overflow just retries next tick (bounded-queue semantics)
    cap = cfg.route_capacity or max(budget // num_shards
                                    + budget // (4 * num_shards), 64)
    bound = prog.wire_bound(num_vertices)
    wire = ex_mod.effective_compression(cfg.wire_compression, prog.dtype,
                                        bound, prog.aggregator.idempotent)
    return EngineParams(
        num_shards=num_shards, vs=vs, max_vertices_per_tick=m,
        degree_window=d_cap, route_capacity=int(cap),
        enforce_fraction=cfg.enforce_fraction, priority=cfg.priority,
        priority_scale=prog.priority_scale or float(num_vertices),
        wire_compression=wire, wire_value_bound=bound,
        straggler_demote=getattr(cfg, "straggler_demote", 0))


def default_params(cfg: GraphConfig, graph: ShardedGraph,
                   prog=None) -> EngineParams:
    prog = prog or prog_mod.get_program(cfg)
    return derive_params(cfg, num_shards=graph.num_shards, vs=graph.vs,
                         es=graph.es, num_vertices=graph.num_vertices,
                         prog=prog)


# ======================================================================
# Priority bucketing (§3.5: linear vs log; disabled = arbitrary order)
# ======================================================================
def priority_buckets(pv: jnp.ndarray, strategy: str, scale: float) -> jnp.ndarray:
    if strategy == "disabled":
        return jnp.zeros(pv.shape, jnp.int32)
    x = jnp.clip(pv, 0.0, scale) / scale  # [0, 1]
    if strategy == "linear":
        b = jnp.floor(x * N_BUCKETS)
    else:  # log: reserve precision at the low end (paper Fig 9b)
        b = jnp.floor(jnp.log2(1.0 + x * (2.0 ** N_BUCKETS - 1)))
    return jnp.clip(b, 0, N_BUCKETS - 1).astype(jnp.int32)


# ======================================================================
# Per-shard tick phases (operate on ONE shard's arrays)
# ======================================================================
def _route_rank(dst_shard, Pn: int):
    """``[M, D]`` int32: for each slot with ``dst_shard < Pn``, the number
    of earlier slots in flat ``(m, d)`` order bound for the same shard, i.e.
    its slot in that shard's send buffer.  Slots with ``dst_shard == Pn``
    (no message) get an unspecified rank.

    Dense prefix sums over a one-hot of the destination shard, with no
    sort, gather or scatter: on a TPU each of those costs nanoseconds per
    element of the whole slot plane, where the prefix sums stream it."""
    with jax.named_scope("route_rank"):
        # shard axis first: a trailing [.., Pn] axis would pad the lanes
        oh = (dst_shard[None] == jnp.arange(Pn, dtype=dst_shard.dtype)[
            :, None, None]).astype(jnp.int32)  # [Pn, M, D]
        within = jnp.cumsum(oh, axis=2) - oh  # earlier slots of the row
        per_row = jnp.sum(oh, axis=2)  # [Pn, M]
        row_off = jnp.cumsum(per_row, axis=1) - per_row  # earlier rows
        return jnp.sum(oh * (within + row_off[:, :, None]), axis=0)


def _phase1_create(prog, ep: EngineParams, values, active, cursor,
                   row_ptr, col_idx, weights, shard_id,
                   throttle=None, demote=None, aux=None,
                   stream_window=None):
    """Select + fetch + create + route. Returns ``(active, cursor,
    send_vals, send_ids, sent, fetched, values, aux)`` — values/aux ride
    at the END so callers of the historical 6-tuple still unpack; they
    only change under a push-mode program.

    Crowded-cluster extras (both optional, both traced):
      * ``throttle`` — scalar work-budget divisor for this shard (a
        crowded machine gets through ``1/throttle`` of the per-tick edge
        budget);
      * ``demote`` — [vs] bool mask of frontier work activated over a
        slow link last tick; such vertices take a bucket penalty
        (``ep.straggler_demote``) so settled work drains first.  The
        threshold machinery still selects them when nothing healthier
        remains, so no vertex starves and the fixpoint cannot move
        (selection order is covered by §3.3 reordering invariance).
      * ``stream_window`` — scalar cap on edges fetched per selected
        vertex this call (``<= ep.degree_window``, the static array
        width).  The async schedule compiles a widened window and passes
        ``rate * D`` per shard: one firing of a rate-k shard is k steps'
        worth of edge streaming, delivered at once — without this a
        high-degree vertex on a crowded shard drains k times slower
        than under the budget-divisor (sync) emulation.

    Push mode (``aux is not None``; non-idempotent aggregators): instead
    of propagating its absolute value, a selected vertex *moves mass*.
    On first selection of a push (push latch == 0) it latches ``m =
    residual``, zeroes the residual and banks ``values += m`` — exactly
    once per push, however many ticks the edge stream takes.  Messages
    carry ``combine(m, w, deg)`` and, critically, only the contiguous
    edge prefix up to the first routing drop ships: a kept edge AFTER
    the first drop would be re-fetched when the cursor resumes there —
    harmless duplication under an idempotent reduce, double-counted mass
    under SUM.  When the stream completes (``done``) the latch clears
    and the vertex stays active iff mass re-accumulated meanwhile.
    """
    vs, M, D = ep.vs, ep.max_vertices_per_tick, ep.degree_window
    Pn, cap = ep.num_shards, ep.route_capacity
    push_mode = aux is not None
    if push_mode:
        residual, pushv = aux[0], aux[1]

    with jax.named_scope("tick.select"):
        # ---- select (priority queue with enforcement fraction) ----
        # Sort-free selection (§Perf iter G1): bucket histogram + cumsum
        # threshold + rank-by-cumsum replaces a [vs] argsort — the paper's
        # bucketed queues never needed total order anyway.
        n_active = jnp.sum(active)
        m_eff = (M if throttle is None
                 else jnp.maximum(M // jnp.maximum(throttle, 1), 1))
        target = jnp.clip(jnp.ceil(ep.enforce_fraction * n_active), 1, m_eff
                          ).astype(jnp.int32)
        # the aggregator orients the program's raw potential metric into an
        # ascending key (min: low value first; max/or: high value first;
        # sum: most pending mass — residual + latched push — first)
        pmetric = (prog.priority_value(residual + pushv) if push_mode
                   else prog.priority_value(values))
        pkey = prog.aggregator.priority_key(pmetric, ep.priority_scale)
        buckets = priority_buckets(pkey, ep.priority, ep.priority_scale)
        if demote is not None and ep.straggler_demote:
            buckets = jnp.where(
                demote, jnp.minimum(buckets + ep.straggler_demote,
                                    N_BUCKETS - 1), buckets)
        hist = jnp.zeros((N_BUCKETS,), jnp.int32).at[buckets].add(
            active.astype(jnp.int32))
        cum = jnp.cumsum(hist)
        thr = jnp.searchsorted(cum, target)  # first bucket covering the target
        # strict two-tier rank: every vertex in buckets < thr outranks the
        # threshold bucket (within a bucket, index order — the paper's queues
        # are unordered within a bucket too)
        low = active & (buckets < thr)
        at_thr = active & (buckets == thr)
        n_low = jnp.cumsum(low.astype(jnp.int32))
        n_thr = jnp.cumsum(at_thr.astype(jnp.int32))
        total_low = n_low[-1]
        rank_v = jnp.where(low, n_low - 1, total_low + n_thr - 1)
        pre = low | at_thr
        sel_mask = pre & (rank_v < jnp.minimum(target, M))
        # invalid slots get the out-of-bounds sentinel `vs` so downstream
        # scatters drop them (slot-0 fill would alias a real vertex)
        sel = jnp.full((M,), vs, jnp.int32).at[
            jnp.where(sel_mask, rank_v, M)].set(
            jnp.arange(vs, dtype=jnp.int32), mode="drop")
        sel_valid = jnp.zeros((M,), bool).at[
            jnp.where(sel_mask, rank_v, M)].set(True, mode="drop")
        # overflow slots go to the best buckets first: the two-tier rank
        # above is vertex-index order WITHIN each tier, and the routing rank
        # below follows flat slot order — so under starved
        # route capacity the kept prefix used to be the low-vertex-index
        # work, not the high-priority work (backpressured pagerank lost its
        # big-mass-first schedule).  A stable argsort over the M slots by
        # bucket restores the priority order; with priority disabled every
        # bucket is 0 and the permutation is the identity (FIFO semantics
        # untouched).
        slot_bucket = jnp.where(sel_valid, buckets[jnp.minimum(sel, vs - 1)],
                                N_BUCKETS)
        reorder = jnp.argsort(slot_bucket)  # stable; invalid slots sort last
        sel = sel[reorder]
        sel_valid = sel_valid[reorder]
        sel_safe = jnp.minimum(sel, vs - 1)  # for gathers

    with jax.named_scope("tick.fetch"):
        # ---- fetch adjacency window (streamed via cursor) ----
        deg = (row_ptr[sel_safe + 1] - row_ptr[sel_safe]).astype(jnp.int32)
        cur = cursor[sel_safe]
        base = row_ptr[sel_safe].astype(jnp.int32) + cur
        offs = jnp.arange(D, dtype=jnp.int32)
        eidx = base[:, None] + offs[None, :]
        edge_valid = sel_valid[:, None] & ((cur[:, None] + offs[None, :])
                                           < deg[:, None])
        if stream_window is not None:
            edge_valid = edge_valid & (offs[None, :] < stream_window)
        eidx_safe = jnp.clip(eidx, 0, col_idx.shape[0] - 1)
        dst = jnp.where(edge_valid, col_idx[eidx_safe], -1)  # global ids
        w = weights[eidx_safe] if weights is not None else None

        # ---- create messages ----
        if push_mode:
            # latch: a selected vertex not already mid-push moves its
            # residual into the outgoing latch and banks it into the output
            # value — exactly once per push.  Mid-push means a nonzero latch
            # OR a nonzero cursor: a zero-mass push (selected while the
            # residual is exactly 0, e.g. restart-personalized pagerank
            # where init activates every vertex) streams its adjacency with
            # latch == 0, and re-latching mid-stream would resume at the
            # cursor and ship the new mass over only the tail of the edge
            # list, silently losing the head's share.
            latch = sel_valid & (pushv[sel_safe] == 0) & (cur == 0)
            mass = jnp.where(latch, residual[sel_safe], pushv[sel_safe])  # [M]
            msg = jnp.broadcast_to(
                prog.combine(mass[:, None], w, deg[:, None]), (M, D))
        else:
            msg = jnp.broadcast_to(prog.combine(values[sel_safe][:, None], w),
                                   (M, D))

    with jax.named_scope("tick.route"):
        # ---- route: bucket by destination shard, bounded capacity ----
        dst_shard = jnp.where(dst >= 0, dst // vs, Pn)  # Pn = invalid bucket
        rank = _route_rank(dst_shard, Pn)

        keep = edge_valid & (rank < cap)
        # first routing drop per vertex — the cursor stops there and retries
        dropped = edge_valid & ~keep
        any_drop = dropped.any(axis=1)
        first_drop = jnp.where(any_drop, jnp.argmax(dropped, axis=1), D)
        if stream_window is not None:
            # the cursor must stop at the window even with no routing drop:
            # edges past it were never fetched this call
            first_drop = jnp.minimum(first_drop, stream_window)
        if push_mode:
            # exactly-once: ship ONLY the contiguous prefix the cursor will
            # advance past.  A kept edge after the first drop is re-fetched
            # when the cursor resumes — idempotent reduces absorb that
            # duplicate, a SUM would count the mass twice.
            keep = keep & (offs[None, :] < first_drop[:, None])
        r_safe = jnp.where(keep, rank, cap)  # cap = out of bounds -> dropped
        ds_safe = jnp.where(keep, dst_shard, 0)
        send_vals = jnp.full((Pn, cap), prog.identity, prog.jdtype).at[
            ds_safe.reshape(-1), r_safe.reshape(-1)].set(
            msg.reshape(-1).astype(prog.jdtype), mode="drop")
        send_ids = jnp.full((Pn, cap), -1, jnp.int32).at[
            ds_safe.reshape(-1), r_safe.reshape(-1)].set(
            jnp.where(keep, dst % vs, -1).reshape(-1).astype(jnp.int32),
            mode="drop")

        # ---- cursor advance: up to the first dropped edge (retry the rest)
        advance = jnp.minimum(first_drop.astype(jnp.int32), deg - cur)
        new_cur = cur + jnp.where(sel_valid, advance, 0)
        done = sel_valid & (new_cur >= deg)
        upd_idx = jnp.where(sel_valid, sel, vs)  # OOB -> dropped
        cursor = cursor.at[upd_idx].set(jnp.where(done, 0, new_cur),
                                        mode="drop")
        if push_mode:
            res_after = jnp.where(latch, 0.0, residual[sel_safe]).astype(
                prog.jdtype)
            values = values.at[upd_idx].add(
                jnp.where(latch, mass, 0.0).astype(prog.jdtype), mode="drop")
            residual = residual.at[upd_idx].set(res_after, mode="drop")
            pushv = pushv.at[upd_idx].set(
                jnp.where(done, 0.0, mass).astype(prog.jdtype), mode="drop")
            # a finished push retires; it re-arms iff mass accumulated while
            # the stream was in flight (receives do NOT touch the cursor in
            # push mode, so only this site may conclude a push).  abs: delta
            # corrections (serve/graph) inject signed mass, and a negative
            # residual must drain just like a positive one — identical for
            # ordinary runs, whose residuals never go negative.
            active = active.at[upd_idx].set(
                jnp.where(done, jnp.abs(res_after) > prog.push_eps, True),
                mode="drop")
            aux = jnp.stack([residual, pushv])
        else:
            active = active.at[upd_idx].set(~done, mode="drop")

    sent = jnp.sum(keep)
    fetched = jnp.sum(edge_valid)
    return active, cursor, send_vals, send_ids, sent, fetched, values, aux


@jax.named_scope("tick.receive")
def _phase2_receive(prog, ep: EngineParams, values, active, cursor,
                    recv_vals, recv_ids):
    """Deliver: idempotent scatter-⊕ (the program's aggregator); improved
    vertices activate."""
    agg = prog.aggregator
    vs = ep.vs
    ids = recv_ids.reshape(-1)
    vals = recv_vals.reshape(-1).astype(prog.jdtype)
    valid = ids >= 0
    idx = jnp.where(valid, ids, vs)  # vs -> dropped (out of bounds)
    old = values
    values = agg.scatter(values, idx, vals)
    accepted = jnp.sum(valid & agg.improves(vals,
                                            old[jnp.clip(idx, 0, vs - 1)]))
    changed = agg.improves(values, old)
    active = active | changed
    cursor = jnp.where(changed, 0, cursor)
    return values, active, cursor, accepted


def receive_widths(cap: int) -> tuple[int, ...]:
    """The static widths of the idempotent receive, narrowest first:
    ``cap``, and about ``cap / 8`` and ``cap / 64`` rounded up to whole
    128-lane rows where that is narrower than ``cap``."""
    def lanes(n):
        return -(-n // 128) * 128
    return tuple(sorted({cap} | {w for w in (lanes(-(-cap // 8)),
                                             lanes(-(-cap // 64)))
                                 if w < cap}))


def _receive_prefix(prog, ep: EngineParams, values, active, cursor, rv, ri):
    """The idempotent receive over a block of shards (``rv``, ``ri``
    ``[Pb, Pn, cap]``), run on the occupied prefix of every row only: the
    narrowest of :func:`receive_widths` that holds each valid id of the
    block, picked on the device.  Every message lies in the prefix, so the
    result is the full-width receive's.  Returns ``(values, active,
    cursor, accepted [Pb], slots)``, ``slots`` the receive slots
    processed.

    The branch is taken outside the ``vmap`` over the block: under it a
    ``switch`` would run every branch.  The ``switch`` itself stays out of
    the ``tick.receive`` scope that its branches and the extent carry: a
    device trace shows the conditional as one op that spans the ops of
    the branch it ran, which the scope would count twice."""
    p2v = jax.vmap(lambda v, a, c, rvals, rids: _phase2_receive(
        prog, ep, v, a, c, rvals, rids))
    Pb, Pn, cap = ri.shape
    widths = receive_widths(cap)
    if len(widths) == 1:
        return (*p2v(values, active, cursor, rv, ri),
                jnp.int32(Pb * Pn * cap))
    with jax.named_scope("tick.receive"):
        # one past the last position of a valid id in any row
        extent = jnp.max(jnp.where(
            ri >= 0, jnp.arange(1, cap + 1, dtype=jnp.int32), 0))
        which = jnp.sum(extent > jnp.asarray(widths[:-1], jnp.int32))
        slots = jnp.asarray(widths, jnp.int32)[which] * (Pb * Pn)

    def prefix(C, v, a, c, rvals, rids):
        with jax.named_scope("tick.receive"):
            return p2v(v, a, c, rvals[..., :C], rids[..., :C])

    out = jax.lax.switch(which, [partial(prefix, C) for C in widths],
                         values, active, cursor, rv, ri)
    return (*out, slots)


@jax.named_scope("tick.receive")
def _phase2_receive_push(prog, ep: EngineParams, residual, active,
                         recv_vals, recv_ids):
    """Push-mode delivery: scatter-ADD into the residual plane (the SUM
    aggregator); vertices whose pending mass crosses the push threshold
    join the frontier.

    Two deliberate differences from the idempotent receive: the banked
    output (``values``) is untouched — mass only enters it through the
    phase-1 latch — and the cursor is NOT reset, because restarting an
    in-progress edge stream would re-ship its already-delivered prefix
    (exactly-once would become at-least-once)."""
    agg = prog.aggregator
    vs = ep.vs
    ids = recv_ids.reshape(-1)
    vals = recv_vals.reshape(-1).astype(prog.jdtype)
    valid = ids >= 0
    idx = jnp.where(valid, ids, vs)  # vs -> dropped (out of bounds)
    residual = agg.scatter(residual, idx,
                           jnp.where(valid, vals, prog.identity))
    accepted = jnp.sum(valid)  # every delivered message lands mass
    # abs: signed delta-correction mass (serve/graph) activates on
    # magnitude; no-op for ordinary runs (residuals stay non-negative)
    active = active | (jnp.abs(residual) > prog.push_eps)
    return residual, active, accepted


# ======================================================================
# The plain tick: on one device (vmap), or on a mesh (shard_map)
# ======================================================================
def make_local_tick(prog, ep: EngineParams, weighted: bool):
    """The plain tick over a block of shards ``[P, ...]``.  With
    ``ep.mesh_axis`` it is one device's body under ``shard_map``
    (:func:`shard_local_tick`): the block is the device's ``num_shards /
    mesh_devices`` shards, the exchange crosses devices, and the counts
    are summed over the mesh."""
    codec = wire_codec(prog, ep)
    push_mode = not prog.aggregator.idempotent

    def tick(state: EngineState, g: ShardGraph):
        if ep.mesh_axis is None:
            shard_ids = jnp.arange(ep.num_shards)
        else:
            Pd = ep.num_shards // ep.mesh_devices
            shard_ids = (jax.lax.axis_index(ep.mesh_axis) * Pd
                         + jnp.arange(Pd))
        w = g.weights if weighted else None
        aux = state.aux if push_mode else None

        p1v = jax.vmap(
            lambda v, a, c, r, ci, wt, s, ax: _phase1_create(
                prog, ep, v, a, c, r, ci, wt, s, aux=ax),
            in_axes=(0, 0, 0, 0, 0, 0 if weighted else None, 0,
                     0 if push_mode else None))
        active, cursor, sv, si, sent, fetched, values, aux = p1v(
            state.values, state.active, state.cursor, g.row_ptr,
            g.col_idx, w, shard_ids, aux)

        # exchange: send[p][q] -> recv[q][p] via the dist substrate
        rv, ri = ex_mod.exchange_local(codec, sv, si)

        if push_mode:
            p2v = jax.vmap(lambda res, a, rvals, rids: _phase2_receive_push(
                prog, ep, res, a, rvals, rids))
            residual, active, accepted = p2v(aux[:, 0], active, rv, ri)
            aux = aux.at[:, 0].set(residual)
            recv_slots = jnp.int32(ri.size)
        else:
            values, active, cursor, accepted, recv_slots = _receive_prefix(
                prog, ep, values, active, cursor, rv, ri)
            aux = state.aux  # None (or an untouched caller-supplied plane)
        stats = TickStats(jnp.sum(active), jnp.sum(sent), jnp.sum(accepted),
                          jnp.sum(fetched), recv_slots)
        if ep.mesh_axis is not None:
            stats = jax.lax.psum(stats, ep.mesh_axis)
        return (EngineState(values, active, cursor, state.tick + 1, aux),
                stats, (sv, si))

    return jax.jit(tick)


def on_mesh(ep: EngineParams, mesh: Mesh) -> EngineParams:
    """``ep`` for the tick's body on a 1-D ``mesh``; ``ValueError`` where
    the mesh cannot hold the shards in equal blocks."""
    if len(mesh.axis_names) != 1:
        raise ValueError(f"the engine runs on a 1-D mesh; this one has axes "
                         f"{mesh.axis_names}")
    n = mesh.devices.size
    if ep.num_shards % n:
        raise ValueError(f"{ep.num_shards} shards do not split evenly over "
                         f"the mesh's {n} devices")
    return dataclasses.replace(ep, mesh_axis=mesh.axis_names[0],
                               mesh_devices=n)


def shard_local_tick(prog, ep: EngineParams, mesh: Mesh, weighted: bool):
    """The plain tick over ``mesh``: :func:`make_local_tick` (looked up
    when the tick is built) as each device's body under ``shard_map``.
    Takes and returns what the one-device tick does, the shard axis of
    every array split over the mesh and the tick counter replicated; its
    ``TickStats`` are the mesh's totals.  Not jitted, so that a caller may
    donate the state."""
    ep = on_mesh(ep, mesh)
    body = make_local_tick(prog, ep, weighted)
    rows = P(ep.mesh_axis)

    def specs(tree):
        return jax.tree.map(lambda x: P() if jnp.ndim(x) == 0 else rows,
                            tree)

    def tick(state: EngineState, g: ShardGraph):
        return shard_map(
            body, mesh=mesh, in_specs=(specs(state), specs(g)),
            out_specs=(specs(state), TickStats(*[P()] * len(
                TickStats._fields)), (rows, rows)),
            check_vma=False)(state, g)

    return tick


# ======================================================================
# Crowded-cluster emulation (paper §5.4): deferred delivery + throttled
# budgets + straggler-aware scheduling
# ======================================================================
class CrowdedState(NamedTuple):
    core: EngineState
    ring: ex_mod.DelayRing  # in-flight messages (the emulated slow wire)
    demote: jnp.ndarray  # [P, vs] bool — frontier work to deprioritize


class CrowdedStats(NamedTuple):
    base: TickStats
    pending: jnp.ndarray  # messages still in flight in the delay ring
    shard_fetched: jnp.ndarray  # [P] edges fetched per shard this tick
    shard_recv: jnp.ndarray  # [P] messages processed per shard this tick


def init_crowded_state(prog, ep: EngineParams, graph: ShardedGraph,
                       max_delay: int) -> CrowdedState:
    return CrowdedState(
        init_state(prog, graph),
        ex_mod.init_delay_ring(max_delay, ep.num_shards, ep.num_shards,
                               ep.route_capacity, prog.identity,
                               prog.jdtype),
        jnp.zeros((ep.num_shards, ep.vs), bool))


def _demote_row(agg, ep: EngineParams, new_values, old_values, recv_ids,
                slow_row):
    """One shard's [vs] demotion mask: vertices whose value improved this
    tick AND that were targeted by at least one message arriving over a
    slow (delay > 0) link (``slow_row`` flags the slow receive rows).
    Recomputed every tick (a one-tick demotion, not accumulated), so
    repeated slow-link arrivals keep deferring the work while fresh local
    work cannot be starved."""
    changed = agg.improves(new_values, old_values)  # [vs]
    idx = jnp.where((recv_ids >= 0) & slow_row[:, None], recv_ids, ep.vs)
    slow_targets = jnp.zeros((ep.vs + 1,), bool).at[
        idx.reshape(-1)].set(True, mode="drop")[: ep.vs]
    return changed & slow_targets


def _slow_recv_rows(ep: EngineParams, num_rows: int, delays):
    """[Pn, num_rows] — for each receiver q, which delivered rows (row
    ``l * P + p`` is sender p's ring slot l) crossed a slow link."""
    sender = jnp.arange(num_rows, dtype=jnp.int32) % ep.num_shards
    return (delays[sender, :] > 0).T


def make_crowded_tick(prog, ep: EngineParams, weighted: bool):
    """Local-transport tick under emulated crowding.

    ``tick(cstate, g, delays, throttle)`` — ``delays [P, Pn]`` and
    ``throttle [P]`` are *traced* inputs (from a ``dist.latency`` model,
    possibly overridden per tick by fault-injected slowdowns), so the
    cluster condition can change mid-run without recompilation.  Send
    buffers are parked in the exchange substrate's delay ring and
    delivered when due; convergence therefore requires BOTH an empty
    frontier AND an empty ring (``stats.pending == 0``)."""
    codec = wire_codec(prog, ep)
    agg = prog.aggregator
    push_mode = not agg.idempotent

    def tick(cstate: CrowdedState, g: ShardGraph, delays, throttle):
        state = cstate.core
        shard_ids = jnp.arange(ep.num_shards)
        w = g.weights if weighted else None
        aux = state.aux if push_mode else None

        p1v = jax.vmap(
            lambda v, a, c, r, ci, wt, s, t_, d_, ax: _phase1_create(
                prog, ep, v, a, c, r, ci, wt, s, throttle=t_, demote=d_,
                aux=ax),
            in_axes=(0, 0, 0, 0, 0, 0 if weighted else None, 0, 0, 0,
                     0 if push_mode else None))
        active, cursor, sv, si, sent, fetched, values, aux = p1v(
            state.values, state.active, state.cursor, g.row_ptr,
            g.col_idx, w, shard_ids, throttle, cstate.demote, aux)

        # exchange through the deferred-delivery ring: messages from slow
        # links surface ticks later, healthy links deliver immediately
        rv, ri, ring, pending = ex_mod.exchange_local_delayed(
            codec, cstate.ring, sv, si, state.tick, delays, prog.identity)

        if push_mode:
            # receive accumulates into the residual plane; the demotion
            # comparison plane is the residual, too (that is where a slow
            # link's arrival lands)
            old_plane = aux[:, 0]
            p2v = jax.vmap(lambda res, a, rvals, rids: _phase2_receive_push(
                prog, ep, res, a, rvals, rids))
            residual, active, accepted = p2v(old_plane, active, rv, ri)
            aux = aux.at[:, 0].set(residual)
            new_plane = residual
        else:
            old_plane = state.values
            p2v = jax.vmap(lambda v, a, c, rvals, rids:
                           _phase2_receive(prog, ep, v, a, c, rvals, rids))
            values, active, cursor, accepted = p2v(values, active, cursor,
                                                   rv, ri)
            aux = state.aux
            new_plane = values
        if ep.straggler_demote:
            slow_rows = _slow_recv_rows(ep, ri.shape[1], delays)
            demote = jax.vmap(lambda nv, ov, rids, srow: _demote_row(
                agg, ep, nv, ov, rids, srow))(new_plane, old_plane, ri,
                                              slow_rows)
        else:
            demote = jnp.zeros_like(cstate.demote)

        stats = TickStats(jnp.sum(active), jnp.sum(sent),
                          jnp.sum(accepted), jnp.sum(fetched))
        cstats = CrowdedStats(stats, pending, fetched,
                              jnp.sum(ri >= 0, axis=(1, 2)))
        core = EngineState(values, active, cursor, state.tick + 1, aux)
        return CrowdedState(core, ring, demote), cstats, (sv, si)

    return jax.jit(tick)


# ======================================================================
# Crowded ticks over a `workers` mesh (shard_map, one shard per device)
# ======================================================================
def init_crowded_dist_state(prog, ep: EngineParams, graph: ShardedGraph,
                            max_delay: int) -> CrowdedState:
    """Like :func:`init_crowded_state` but with the per-shard (sender-side)
    delay ring layout the dist transport rings: [P, ring_len, Pn, cap]."""
    L1 = max_delay + 1
    Pn, cap = ep.num_shards, ep.route_capacity
    return CrowdedState(
        init_state(prog, graph),
        ex_mod.DelayRing(
            jnp.full((Pn, L1, Pn, cap), prog.identity, prog.jdtype),
            jnp.full((Pn, L1, Pn, cap), -1, jnp.int32),
            jnp.full((Pn, L1, Pn), -1, jnp.int32)),
        jnp.zeros((Pn, ep.vs), bool))


def make_crowded_dist_tick(prog, ep: EngineParams, mesh: Mesh,
                           weighted: bool):
    """Crowded tick over ``shard_map``: the production transport with the
    same deferred-delivery semantics (and bit-identical delivery order) as
    :func:`make_crowded_tick` — each shard parks its own sends in a local
    ring and ``exchange_dist_delayed`` ships due rows via ``all_to_all``.
    ``delays [P, Pn]`` and ``throttle [P]`` ride replicated so the host
    can inject slowdowns without recompiling."""
    axis = "workers"
    codec = wire_codec(prog, ep)
    agg = prog.aggregator
    push_mode = not agg.idempotent

    def local_fn(values, active, cursor, tick, aux, rv_ring, ri_ring,
                 rd_ring, demote, row_ptr, col_idx, weights, delays,
                 throttle):
        sid = jax.lax.axis_index(axis)
        values, active, cursor = values[0], active[0], cursor[0]
        aux_row = aux[0] if push_mode else None
        ring = ex_mod.DelayRing(rv_ring[0], ri_ring[0], rd_ring[0])
        w = weights[0] if weighted else None
        active, cursor, sv, si, sent, fetched, values, aux_row = \
            _phase1_create(prog, ep, values, active, cursor, row_ptr[0],
                           col_idx[0], w, sid, throttle=throttle[sid],
                           demote=demote[0], aux=aux_row)
        rv, ri, ring, pending = ex_mod.exchange_dist_delayed(
            codec, ring, sv, si, tick, delays[sid], axis, prog.identity)
        if push_mode:
            old_plane = aux_row[0]
            residual, active, accepted = _phase2_receive_push(
                prog, ep, old_plane, active, rv, ri)
            aux_row = aux_row.at[0].set(residual)
            new_plane, aux_out = residual, aux_row[None]
        else:
            old_plane = values
            values, active, cursor, accepted = _phase2_receive(
                prog, ep, values, active, cursor, rv, ri)
            new_plane, aux_out = values, aux
        if ep.straggler_demote:
            srow = delays[jnp.arange(ri.shape[0], dtype=jnp.int32)
                          % ep.num_shards, sid] > 0
            dem = _demote_row(agg, ep, new_plane, old_plane, ri, srow)
        else:
            dem = jnp.zeros_like(demote[0])
        stats = TickStats(jax.lax.psum(jnp.sum(active), axis),
                          jax.lax.psum(sent, axis),
                          jax.lax.psum(accepted, axis),
                          jax.lax.psum(fetched, axis))
        pending = jax.lax.psum(pending, axis)
        return (values[None], active[None], cursor[None], tick + 1,
                aux_out, ring.vals[None], ring.ids[None], ring.due[None],
                dem[None], stats, pending)

    def tick_fn(cstate: CrowdedState, g: ShardGraph, delays, throttle):
        state = cstate.core
        Pw = P(axis)
        aux_spec = Pw if push_mode else P()
        sm = shard_map(
            local_fn, mesh=mesh,
            in_specs=(Pw, Pw, Pw, P(), aux_spec, Pw, Pw, Pw, Pw, Pw, Pw,
                      Pw if weighted else P(), P(), P()),
            out_specs=(Pw, Pw, Pw, P(), aux_spec, Pw, Pw, Pw, Pw,
                       TickStats(P(), P(), P(), P()), P()),
            check_vma=False)
        weights = g.weights if weighted else jnp.zeros((), jnp.float32)
        aux_in = state.aux if push_mode else jnp.zeros((), jnp.float32)
        (values, active, cursor, tick, aux, rvr, rir, rdr, demote, stats,
         pending) = sm(state.values, state.active, state.cursor, state.tick,
                       aux_in, cstate.ring.vals, cstate.ring.ids,
                       cstate.ring.due, cstate.demote, g.row_ptr, g.col_idx,
                       weights, delays, throttle)
        core = EngineState(values, active, cursor, tick,
                           aux if push_mode else state.aux)
        return (CrowdedState(core, ex_mod.DelayRing(rvr, rir, rdr), demote),
                stats, pending)

    return tick_fn


# ======================================================================
# Asynchronous (barrier-free) execution: per-shard progress clocks
# ======================================================================
class AsyncState(NamedTuple):
    """State of one async run.  ``core.tick`` stays the *emulated
    wall-clock* step (it keys the delay-ring slots — latency cannot be
    emulated without a wall clock); the per-shard logical ``clock``
    replaces it everywhere progress semantics matter: recovery cuts,
    convergence accounting, the metrics log."""
    core: EngineState
    ring: ex_mod.DelayRing  # in-flight messages (arrivals queue here)
    demote: jnp.ndarray  # [P, vs] bool — carried until the shard fires
    clock: jnp.ndarray  # [P] int32 — firings incorporated into `core`


class AsyncStats(NamedTuple):
    base: TickStats
    pending: jnp.ndarray  # messages still in flight (all shards)
    shard_active: jnp.ndarray  # [P] frontier size per shard
    shard_pending: jnp.ndarray  # [P] in-flight messages bound for shard
    clock: jnp.ndarray  # [P] logical clocks after this step


def async_ring_delay(max_delay: int, max_stall: int) -> int:
    """Ring sizing for async mode, as a ``max_delay``-equivalent.

    The synchronous rule (``max_delay + 1`` slots) is a staleness bug
    under per-shard clocks: a message due at step ``t`` is only consumed
    when its receiver fires, up to ``max_stall - 1`` steps later, and
    the sender would overwrite its slot at ``t + ring_len``.  The async
    ring therefore needs ``max_delay + max_stall`` slots."""
    return max_delay + max(int(max_stall), 1) - 1


def init_async_state(prog, ep: EngineParams, graph: ShardedGraph,
                     ring_delay: int) -> AsyncState:
    """``ring_delay`` comes from :func:`async_ring_delay` (max link delay
    widened by the interleaving's stall bound)."""
    return AsyncState(
        init_state(prog, graph),
        ex_mod.init_delay_ring(ring_delay, ep.num_shards, ep.num_shards,
                               ep.route_capacity, prog.identity,
                               prog.jdtype),
        jnp.zeros((ep.num_shards, ep.vs), bool),
        jnp.zeros((ep.num_shards,), jnp.int32))


def make_async_tick(prog, ep: EngineParams, weighted: bool):
    """Barrier-free step over the local transport.

    ``tick(astate, g, delays, fire)`` — ``fire [P]`` bool is the step's
    seeded firing mask (``dist.latency.AsyncInterleaving``).  A firing
    shard drains its due ring arrivals, selects frontier work with its
    FULL edge budget (throttle is a progress rate here, not a budget
    divisor) and pushes new messages; a non-firing shard keeps its state
    verbatim, contributes empty send buffers, and its inbound due rows
    stay parked (``recv_gate``).  Convergence is per shard: every
    shard's frontier empty AND every shard's inbound ring drained
    (``shard_active + shard_pending == 0`` for all shards)."""
    codec = wire_codec(prog, ep)
    agg = prog.aggregator
    push_mode = not agg.idempotent

    def tick(astate: AsyncState, g: ShardGraph, delays, fire, window=None):
        state = astate.core
        shard_ids = jnp.arange(ep.num_shards)
        w = g.weights if weighted else None
        aux = state.aux if push_mode else None
        if window is None:  # full static window for every shard
            window = jnp.full((ep.num_shards,), ep.degree_window,
                              jnp.int32)

        p1v = jax.vmap(
            lambda v, a, c, r, ci, wt, s, d_, ax, w_: _phase1_create(
                prog, ep, v, a, c, r, ci, wt, s, demote=d_, aux=ax,
                stream_window=w_),
            in_axes=(0, 0, 0, 0, 0, 0 if weighted else None, 0, 0,
                     0 if push_mode else None, 0))
        active1, cursor1, sv, si, sent, fetched, values1, aux1 = p1v(
            state.values, state.active, state.cursor, g.row_ptr,
            g.col_idx, w, shard_ids, astate.demote, aux, window)

        # only firing shards advance: the rest keep their state verbatim
        # and send nothing this step
        fire_v = fire[:, None]
        values = jnp.where(fire_v, values1, state.values)
        active = jnp.where(fire_v, active1, state.active)
        cursor = jnp.where(fire_v, cursor1, state.cursor)
        if push_mode:
            aux = jnp.where(fire[:, None, None], aux1, state.aux)
        sv = jnp.where(fire[:, None, None], sv,
                       jnp.asarray(prog.identity, sv.dtype))
        si = jnp.where(fire[:, None, None], si, -1)
        sent = jnp.where(fire, sent, 0)
        fetched = jnp.where(fire, fetched, 0)

        # exchange: park sends, pop keyed on the RECEIVERS' clocks — a
        # due row surfaces only on a step its destination shard fires
        rv, ri, ring, pending = ex_mod.exchange_local_delayed(
            codec, astate.ring, sv, si, state.tick, delays, prog.identity,
            recv_gate=fire)

        # phase 2 needs no fire masking: a gated (non-firing) receiver's
        # rows arrive empty (ids -1 / identity), and the receive phase is
        # an exact no-op on empty buffers
        if push_mode:
            old_plane = aux[:, 0]
            p2v = jax.vmap(lambda res, a, rvals, rids: _phase2_receive_push(
                prog, ep, res, a, rvals, rids))
            residual, active, accepted = p2v(old_plane, active, rv, ri)
            aux = aux.at[:, 0].set(residual)
            new_plane = residual
        else:
            old_plane = values
            p2v = jax.vmap(lambda v, a, c, rvals, rids:
                           _phase2_receive(prog, ep, v, a, c, rvals, rids))
            values, active, cursor, accepted = p2v(values, active, cursor,
                                                   rv, ri)
            aux = aux if push_mode else state.aux
            new_plane = values
        if ep.straggler_demote:
            slow_rows = _slow_recv_rows(ep, ri.shape[1], delays)
            new_demote = jax.vmap(lambda nv, ov, rids, srow: _demote_row(
                agg, ep, nv, ov, rids, srow))(new_plane, old_plane, ri,
                                              slow_rows)
            # a non-firing shard carries its pending demotions to its
            # next firing instead of forgetting them (the sync tick
            # recomputes every tick because every shard fires every tick)
            demote = jnp.where(fire_v, new_demote, astate.demote)
        else:
            demote = jnp.zeros_like(astate.demote)

        clock = astate.clock + fire.astype(jnp.int32)
        inflight = (ring.ids >= 0) & (ring.due >= 0)[..., None]
        shard_pending = jnp.sum(inflight, axis=(0, 1, 3))
        stats = TickStats(jnp.sum(active), jnp.sum(sent),
                          jnp.sum(accepted), jnp.sum(fetched))
        astats = AsyncStats(stats, pending, jnp.sum(active, axis=1),
                            shard_pending, clock)
        core = EngineState(values, active, cursor, state.tick + 1, aux)
        return AsyncState(core, ring, demote, clock), astats, (sv, si)

    return jax.jit(tick)


def init_async_dist_state(prog, ep: EngineParams, graph: ShardedGraph,
                          ring_delay: int) -> AsyncState:
    """Like :func:`init_async_state` but with the per-shard (sender-side)
    ring layout the dist transport rings: [P, ring_len, Pn, cap]."""
    L1 = ring_delay + 1
    Pn, cap = ep.num_shards, ep.route_capacity
    return AsyncState(
        init_state(prog, graph),
        ex_mod.DelayRing(
            jnp.full((Pn, L1, Pn, cap), prog.identity, prog.jdtype),
            jnp.full((Pn, L1, Pn, cap), -1, jnp.int32),
            jnp.full((Pn, L1, Pn), -1, jnp.int32)),
        jnp.zeros((Pn, ep.vs), bool),
        jnp.zeros((Pn,), jnp.int32))


def make_async_dist_tick(prog, ep: EngineParams, mesh: Mesh,
                         weighted: bool):
    """Async step over ``shard_map``: the production transport with the
    same per-shard-clock semantics (and bit-identical delivery order) as
    :func:`make_async_tick`.  ``delays [P, Pn]`` and ``fire [P]`` ride
    replicated — every sender gates its per-receiver ring rows on the
    full firing vector."""
    axis = "workers"
    codec = wire_codec(prog, ep)
    agg = prog.aggregator
    push_mode = not agg.idempotent

    def local_fn(values, active, cursor, tick, aux, rv_ring, ri_ring,
                 rd_ring, demote, clock, row_ptr, col_idx, weights, delays,
                 fire, window):
        sid = jax.lax.axis_index(axis)
        old_v, old_a, old_c = values[0], active[0], cursor[0]
        aux_row = aux[0] if push_mode else None
        ring = ex_mod.DelayRing(rv_ring[0], ri_ring[0], rd_ring[0])
        w = weights[0] if weighted else None
        f = fire[sid]
        active1, cursor1, sv, si, sent, fetched, values1, aux1 = \
            _phase1_create(prog, ep, old_v, old_a, old_c, row_ptr[0],
                           col_idx[0], w, sid, demote=demote[0],
                           aux=aux_row, stream_window=window[sid])
        values = jnp.where(f, values1, old_v)
        active = jnp.where(f, active1, old_a)
        cursor = jnp.where(f, cursor1, old_c)
        if push_mode:
            aux_row = jnp.where(f, aux1, aux_row)
        sv = jnp.where(f, sv, jnp.asarray(prog.identity, sv.dtype))
        si = jnp.where(f, si, -1)
        sent = jnp.where(f, sent, 0)
        fetched = jnp.where(f, fetched, 0)
        rv, ri, ring, pending = ex_mod.exchange_dist_delayed(
            codec, ring, sv, si, tick, delays[sid], axis, prog.identity,
            recv_gate=fire)
        if push_mode:
            old_plane = aux_row[0]
            residual, active, accepted = _phase2_receive_push(
                prog, ep, old_plane, active, rv, ri)
            aux_row = aux_row.at[0].set(residual)
            new_plane, aux_out = residual, aux_row[None]
        else:
            old_plane = values
            values, active, cursor, accepted = _phase2_receive(
                prog, ep, values, active, cursor, rv, ri)
            new_plane, aux_out = values, aux
        if ep.straggler_demote:
            srow = delays[jnp.arange(ri.shape[0], dtype=jnp.int32)
                          % ep.num_shards, sid] > 0
            dem = _demote_row(agg, ep, new_plane, old_plane, ri, srow)
            dem = jnp.where(f, dem, demote[0])
        else:
            dem = jnp.zeros_like(demote[0])
        new_clock = clock[0] + f.astype(jnp.int32)
        inflight = (ring.ids >= 0) & (ring.due >= 0)[..., None]
        shard_pending = jax.lax.psum(jnp.sum(inflight, axis=(0, 2)), axis)
        stats = TickStats(jax.lax.psum(jnp.sum(active), axis),
                          jax.lax.psum(sent, axis),
                          jax.lax.psum(accepted, axis),
                          jax.lax.psum(fetched, axis))
        pending = jax.lax.psum(pending, axis)
        return (values[None], active[None], cursor[None], tick + 1,
                aux_out, ring.vals[None], ring.ids[None], ring.due[None],
                dem[None], new_clock[None], stats, pending,
                jnp.sum(active)[None], shard_pending)

    def tick_fn(astate: AsyncState, g: ShardGraph, delays, fire,
                window=None):
        state = astate.core
        if window is None:  # full static window for every shard
            window = jnp.full((ep.num_shards,), ep.degree_window,
                              jnp.int32)
        Pw = P(axis)
        aux_spec = Pw if push_mode else P()
        sm = shard_map(
            local_fn, mesh=mesh,
            in_specs=(Pw, Pw, Pw, P(), aux_spec, Pw, Pw, Pw, Pw, Pw, Pw,
                      Pw, Pw if weighted else P(), P(), P(), P()),
            out_specs=(Pw, Pw, Pw, P(), aux_spec, Pw, Pw, Pw, Pw, Pw,
                       TickStats(P(), P(), P(), P()), P(), Pw, P()),
            check_vma=False)
        weights = g.weights if weighted else jnp.zeros((), jnp.float32)
        aux_in = state.aux if push_mode else jnp.zeros((), jnp.float32)
        (values, active, cursor, tick, aux, rvr, rir, rdr, demote, clock,
         stats, pending, shard_active, shard_pending) = sm(
            state.values, state.active, state.cursor, state.tick, aux_in,
            astate.ring.vals, astate.ring.ids, astate.ring.due,
            astate.demote, astate.clock, g.row_ptr, g.col_idx, weights,
            delays, fire, window)
        core = EngineState(values, active, cursor, tick,
                           aux if push_mode else state.aux)
        astats = AsyncStats(stats, pending, shard_active, shard_pending,
                            clock)
        return (AsyncState(core, ex_mod.DelayRing(rvr, rir, rdr), demote,
                           clock), astats)

    # jitted like make_async_tick (host drivers step it thousands of
    # times); lower_tick_for_mesh re-wraps for donation, which collapses
    return jax.jit(tick_fn)


# ======================================================================
# Host driver helpers
# ======================================================================
def init_state(prog, graph: ShardedGraph) -> EngineState:
    P_, vs = graph.num_shards, graph.vs
    gids = jnp.arange(P_ * vs, dtype=jnp.int32).reshape(P_, vs)
    valid = gids < graph.num_real_vertices
    values, active = prog.init(gids, valid)
    aux = prog.init_aux(gids, valid) if prog.aux_channels else None
    return EngineState(values, active,
                       jnp.zeros((P_, vs), jnp.int32),
                       jnp.zeros((), jnp.int32), aux)


def to_device_graph(graph: ShardedGraph) -> ShardGraph:
    return ShardGraph(
        jnp.asarray(graph.row_ptr, jnp.int32),
        jnp.asarray(np.where(graph.col_idx < 0, -1, graph.col_idx), jnp.int32),
        jnp.asarray(graph.weights) if graph.weights is not None else None)


class EngineSession:
    """A resumable engine run: the host-side driver behind
    :func:`run_to_convergence`, extracted so a server can interleave
    convergence work with query traffic (tick a few steps, answer
    queries, tick again) and keep the run alive across streaming graph
    deltas (``serve/graph.py``).

    Holds (graph, program, params, tick builders, mode state) for one
    schedule — plain sync, crowded (deferred-delivery ring), or async —
    and exposes :meth:`tick_until_quiescent`.  The per-tick bookkeeping
    order (fault recording → checkpoint cut → kill/recover → log entry →
    convergence test) is lifted verbatim from the old inline loops;
    :func:`run_to_convergence` is now a thin wrapper over this class and
    must stay bit-identical to the pre-extraction behavior
    (tests/test_session.py pins the parity).

    ``latency`` — a ``dist.latency.LatencyModel`` (or None to resolve one
    from ``cfg.latency_profile``) switches the run onto the crowded tick:
    messages cross the deferred-delivery ring, crowded shards get
    throttled work budgets, and quiescence additionally requires the
    ring to drain.  A ``fault_plan`` with slowdown fields composes.

    ``schedule`` — ``"sync"`` (default; the BSP-style global tick
    barrier) or ``"async"`` (barrier-free: each shard consumes its
    delay-ring arrivals and pushes new messages on its own seeded firing
    steps, advancing a per-shard logical clock).  ``None`` resolves from
    ``cfg.schedule``.  Async runs always cross the delay ring and are
    quiescent when EVERY shard's frontier is empty AND its inbound ring
    rows are drained.

    ``mesh`` — a 1-D device mesh (``launch/mesh.py`` ``make_worker_mesh``)
    runs the plain tick over it (:func:`shard_local_tick`): state and
    graph split over the mesh by shard, ``num_shards / devices`` shards
    on each device.  Fault plans, latency models and the async schedule
    do not run on a mesh yet (``ValueError``).
    """

    def __init__(self, cfg: GraphConfig, *,
                 graph: Optional[ShardedGraph] = None, prog=None,
                 params: Optional[EngineParams] = None,
                 collect_log: bool = False, fault_plan=None, latency=None,
                 schedule: Optional[str] = None,
                 mesh: Optional[Mesh] = None):
        from repro.core import faults as faults_mod
        from repro.dist import latency as lat_mod
        self._faults = faults_mod
        self.cfg = cfg
        self.graph = graph or build_sharded_graph(cfg)
        self.prog = prog or prog_mod.get_program(cfg)
        self.ep = params or default_params(cfg, self.graph, self.prog)
        self.mesh = mesh
        if mesh is not None:
            on_mesh(self.ep, mesh)  # the shards split evenly over it
        self.g = self._place(to_device_graph(self.graph))
        self.collect_log = collect_log
        self.fault_plan = fault_plan

        schedule = schedule or getattr(cfg, "schedule", "sync") or "sync"
        if schedule not in ("sync", "async"):
            raise ValueError(f"unknown schedule {schedule!r}; "
                             f"valid: 'sync', 'async'")
        self.schedule = schedule
        if latency is None and cfg.latency_profile != "none":
            latency = lat_mod.from_config(cfg)
        self.latency = latency
        injected = faults_mod.max_injected_delay(fault_plan)
        self.crowded = (latency is not None
                        or faults_mod.injects_slowdown(fault_plan))
        self.max_delay = (max(latency.max_delay if latency else 0, injected)
                          if self.crowded else 0)
        if mesh is not None:
            unsupported = [what for what, used in (
                ("a fault plan", fault_plan is not None),
                ("a latency model", self.crowded),
                ("the async schedule", schedule == "async")) if used]
            if unsupported:
                raise ValueError(f"{' and '.join(unsupported)} cannot run "
                                 "on a mesh yet: run without mesh=")

        self.log: list = []
        self.totals = {"ticks": 0, "sent": 0, "accepted": 0, "fetched": 0,
                       "replayed": 0, "failures": 0, "pending": 0,
                       "recv_slots": 0, "schedule": schedule}
        # the plain tick's receive slots, summed on the device over the
        # ticks since the last pull (None: none), and those ticks
        self._recv_slots = None
        self._recv_ticks = 0
        self._t = 0  # host step counter (fault schedules key on it)
        self._pending = 0
        self._ring_ckpt = None
        if schedule == "async":
            self._init_async(lat_mod)
        elif self.crowded:
            self._init_crowded()
        else:
            self._init_plain()

    # -- mode setup ----------------------------------------------------
    def _init_async(self, lat_mod) -> None:
        cfg, latency, fault_plan = self.cfg, self.latency, self.fault_plan
        P_ = self.graph.num_shards
        self._base_delays = (latency.delays if latency
                             else np.zeros((P_, P_), np.int32))
        self._base_throttle = (latency.throttle if latency
                               else np.ones((P_,), np.int32))
        self._inter = lat_mod.make_interleaving(
            P_, rates=self._base_throttle,
            seed=getattr(cfg, "async_seed", 0),
            jitter=getattr(cfg, "async_jitter", False))
        plan_rate = (fault_plan.slow_intensity
                     if self._faults.injects_slowdown(fault_plan) else 1)
        max_stall = self._inter.stall_bound(plan_rate)
        self._ring_delay = async_ring_delay(self.max_delay, max_stall)
        # cycle-scaled resources: one firing of a rate-k shard stands in
        # for k barrier steps, so it must carry k steps' worth of edge
        # streaming and routing room.  Compile the widened window / caps
        # once (max rate across the profile and any injected slowdown)
        # and pass the LIVE per-shard window each step; a healthy run has
        # r_all == 1 and keeps the exact sync-shaped params, preserving
        # bit-identity with the barrier schedule.
        self._r_all = max(int(np.asarray(self._base_throttle).max(initial=1)),
                          plan_rate, 1)
        self.ep_run = (dataclasses.replace(
            self.ep, degree_window=self.ep.degree_window * self._r_all,
            route_capacity=self.ep.route_capacity * self._r_all)
            if self._r_all > 1 else self.ep)
        self._D_base = self.ep.degree_window
        # replay recovery must reach back past the checkpoint by the
        # maximum link delay AND the staleness bound: a pre-checkpoint
        # send can sit due-but-unconsumed until its receiver fires
        self.fault_mgr = self._faults.FaultManager(
            cfg, self.graph, self.prog, self.ep_run,
            replay_slack=self.max_delay + max_stall) \
            if fault_plan is not None else None
        self._tick_fn = make_async_tick(self.prog, self.ep_run,
                                        self.prog.weighted)
        self._astate = init_async_state(self.prog, self.ep_run, self.graph,
                                        self._ring_delay)
        self._n_active = int(jnp.sum(self._astate.core.active))
        self._shard_busy = np.asarray(
            jnp.sum(self._astate.core.active, axis=1))

    def _init_sync_fault_mgr(self) -> None:
        # replay recovery must reach back past the checkpoint by the
        # maximum link delay: deferred messages straddling the snapshot
        # are otherwise in neither the restored state nor the replayed
        # range
        self.fault_mgr = self._faults.FaultManager(
            self.cfg, self.graph, self.prog, self.ep,
            replay_slack=self.max_delay) \
            if self.fault_plan is not None else None

    def _init_crowded(self) -> None:
        latency = self.latency
        P_ = self.graph.num_shards
        self._init_sync_fault_mgr()
        self.ep_run = self.ep
        self._base_delays = (latency.delays if latency
                             else np.zeros((P_, P_), np.int32))
        self._base_throttle = (latency.throttle if latency
                               else np.ones((P_,), np.int32))
        self._tick_fn = make_crowded_tick(self.prog, self.ep,
                                          self.prog.weighted)
        self._cstate = init_crowded_state(self.prog, self.ep, self.graph,
                                          self.max_delay)
        self._n_active = int(jnp.sum(self._cstate.core.active))

    def _init_plain(self) -> None:
        self._init_sync_fault_mgr()
        self.ep_run = self.ep
        if self.mesh is None:
            self._tick_fn = make_local_tick(self.prog, self.ep,
                                            self.prog.weighted)
        else:
            self._tick_fn = jax.jit(shard_local_tick(
                self.prog, self.ep, self.mesh, self.prog.weighted))
        # a zero-budget run (or an initially empty frontier) must still
        # report a well-defined activity count
        self._state = self._place(init_state(self.prog, self.graph))
        self._n_active = int(jnp.sum(self._state.active))

    def _place(self, tree):
        """``tree`` split over the session's mesh by its leading (shard)
        axis, scalars replicated; as it is without a mesh."""
        if self.mesh is None:
            return tree
        rows = NamedSharding(self.mesh, P(self.mesh.axis_names[0]))
        whole = NamedSharding(self.mesh, P())
        return jax.tree.map(lambda x: jax.device_put(
            x, whole if jnp.ndim(x) == 0 else rows), tree)

    # -- per-tick drivers (bookkeeping order mirrors across all three:
    # totals, fault handling, log entry — keep changes in sync) --------
    def _step_async(self) -> None:
        t, fault_plan, fault_mgr = self._t, self.fault_plan, self.fault_mgr
        # key the interleaving (and the emulated slowdown windows) on
        # the DEVICE tick, not the host step: a checkpoint restore
        # rewinds core.tick, and the ring-sizing guarantee (every due
        # row is consumed within max_stall steps of its slot being
        # reused) only holds if the firing pattern is a pure function
        # of device time — keyed on the host step, the pattern would
        # shift across a restore and a due-but-unconsumed row could
        # be overwritten, silently dropping in-flight messages
        with trace.span(trace.SYNC, pulls=1):
            dev_tick = int(self._astate.core.tick)
        delays, throttle = self._faults.apply_slowdown(
            fault_plan, dev_tick, self._base_delays, self._base_throttle)
        fire = self._inter.fire_mask(dev_tick, rates=throttle)
        window = jnp.asarray(
            np.minimum(np.asarray(throttle, np.int64), self._r_all)
            * self._D_base, jnp.int32)
        with trace.span(trace.DISPATCH):
            astate, astats, send_bufs = self._tick_fn(
                self._astate, self.g,
                jnp.asarray(np.minimum(delays, self.max_delay), jnp.int32),
                jnp.asarray(fire), window)
        stats = astats.base
        totals = self.totals
        with trace.span(trace.SYNC, pulls=7):
            n_active = int(stats.active)
            pending = int(astats.pending)
            shard_busy = (np.asarray(astats.shard_active)
                          + np.asarray(astats.shard_pending))
            totals["ticks"] += 1
            totals["sent"] += int(stats.sent)
            totals["accepted"] += int(stats.accepted)
            totals["fetched"] += int(stats.fetched)
        if fault_mgr is not None:
            fault_mgr.record(t, astate.core, send_bufs,
                             clock=astate.clock)
            if (fault_mgr.recovery == "checkpoint"
                    and t % fault_mgr.ckpt_every == 0):
                # the consistent cut under per-shard clocks is no
                # longer "same logical tick everywhere" — it is the
                # snapshot instant's (state, ring, wall-clock step,
                # clock VECTOR): the ring carries every in-flight
                # message and the clock vector records how far each
                # shard had advanced
                self._ring_ckpt = (astate.ring, astate.demote,
                                   astate.core.tick, astate.clock)
            core, extra = fault_mgr.maybe_fail(
                t, astate.core, fault_plan, clock=astate.clock)
            astate = astate._replace(core=core)
            if extra.get("clock") is not None:
                astate = astate._replace(clock=extra["clock"])
            if (extra.get("failures")
                    and fault_mgr.recovery == "checkpoint"):
                if self._ring_ckpt is not None:
                    ring, demote, snap_tick, snap_clock = self._ring_ckpt
                    astate = AsyncState(core._replace(tick=snap_tick),
                                        ring, demote, snap_clock)
                else:  # no snapshot yet -> run re-inits: empty ring
                    astate = init_async_state(
                        self.prog, self.ep_run, self.graph,
                        self._ring_delay)._replace(
                        core=core._replace(
                            tick=jnp.zeros((), jnp.int32)))
                with trace.span(trace.SYNC, pulls=1):
                    pending = int(jnp.sum(
                        (astate.ring.ids >= 0)
                        & (astate.ring.due >= 0)[..., None]))
            totals["replayed"] += extra.get("replayed", 0)
            totals["failures"] += extra.get("failures", 0)
            if extra.get("failures"):
                with trace.span(trace.SYNC, pulls=3):
                    n_active = int(jnp.sum(astate.core.active))
                    shard_busy = (
                        np.asarray(jnp.sum(astate.core.active, axis=1))
                        + np.asarray(jnp.sum(
                            (astate.ring.ids >= 0)
                            & (astate.ring.due >= 0)[..., None],
                            axis=(0, 1, 3))))
        if self.collect_log:
            # the clock; the rest were pulled above, ``fire`` is host numpy
            with trace.span(trace.SYNC, pulls=1):
                self.log.append({
                    "tick": t, "active": n_active,
                    "sent": int(stats.sent),
                    "accepted": int(stats.accepted),
                    "fetched": int(stats.fetched), "pending": pending,
                    "fired": np.asarray(fire).astype(int).tolist(),
                    "clock": np.asarray(astate.clock).tolist(),
                    "shard_active": np.asarray(
                        astats.shard_active).tolist(),
                    "shard_pending": np.asarray(
                        astats.shard_pending).tolist()})
        self._astate = astate
        self._n_active = n_active
        self._pending = pending
        self._shard_busy = shard_busy

    def _step_crowded(self) -> None:
        t, fault_plan, fault_mgr = self._t, self.fault_plan, self.fault_mgr
        delays, throttle = self._faults.apply_slowdown(
            fault_plan, t, self._base_delays, self._base_throttle)
        with trace.span(trace.DISPATCH):
            cstate, cstats, send_bufs = self._tick_fn(
                self._cstate, self.g,
                jnp.asarray(np.minimum(delays, self.max_delay), jnp.int32),
                jnp.asarray(throttle, jnp.int32))
        stats = cstats.base
        totals = self.totals
        with trace.span(trace.SYNC, pulls=5):
            n_active = int(stats.active)
            pending = int(cstats.pending)
            totals["ticks"] += 1
            totals["sent"] += int(stats.sent)
            totals["accepted"] += int(stats.accepted)
            totals["fetched"] += int(stats.fetched)
        if fault_mgr is not None:
            fault_mgr.record(t, cstate.core, send_bufs)
            if (fault_mgr.recovery == "checkpoint"
                    and t % fault_mgr.ckpt_every == 0):
                # checkpoint-restore recovery rolls EVERY shard back
                # to the snapshot; with a delay ring the snapshot's
                # consistent cut must include the in-flight messages
                # (their senders' cursors have already advanced, so
                # they would never be re-sent) AND the device tick
                # (ring slots are keyed by tick % ring_len — resumed
                # pushes must reuse the original numbering or they
                # would collide with restored in-flight slots)
                self._ring_ckpt = (cstate.ring, cstate.demote,
                                   cstate.core.tick)
            core, extra = fault_mgr.maybe_fail(t, cstate.core,
                                               fault_plan)
            cstate = cstate._replace(core=core)
            if extra.get("failures") and fault_mgr.recovery == "checkpoint":
                if self._ring_ckpt is not None:
                    ring, demote, snap_tick = self._ring_ckpt
                    cstate = CrowdedState(core._replace(tick=snap_tick),
                                          ring, demote)
                else:  # no snapshot yet -> run re-inits: empty ring
                    cstate = init_crowded_state(
                        self.prog, self.ep, self.graph,
                        self.max_delay)._replace(
                        core=core._replace(
                            tick=jnp.zeros((), jnp.int32)))
                with trace.span(trace.SYNC, pulls=1):
                    pending = int(jnp.sum(
                        (cstate.ring.ids >= 0)
                        & (cstate.ring.due >= 0)[..., None]))
            totals["replayed"] += extra.get("replayed", 0)
            totals["failures"] += extra.get("failures", 0)
            if extra.get("failures"):
                with trace.span(trace.SYNC, pulls=1):
                    n_active = int(jnp.sum(cstate.core.active))
        if self.collect_log:
            # the shard arrays; the ints were pulled above
            with trace.span(trace.SYNC, pulls=2):
                self.log.append({
                    "tick": t, "active": n_active,
                    "sent": int(stats.sent),
                    "accepted": int(stats.accepted),
                    "fetched": int(stats.fetched), "pending": pending,
                    "shard_work": (np.asarray(cstats.shard_fetched)
                                   + np.asarray(cstats.shard_recv)
                                   ).tolist()})
        self._cstate = cstate
        self._n_active = n_active
        self._pending = pending

    def _step_plain(self) -> None:
        t, fault_plan, fault_mgr = self._t, self.fault_plan, self.fault_mgr
        with trace.span(trace.DISPATCH):
            state, stats, send_bufs = self._tick_fn(self._state, self.g)
        self._count_recv_slots(stats.recv_slots)
        totals = self.totals
        with trace.span(trace.SYNC) as span:
            n_active = int(stats.active)
            totals["ticks"] += 1
            totals["sent"] += int(stats.sent)
            # a message that improves a value leaves its vertex active, so
            # an idempotent tick that leaves none active accepted none
            pull_accepted = (n_active > 0
                             or not self.prog.aggregator.idempotent)
            accepted = int(stats.accepted) if pull_accepted else 0
            totals["accepted"] += accepted
            totals["fetched"] += int(stats.fetched)
            span.set_metadata(pulls=3 + pull_accepted)
        if fault_mgr is not None:
            fault_mgr.record(t, state, send_bufs)
            state, extra = fault_mgr.maybe_fail(t, state, fault_plan)
            totals["replayed"] += extra.get("replayed", 0)
            totals["failures"] += extra.get("failures", 0)
            if extra.get("failures"):
                with trace.span(trace.SYNC, pulls=1):
                    n_active = int(jnp.sum(state.active))
        if self.collect_log:  # the ints were pulled above
            self.log.append({"tick": t, "active": n_active,
                             "sent": int(stats.sent),
                             "accepted": accepted,
                             "fetched": int(stats.fetched)})
        self._state = state
        self._n_active = n_active

    def _count_recv_slots(self, slots) -> None:
        """Adds a tick's receive slots to the sum kept on the device,
        pulling it first where one more tick could overflow its int32."""
        ep = self.ep_run
        if (self._recv_ticks + 1) * ep.num_shards ** 2 * ep.route_capacity \
                >= 2 ** 31:
            self._pull_recv_slots()
        if self._recv_slots is None:  # placed as the tick's counts are, so
            # that the add compiles on the first tick and never again
            self._recv_slots = self._place(jnp.zeros((), jnp.int32))
        self._recv_slots = self._recv_slots + slots
        self._recv_ticks += 1

    def _pull_recv_slots(self) -> None:
        """The one pull of the receive slots summed since the last, into
        ``totals["recv_slots"]``; its span also gives the slots that a
        full-width receive would have processed (``recv_budget``)."""
        if self._recv_slots is None:
            return
        ep = self.ep_run
        budget = self._recv_ticks * ep.num_shards ** 2 * ep.route_capacity
        with trace.span(trace.TOTALS, pulls=1) as span:
            slots = int(self._recv_slots)
            span.set_metadata(recv_slots=slots, recv_budget=budget)
        self.totals["recv_slots"] += slots
        self._recv_slots, self._recv_ticks = None, 0

    # -- public surface ------------------------------------------------
    @property
    def state(self) -> EngineState:
        """The core engine state (ring/clock planes stay internal)."""
        if self.schedule == "async":
            return self._astate.core
        if self.crowded:
            return self._cstate.core
        return self._state

    @property
    def quiescent(self) -> bool:
        """No frontier anywhere and (ring modes) all deliveries drained.

        Async quiescence is per-shard: EVERY shard must have an empty
        frontier AND a drained inbound ring (a barrier-free run has no
        "same tick everywhere" instant to test at)."""
        if self.schedule == "async":
            return int(self._shard_busy.max(initial=0)) == 0
        if self.crowded:
            return self._n_active == 0 and self._pending == 0
        return self._n_active == 0

    def step(self) -> None:
        """Run exactly one engine tick (plus its fault bookkeeping)."""
        with trace.span(trace.STEP, tick=self._t):
            if self.schedule == "async":
                self._step_async()
            elif self.crowded:
                self._step_crowded()
            else:
                self._step_plain()
        self._t += 1

    def tick_until_quiescent(self, budget: Optional[int] = None) -> dict:
        """Tick until quiescent or ``budget`` ticks elapse; returns the
        cumulative totals snapshot.  ``None`` -> ``cfg.max_ticks``.

        Parity note: the very first call always runs at least one tick
        even on an initially-empty frontier (the pre-extraction loop had
        no pre-loop convergence test); later calls on a quiescent
        session return immediately, so a server can poll for free."""
        budget = self.cfg.max_ticks if budget is None else budget
        for _ in range(budget):
            if self.totals["ticks"] > 0 and self.quiescent:
                break
            self.step()
            if self.quiescent:
                break
        return self.totals_snapshot()

    def totals_snapshot(self) -> dict:
        """The metrics dict ``run_to_convergence`` has always returned,
        with the receive slots that the device counted since the last
        snapshot pulled into it."""
        self._pull_recv_slots()
        out = dict(self.totals)
        if self.schedule == "async":
            out["pending"] = self._pending
            out["converged"] = self.quiescent
            out["clock"] = np.asarray(self._astate.clock).tolist()
            out["log"] = self.log
            return out
        if self.crowded:
            out["pending"] = self._pending
        out["converged"] = self.quiescent
        out["log"] = self.log
        return out

    # -- streaming-delta hooks (serve/graph.py) ------------------------
    def fork(self) -> "EngineSession":
        """A shadow copy of this session: same graph / program / params
        / schedule, with the CURRENT run state (core state, ring and
        clock planes, host step, cumulative totals) duplicated so the
        fork and the original tick independently from this instant.

        This is the double-buffered serving path's write handle: the
        primary session keeps answering queries at the committed
        fixpoint while the fork absorbs a streaming delta and ticks
        toward the next epoch; at commit the fork atomically replaces
        the primary (``serve/graph.py::DeltaTransaction``).

        The compiled tick function is SHARED (it is a pure function of
        (program, params) — a fork must not pay a second JIT compile).
        Engine state lives in immutable jax arrays, so duplicating the
        wrapper tuples is a true logical copy.  The fork gets a FRESH
        FaultManager (no message log / snapshots): callers seed it with
        ``rebase_recovery()``, exactly as the delta path requires."""
        new = EngineSession(self.cfg, graph=self.graph, prog=self.prog,
                            params=self.ep, collect_log=self.collect_log,
                            fault_plan=self.fault_plan, latency=self.latency,
                            schedule=self.schedule, mesh=self.mesh)
        new._tick_fn = self._tick_fn
        if self.schedule == "async":
            new._astate = self._astate
            new._shard_busy = np.asarray(self._shard_busy).copy()
        elif self.crowded:
            new._cstate = self._cstate
        else:
            new._state = self._state
        new._n_active = self._n_active
        new._pending = self._pending
        new._ring_ckpt = self._ring_ckpt
        new._t = self._t
        new._recv_slots, new._recv_ticks = self._recv_slots, self._recv_ticks
        new.totals = dict(self.totals)
        new.log = list(self.log)
        return new

    def replace_state(self, core: EngineState) -> None:
        """Swap the core engine state (host-side delta seeding) and
        refresh the activity counters.  The ring / demotion / clock
        planes of the crowded and async wrappers are preserved — deltas
        are applied at quiescence, when the rings are drained."""
        self._n_active = int(jnp.sum(core.active))
        if self.schedule == "async":
            self._astate = self._astate._replace(core=core)
            self._shard_busy = (
                np.asarray(jnp.sum(core.active, axis=1))
                + np.asarray(jnp.sum(
                    (self._astate.ring.ids >= 0)
                    & (self._astate.ring.due >= 0)[..., None],
                    axis=(0, 1, 3))))
        elif self.crowded:
            self._cstate = self._cstate._replace(core=core)
        else:
            self._state = self._place(core)

    def rebind_graph(self, graph: ShardedGraph) -> None:
        """Point the session at a patched graph (streaming delta).  The
        jitted tick retraces automatically if the padded edge width
        changed; EngineParams stay as derived for the original graph, so
        route capacity keeps its head-room across small deltas.  On a
        mesh the graph is split over it as the first one was."""
        self.graph = graph
        self.g = self._place(to_device_graph(graph))
        if self.fault_mgr is not None:
            self.fault_mgr.graph = graph

    def rebase_recovery(self) -> None:
        """Make the CURRENT state the recovery floor (call right after a
        delta is seeded): pre-delta snapshots and logged messages were
        derived on the old graph — restoring or replaying them would
        resurrect stale values and silently diverge from the patched
        graph's fixpoint.  Checkpoint-restore recovery additionally
        re-cuts its ring snapshot at this instant."""
        if self.fault_mgr is None:
            return
        if self.schedule == "async":
            self.fault_mgr.rebase(self._t, self._astate.core,
                                  clock=self._astate.clock,
                                  graph=self.graph)
            self._ring_ckpt = (self._astate.ring, self._astate.demote,
                               self._astate.core.tick, self._astate.clock)
        elif self.crowded:
            self.fault_mgr.rebase(self._t, self._cstate.core,
                                  graph=self.graph)
            self._ring_ckpt = (self._cstate.ring, self._cstate.demote,
                               self._cstate.core.tick)
        else:
            self.fault_mgr.rebase(self._t, self._state, graph=self.graph)


def run_to_convergence(cfg: GraphConfig, *, graph: Optional[ShardedGraph] = None,
                       prog=None, params: Optional[EngineParams] = None,
                       max_ticks: Optional[int] = None,
                       collect_log: bool = False,
                       fault_plan=None, latency=None,
                       schedule: Optional[str] = None,
                       mesh: Optional[Mesh] = None):
    """Host loop (the propagation phase). Returns (state, metrics dict).

    Thin wrapper over :class:`EngineSession` — construct a session, tick
    it to quiescence, return ``(state, totals)``.  See the session class
    for the ``latency`` / ``schedule`` semantics; behavior (including
    every per-tick side effect) is identical to the old inline loops.
    """
    session = EngineSession(cfg, graph=graph, prog=prog, params=params,
                            collect_log=collect_log, fault_plan=fault_plan,
                            latency=latency, schedule=schedule, mesh=mesh)
    totals = session.tick_until_quiescent(
        cfg.max_ticks if max_ticks is None else max_ticks)
    return session.state, totals


# ======================================================================
# Dry-run entry (launch/dryrun.py --graph)
# ======================================================================
def lower_tick_for_mesh(cfg: GraphConfig, mesh_2d, n_workers: int):
    """Lower+compile the distributed tick on a 1-D workers view of the
    production mesh (the graph engine shards vertices over every chip)."""
    devs = np.asarray(mesh_2d.devices).reshape(-1)[:n_workers]
    mesh = Mesh(devs, ("workers",), axis_types=(AxisType.Auto,))
    cfg = dataclasses.replace(cfg, num_shards=n_workers)
    prog = prog_mod.get_program(cfg)
    from repro.dist.sharding import vertex_partition
    vs = vertex_partition(cfg.num_vertices, n_workers).vs
    es = max(cfg.num_edges * 2 // n_workers, 1)  # symmetrized estimate
    # ONE derivation with production (default_params) — the dry-run
    # compiles exactly the params a real run would use, including the
    # SUM/idempotence wire gating
    ep = derive_params(cfg, num_shards=n_workers, vs=vs, es=es,
                       num_vertices=cfg.num_vertices, prog=prog)

    sh = lambda spec: NamedSharding(mesh, spec)
    Pw = P("workers")
    state = EngineState(
        jax.ShapeDtypeStruct((n_workers, vs), prog.jdtype, sharding=sh(Pw)),
        jax.ShapeDtypeStruct((n_workers, vs), jnp.bool_, sharding=sh(Pw)),
        jax.ShapeDtypeStruct((n_workers, vs), jnp.int32, sharding=sh(Pw)),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=sh(P())),
        jax.ShapeDtypeStruct((n_workers, prog.aux_channels, vs),
                             prog.jdtype, sharding=sh(Pw))
        if prog.aux_channels else None,
    )
    g = ShardGraph(
        jax.ShapeDtypeStruct((n_workers, vs + 1), jnp.int32, sharding=sh(Pw)),
        jax.ShapeDtypeStruct((n_workers, es), jnp.int32, sharding=sh(Pw)),
        jax.ShapeDtypeStruct((n_workers, es), jnp.float32, sharding=sh(Pw))
        if prog.weighted else None,
    )
    codec = wire_codec(prog, ep)
    info = {"workers": n_workers, "vs": vs, "es": es,
            "M": ep.max_vertices_per_tick, "D": ep.degree_window,
            "cap": ep.route_capacity, "wire": codec.compression,
            "wire_bytes_per_tick": codec.wire_bytes_per_tick(),
            "schedule": cfg.schedule}
    if cfg.schedule == "async":
        # the async tick carries a different state pytree (ring + demote
        # + clock vector) and two extra replicated inputs — lower exactly
        # what a production async run would compile
        from repro.dist import latency as lat_mod
        lat = (lat_mod.from_config(cfg)
               if cfg.latency_profile != "none" else None)
        inter = lat_mod.make_interleaving(
            n_workers,
            rates=lat.throttle if lat else None,
            seed=cfg.async_seed, jitter=cfg.async_jitter)
        ring_delay = async_ring_delay(lat.max_delay if lat else 0,
                                      inter.stall_bound())
        # cycle-scaled resources, as run_to_convergence compiles them: a
        # rate-k firing carries k steps' worth of window and routing room
        r_all = int(inter.rates.max(initial=1))
        ep = (dataclasses.replace(
            ep, degree_window=ep.degree_window * r_all,
            route_capacity=ep.route_capacity * r_all)
            if r_all > 1 else ep)
        info["D"], info["cap"] = ep.degree_window, ep.route_capacity
        L1, cap = ring_delay + 1, ep.route_capacity
        astate = AsyncState(
            state,
            ex_mod.DelayRing(
                jax.ShapeDtypeStruct((n_workers, L1, n_workers, cap),
                                     prog.jdtype, sharding=sh(Pw)),
                jax.ShapeDtypeStruct((n_workers, L1, n_workers, cap),
                                     jnp.int32, sharding=sh(Pw)),
                jax.ShapeDtypeStruct((n_workers, L1, n_workers),
                                     jnp.int32, sharding=sh(Pw))),
            jax.ShapeDtypeStruct((n_workers, vs), jnp.bool_,
                                 sharding=sh(Pw)),
            jax.ShapeDtypeStruct((n_workers,), jnp.int32,
                                 sharding=sh(P())))
        delays = jax.ShapeDtypeStruct((n_workers, n_workers), jnp.int32,
                                      sharding=sh(P()))
        fire = jax.ShapeDtypeStruct((n_workers,), jnp.bool_,
                                    sharding=sh(P()))
        window = jax.ShapeDtypeStruct((n_workers,), jnp.int32,
                                      sharding=sh(P()))
        tick_fn = make_async_dist_tick(prog, ep, mesh, prog.weighted)
        compiled = jax.jit(tick_fn, donate_argnums=(0,)).lower(
            astate, g, delays, fire, window).compile()
        info["ring_slots"] = L1
        return compiled, info
    if cfg.latency_profile != "none":
        # crowded sync tick: a different pytree than the plain tick (the
        # deferred-delivery ring plus replicated delays/throttle riders),
        # so big-mesh dry runs need their own lowering — this is what the
        # scenario matrix's crowded x dist cells compile in production
        from repro.dist import latency as lat_mod
        lat = lat_mod.from_config(cfg)
        L1 = int(lat.max_delay) + 1
        cap = ep.route_capacity
        cstate = CrowdedState(
            state,
            ex_mod.DelayRing(
                jax.ShapeDtypeStruct((n_workers, L1, n_workers, cap),
                                     prog.jdtype, sharding=sh(Pw)),
                jax.ShapeDtypeStruct((n_workers, L1, n_workers, cap),
                                     jnp.int32, sharding=sh(Pw)),
                jax.ShapeDtypeStruct((n_workers, L1, n_workers),
                                     jnp.int32, sharding=sh(Pw))),
            jax.ShapeDtypeStruct((n_workers, vs), jnp.bool_,
                                 sharding=sh(Pw)))
        delays = jax.ShapeDtypeStruct((n_workers, n_workers), jnp.int32,
                                      sharding=sh(P()))
        throttle = jax.ShapeDtypeStruct((n_workers,), jnp.int32,
                                        sharding=sh(P()))
        tick_fn = make_crowded_dist_tick(prog, ep, mesh, prog.weighted)
        compiled = jax.jit(tick_fn, donate_argnums=(0,)).lower(
            cstate, g, delays, throttle).compile()
        info["ring_slots"] = L1
        info["latency_profile"] = cfg.latency_profile
        return compiled, info
    tick_fn = shard_local_tick(prog, ep, mesh, prog.weighted)
    compiled = jax.jit(tick_fn, donate_argnums=(0,)).lower(state, g).compile()
    return compiled, info
