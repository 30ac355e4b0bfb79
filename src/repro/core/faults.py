"""Fault injection + recovery for the ASYMP engine (paper §3.4, §5.5).

Implements the paper's three-step mechanism:
  1. writing checkpoints  — periodic per-shard snapshots of vertex state
     (values + cursors + frontier), taken asynchronously by the host driver;
  2. recovering itself    — on an injected failure the shard's state rolls
     back to its own latest snapshot (other shards keep their newer state —
     there is NO global rollback, unlike BSP checkpointing);
  3. requesting lost msgs — peers replay their logged outgoing buffers for
     ticks since that shard's snapshot (bounded ring log); beyond the log
     horizon they instead re-activate every boundary vertex with an edge into
     the failed shard — strictly correct by self-stabilization, at the cost
     of extra messages (the same trade the paper describes).

Replay (and the boundary fallback) delivers *duplicated* messages, so it
is only legal for programs whose receive-side reduce is idempotent —
``VertexProgram.self_stabilizing`` (paper §3.3).  Programs that declare
``self_stabilizing=False`` are rejected by the replay path: the manager
falls back to a *globally consistent* checkpoint restore (every shard
rolls back to the same snapshot tick — BSP-style, strictly more
expensive, but correct without idempotence).  The shipped ``pagerank``
residual-push program (SUM aggregation) is the canonical case: its
snapshots must carry the push-mode aux planes (residual + latched mass)
alongside values/frontier/cursors, or restored runs would lose and
double-count mass.

`FaultPlan` encodes the paper's §5.5 experiments: fail x% of shards once /
all once / all twice over the course of the run ("rolling failures").

Alongside kill/replay, the plan can inject *slowdowns* (paper §5.4, the
crowded-cluster scenario): a seeded ``slow_fraction`` of shards becomes
crowded for a tick window — their outgoing links gain ``slow_delay``
ticks of wire latency (routed through the exchange substrate's
deferred-delivery ring) and their per-tick work budget is divided by
``slow_intensity``.  Slowdowns are not failures: no state is lost, no
recovery runs — they exercise the *scheduler's* resilience, and compose
freely with kill/replay in the same plan.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import GraphConfig
from repro.core import trace
from repro.core.engine import EngineParams, EngineState, init_state


@dataclasses.dataclass
class FaultPlan:
    """fail_fraction: 0.5 / 1.0 / 2.0 = paper's 50% / 100% / 200% scenarios."""
    fail_fraction: float
    start_tick: int = 4
    every: int = 6  # ticks between rolling failure batches
    batch: int = 1  # shards failed per batch
    seed: int = 0
    # slowdown injection (§5.4): crowd slow_fraction of the shards from
    # slow_start until slow_stop (0 = to the end of the run)
    slow_fraction: float = 0.0
    slow_delay: int = 0  # extra ticks on the crowded shards' outgoing links
    slow_intensity: int = 1  # work-budget divisor while crowded
    slow_start: int = 0
    slow_stop: int = 0

    def slow_shards(self, num_shards: int) -> list[int]:
        """The seeded crowded-shard choice (decorrelated from the kill
        schedule's permutation so combined plans don't always slow the
        same shards they kill)."""
        k = int(round(self.slow_fraction * num_shards))
        rng = np.random.default_rng(self.seed + 1)
        return [int(s) for s in rng.permutation(num_shards)[:k]]

    def schedule(self, num_shards: int) -> dict[int, list[int]]:
        total = int(round(self.fail_fraction * num_shards))
        rng = np.random.default_rng(self.seed)
        shards = [int(s) for s in rng.permutation(num_shards)]
        while len(shards) < total:  # >100%: shards fail multiple times
            shards += [int(s) for s in rng.permutation(num_shards)]
        shards = shards[:total]
        out: dict[int, list[int]] = {}
        t = self.start_tick
        i = 0
        while i < total:
            out[t] = shards[i: i + self.batch]
            i += self.batch
            t += self.every
        return out


def max_injected_delay(plan: Optional[FaultPlan]) -> int:
    """The largest wire delay a plan's slowdown can inject (sizes the
    deferred-delivery ring before the run starts)."""
    if plan is None or plan.slow_fraction <= 0:
        return 0
    return max(int(plan.slow_delay), 0)


def injects_slowdown(plan: Optional[FaultPlan]) -> bool:
    """Does the plan crowd any shard at all — by wire delay OR by
    work-budget throttle?  (A throttle-only plan must still route the
    run onto the crowded tick, else the injection is a silent no-op.)"""
    if plan is None or plan.slow_fraction <= 0:
        return False
    return plan.slow_delay > 0 or plan.slow_intensity > 1


def apply_slowdown(plan: Optional[FaultPlan], t: int, delays: np.ndarray,
                   throttle: np.ndarray
                   ) -> tuple[np.ndarray, np.ndarray]:
    """Overlay a plan's slowdown window onto the base cluster condition.

    Inside [slow_start, slow_stop) the crowded shards' outgoing link
    delays and work throttles are raised to the plan's values (``max``
    against the base, never lowered); outside the window the base
    condition passes through untouched.  Pure host-side numpy — the
    result is fed to the crowded tick as traced arrays, so injection
    never triggers recompilation."""
    if (plan is None or plan.slow_fraction <= 0
            or t < plan.slow_start
            or (plan.slow_stop and t >= plan.slow_stop)):
        return delays, throttle
    # the overlay is deterministic in (plan fields, base) — computed once,
    # not per tick (the host loop calls this every tick of the window).
    # The cache key covers every field the overlay reads, NOT just the
    # base-array identities: a caller mutating slow_delay/slow_fraction/
    # slow_intensity/seed on a (non-frozen) plan between runs used to be
    # served the stale overlay.  (The base arrays are compared by
    # identity; holding them in the cache keeps those ids live.)
    key = (plan.slow_fraction, plan.slow_delay, plan.slow_intensity,
           plan.seed)
    cache = getattr(plan, "_overlay_cache", None)
    if (cache is None or cache[0] != key or cache[1] is not delays
            or cache[2] is not throttle):
        d = delays.copy()
        th = throttle.copy()
        for p in plan.slow_shards(delays.shape[0]):
            d[p, :] = np.maximum(d[p, :], plan.slow_delay)
            th[p] = max(int(th[p]), int(plan.slow_intensity))
        cache = (key, delays, throttle, d, th)
        plan._overlay_cache = cache
    return cache[3], cache[4]


class FaultManager:
    def __init__(self, cfg: GraphConfig, graph, prog, ep: EngineParams,
                 replay_slack: int = 0):
        self.cfg, self.graph, self.prog, self.ep = cfg, graph, prog, ep
        # replay recovery re-delivers (duplicates) messages — legal only
        # under the §3.3 idempotence precondition
        self.recovery = ("replay" if getattr(prog, "self_stabilizing", True)
                         else "checkpoint")
        self.ckpt_every = cfg.checkpoint_every
        self.log_ticks = cfg.replay_log_ticks
        # crowded runs: a message produced BEFORE a shard's checkpoint can
        # be delivered AFTER it (deferred delivery), so it is in neither
        # the snapshot nor the naive since+1..t replay range — widen the
        # replayed window by the maximum link delay (duplicates are safe
        # by idempotence; zero for immediate-delivery runs).  Async runs
        # widen further, by the interleaving's stall bound: a due message
        # is only consumed when its receiver fires
        self.replay_slack = replay_slack
        # per-shard checkpoint: tick -> (values, active, cursor, aux) rows
        # (aux = the push-mode sidecar planes, None for idempotent programs)
        self.ckpt_tick = np.full(graph.num_shards, -1, np.int64)
        self.ckpt: dict[int, tuple] = {}
        # async mode: per-shard LOGICAL clock at the snapshot.  The
        # consistent cut under per-shard progress is a vector, not a
        # scalar — "same tick everywhere" no longer exists, so recovery
        # restores each shard to its own recorded clock entry (replay) or
        # the whole vector (global checkpoint restore)
        self.ckpt_clock: dict[int, int] = {}
        # ring log of outgoing buffers: tick -> (send_vals, send_ids) numpy
        self.msg_log: dict[int, tuple] = {}
        self._schedule: Optional[dict[int, list[int]]] = None
        # what the current kill has pulled to the host: the args of its span
        self.kill_pulled = {"pulls": 0, "bytes": 0}

    # ------------------------------------------------------------------
    def record(self, t: int, state: EngineState, send_bufs,
               clock=None) -> None:
        if t % self.ckpt_every == 0:
            with trace.span(trace.SNAPSHOT, **trace.pulled(
                    state.values, state.active, state.cursor, state.aux,
                    clock)):
                vals = np.asarray(state.values)
                act = np.asarray(state.active)
                cur = np.asarray(state.cursor)
                aux = (np.asarray(state.aux) if state.aux is not None
                       else None)
                cl = np.asarray(clock) if clock is not None else None
            for p in range(self.graph.num_shards):
                self.ckpt[p] = (vals[p].copy(), act[p].copy(), cur[p].copy(),
                                aux[p].copy() if aux is not None else None)
                self.ckpt_tick[p] = t
                if cl is not None:
                    self.ckpt_clock[p] = int(cl[p])
        if self.recovery == "replay":  # checkpoint mode never reads the log
            sv, si = send_bufs
            with trace.span(trace.LOG, **trace.pulled(sv, si)):
                self.msg_log[t] = (np.asarray(sv), np.asarray(si))
            # retention must cover the slack-widened replay window, or
            # crowded runs would always fall to the boundary fallback
            for old in list(self.msg_log):
                if old < t - (self.log_ticks + self.replay_slack):
                    del self.msg_log[old]

    # ------------------------------------------------------------------
    def rebase(self, t: int, state: EngineState, clock=None,
               graph=None) -> None:
        """Re-anchor recovery at the CURRENT state (streaming deltas).

        A graph delta invalidates everything recorded before it: logged
        outgoing buffers carry values derived over edges that may no
        longer exist (replaying them would re-poison a targeted reset),
        and older snapshots predate the patched CSR (restoring one would
        resurrect pre-delta state and converge on the wrong graph).
        ``EngineSession.rebase_recovery`` calls this right after the
        delta frontier is seeded: the post-delta state becomes every
        shard's snapshot, the message log is cleared (a kill inside the
        slack window now takes the boundary fallback, which is correct
        by self-stabilization on the NEW graph), and the boundary maps
        are re-pointed at the patched graph."""
        if graph is not None:
            self.graph = graph
        self.msg_log.clear()
        vals = np.asarray(state.values)
        act = np.asarray(state.active)
        cur = np.asarray(state.cursor)
        aux = np.asarray(state.aux) if state.aux is not None else None
        cl = np.asarray(clock) if clock is not None else None
        for p in range(self.graph.num_shards):
            self.ckpt[p] = (vals[p].copy(), act[p].copy(), cur[p].copy(),
                            aux[p].copy() if aux is not None else None)
            self.ckpt_tick[p] = t
            if cl is not None:
                self.ckpt_clock[p] = int(cl[p])

    # ------------------------------------------------------------------
    def maybe_fail(self, t: int, state: EngineState, plan: FaultPlan,
                   clock=None):
        """``clock`` (async runs): the current per-shard logical clock
        vector.  When given, ``extra["clock"]`` carries the post-recovery
        vector — a replayed shard rolls back to ITS recorded clock entry
        (the other shards keep theirs: the cut is a vector), a global
        checkpoint restore rolls the whole vector back to the snapshot's."""
        if self._schedule is None:
            self._schedule = plan.schedule(self.graph.num_shards)
        shards = self._schedule.get(t, [])
        extra = {"failures": 0, "replayed": 0}
        new_clock = None
        if clock is not None:  # the async step's clock vector, every tick
            with trace.span(trace.SYNC, pulls=1):
                new_clock = np.asarray(clock).copy()
        if not shards:
            return state, extra
        with trace.span(trace.KILL) as span:
            self.kill_pulled = {"pulls": 0, "bytes": 0}
            for p in shards:
                state, replayed = self.fail_shard(t, state, p)
                extra["failures"] += 1
                extra["replayed"] += replayed
                if new_clock is not None:
                    if self.recovery == "checkpoint":
                        for q in range(self.graph.num_shards):
                            new_clock[q] = self.ckpt_clock.get(q, 0)
                    else:
                        new_clock[p] = self.ckpt_clock.get(p, 0)
            if new_clock is not None:
                extra["clock"] = jnp.asarray(new_clock, jnp.int32)
            span.set_metadata(replayed=extra["replayed"], **self.kill_pulled)
        return state, extra

    def fail_shard(self, t: int, state: EngineState, p: int
                   ) -> tuple[EngineState, int]:
        """Kill shard p: wipe its state, restore from its checkpoint, replay
        peer messages (or boundary re-activation beyond the log horizon).

        Non-self-stabilizing programs skip all of that: both replay and
        boundary re-activation hand the shard duplicated messages, which
        only an idempotent reduce tolerates — they take the global
        checkpoint-restore path instead."""
        if self.recovery == "checkpoint":
            return self._global_restore(state), 0
        self._count_pulls(state.values, state.active, state.cursor)
        values = np.asarray(state.values).copy()
        active = np.asarray(state.active).copy()
        cursor = np.asarray(state.cursor).copy()

        # (2) recover own state from the last committed snapshot
        if p in self.ckpt:
            v, a, c, _ = self.ckpt[p]
            values[p], active[p], cursor[p] = v, a, c
            since = int(self.ckpt_tick[p])
        else:  # no checkpoint yet -> re-init this shard
            gids = np.arange(p * self.graph.vs, (p + 1) * self.graph.vs,
                             dtype=np.int64)
            valid = gids < self.graph.num_real_vertices
            v0, a0 = self.prog.init(jnp.asarray(gids, jnp.int32),
                                    jnp.asarray(valid))
            self._count_pulls(v0, a0)
            values[p], active[p] = np.asarray(v0), np.asarray(a0)
            cursor[p] = 0
            since = -1

        # (3) request lost messages — every production tick whose
        # delivery could postdate the snapshot (replay_slack covers
        # messages that were still in flight at checkpoint time)
        replayed = 0
        lost = [tt for tt in range(max(since + 1 - self.replay_slack, 0),
                                   t + 1)]
        if lost and all(tt in self.msg_log for tt in lost):
            for tt in lost:
                sv, si = self.msg_log[tt]
                # peers re-send everything they produced for shard p at tt
                vals_in = sv[:, p, :].reshape(-1)  # [P*cap]
                ids_in = si[:, p, :].reshape(-1)
                valid = ids_in >= 0
                replayed += int(valid.sum())
                improves = self.prog.aggregator.improves
                for i in np.nonzero(valid)[0]:
                    j = int(ids_in[i])
                    if improves(vals_in[i], values[p, j]):
                        values[p, j] = vals_in[i]
                        active[p, j] = True
                        cursor[p, j] = 0
        else:
            # log horizon exceeded: self-stabilizing fallback — peers
            # re-activate every vertex with an edge into shard p
            for q in range(self.graph.num_shards):
                if q == p:
                    continue
                b = self.graph.boundary[q, p]
                active[q] |= b
                cursor[q] = np.where(b, 0, cursor[q])
        # replay recovery is refused for non-idempotent programs, so aux
        # (push-mode only) can simply pass through here
        return EngineState(jnp.asarray(values), jnp.asarray(active),
                           jnp.asarray(cursor), state.tick,
                           state.aux), replayed

    def _count_pulls(self, *arrays) -> None:
        for k, v in trace.pulled(*arrays).items():
            self.kill_pulled[k] += v

    # ------------------------------------------------------------------
    def _global_restore(self, state: EngineState) -> EngineState:
        """BSP-style recovery for non-idempotent programs: EVERY shard
        rolls back to the last (globally consistent) snapshot — snapshots
        are taken between host-loop ticks, so for the immediate-delivery
        transports no messages are in flight at the restore point.  Under
        deferred delivery that premise fails: the caller must restore the
        DelayRing AND the device tick (which keys the ring slots) from
        the same snapshot instant, as ``run_to_convergence``'s crowded
        loop does — restoring state alone would drop parked messages
        whose senders' cursors have already advanced.  With no snapshot
        yet, re-initialize the run."""
        if not self.ckpt:
            return init_state(self.prog, self.graph)._replace(tick=state.tick)
        P_ = self.graph.num_shards
        values = np.stack([self.ckpt[p][0] for p in range(P_)])
        active = np.stack([self.ckpt[p][1] for p in range(P_)])
        cursor = np.stack([self.ckpt[p][2] for p in range(P_)])
        # the push-mode sidecar (residual + latched mass) is program
        # state: restoring values without it would both lose and
        # double-count mass
        aux = (jnp.asarray(np.stack([self.ckpt[p][3] for p in range(P_)]))
               if self.ckpt[0][3] is not None else None)
        return EngineState(jnp.asarray(values), jnp.asarray(active),
                           jnp.asarray(cursor), state.tick, aux)
