"""Crowded-cluster emulation (paper §5.4): the dist.latency profiles, the
exchange substrate's deferred-delivery ring (local + dist transports),
budget throttling, straggler-aware scheduling, and slowdown injection —
plus the self-stabilization property harness parameterized over latency
profiles: delayed/reordered delivery must not change the fixpoint for any
registered program.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # hermetic envs: deterministic seed-grid fallback
    from _propshim import given, settings, strategies as st

from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import GraphConfig
from repro.core import engine as E
from repro.core import graph as G
from repro.core import merger
from repro.core import programs as PR
from repro.core.faults import FaultPlan, apply_slowdown, max_injected_delay
from repro.dist import exchange as X
from repro.dist import latency as L
from jax import shard_map

from conftest import csr_edges

PROFILES = ("uniform", "stragglers", "heavy_tail")


def _cfg(algorithm, **overrides):
    base = dict(name="t", algorithm=algorithm, num_vertices=512,
                avg_degree=5, generator="rmat", num_shards=4,
                enforce_fraction=0.5,
                weighted=(algorithm in ("sssp", "widest_path")))
    base.update(overrides)
    return GraphConfig(**base)


def _run(cfg, graph=None, **kw):
    graph = graph or G.build_sharded_graph(cfg)
    state, totals = E.run_to_convergence(cfg, graph=graph, **kw)
    out = merger.extract(state, graph, kw.get("prog") or PR.get_program(cfg))
    return graph, out, totals


# ======================================================================
class TestLatencyModel:
    def test_deterministic_and_seeded(self):
        a = L.make_latency_model("stragglers", 8, slow_fraction=0.5, seed=3)
        b = L.make_latency_model("stragglers", 8, slow_fraction=0.5, seed=3)
        c = L.make_latency_model("stragglers", 8, slow_fraction=0.5, seed=4)
        np.testing.assert_array_equal(a.delays, b.delays)
        np.testing.assert_array_equal(a.throttle, b.throttle)
        assert not (a.slow_mask == c.slow_mask).all()

    def test_profile_shapes(self):
        none = L.make_latency_model("none", 4)
        assert none.max_delay == 0 and (none.throttle == 1).all()
        uni = L.make_latency_model("uniform", 4, link_delay=3)
        assert (uni.delays == 3).all() and (uni.throttle == 1).all()
        strag = L.make_latency_model("stragglers", 8, slow_fraction=0.5,
                                     link_delay=2, intensity=4)
        assert int(strag.slow_mask.sum()) == 4
        # slow senders delay ALL their outgoing links; healthy ones none
        assert (strag.delays[strag.slow_mask] == 2).all()
        assert (strag.delays[~strag.slow_mask] == 0).all()
        assert (strag.throttle[strag.slow_mask] == 4).all()
        ht = L.make_latency_model("heavy_tail", 64, intensity=5, seed=1)
        assert ht.slow_mask.any() and not ht.slow_mask.all()
        assert ht.throttle.max() <= 6 and ht.throttle.min() == 1

    def test_unknown_profile_raises(self):
        with pytest.raises(ValueError):
            L.make_latency_model("bursty", 4)

    def test_from_config(self):
        cfg = _cfg("cc", latency_profile="stragglers", slow_fraction=0.25,
                   link_delay=5, slow_intensity=2, latency_seed=9)
        m = L.from_config(cfg)
        assert m.profile == "stragglers" and m.max_delay == 5
        assert int(m.slow_mask.sum()) == 1  # 0.25 * 4 shards


# ======================================================================
class TestDelayedExchange:
    def _codec(self):
        return X.make_wire_codec(num_shards=1, capacity=8, vs=64,
                                 requested="none", value_kind="int32",
                                 identity=2 ** 31 - 1)

    def test_message_arrives_exactly_delay_ticks_later(self):
        codec = self._codec()
        inf = 2 ** 31 - 1
        ring = X.init_delay_ring(3, 1, 1, 8, inf, jnp.int32)
        delays = jnp.asarray([[2]], jnp.int32)
        arrivals = {}
        for t in range(6):
            sv = jnp.full((1, 1, 8), inf, jnp.int32)
            si = jnp.full((1, 1, 8), -1, jnp.int32)
            if t == 0:  # one message, sent only at t=0
                sv = sv.at[0, 0, 0].set(42)
                si = si.at[0, 0, 0].set(7)
            rv, ri, ring, pending = X.exchange_local_delayed(
                codec, ring, sv, si, jnp.int32(t), delays, inf)
            got = np.asarray(ri[0])[np.asarray(ri[0]) >= 0]
            arrivals[t] = (got.tolist(), int(pending))
        assert arrivals[0] == ([], 1)  # in flight
        assert arrivals[1] == ([], 1)
        assert arrivals[2][0] == [7]  # delivered at t_send + delay
        assert arrivals[2][1] == 0
        assert arrivals[3] == ([], 0)  # delivered once, not re-delivered

    def test_zero_delay_matches_immediate_transport(self):
        """A drained ring with an all-zero delay matrix must deliver the
        same rows (padded with empties) as the immediate exchange."""
        codec = X.make_wire_codec(num_shards=2, capacity=4, vs=32,
                                  requested="int16", value_kind="int32",
                                  identity=2 ** 31 - 1, max_int_value=32,
                                  idempotent=True)
        inf = 2 ** 31 - 1
        rng = np.random.default_rng(0)
        sv = jnp.asarray(rng.integers(0, 32, (2, 2, 4)), jnp.int32)
        si = jnp.asarray(rng.integers(0, 32, (2, 2, 4)), jnp.int32)
        ring = X.init_delay_ring(2, 2, 2, 4, inf, jnp.int32)
        delays = jnp.zeros((2, 2), jnp.int32)
        rv, ri, ring, pending = X.exchange_local_delayed(
            codec, ring, sv, si, jnp.int32(0), delays, inf)
        iv, ii = X.exchange_local(codec, sv, si)
        assert int(pending) == 0
        # ring rows: l * P + p; slot 0 carries this tick's sends
        np.testing.assert_array_equal(np.asarray(rv[:, :2]), np.asarray(iv))
        np.testing.assert_array_equal(np.asarray(ri[:, :2]), np.asarray(ii))
        assert (np.asarray(ri[:, 2:]) == -1).all()  # other slots empty

    def test_local_and_dist_delayed_transports_agree(self):
        """Same codec, same delays, both delayed transports, bit-identical
        delivery tick by tick (1-device mesh)."""
        codec = X.make_wire_codec(num_shards=1, capacity=8, vs=64,
                                  requested="int16", value_kind="int32",
                                  identity=2 ** 31 - 1, max_int_value=64,
                                  idempotent=True)
        inf = 2 ** 31 - 1
        ring_l = X.init_delay_ring(2, 1, 1, 8, inf, jnp.int32)
        ring_d = X.init_delay_ring(2, 0, 1, 8, inf, jnp.int32)
        mesh = Mesh(np.array(jax.devices()[:1]), ("workers",))
        delays = jnp.asarray([[2]], jnp.int32)
        for t in range(5):
            sv = jnp.full((1, 1, 8), inf, jnp.int32).at[0, 0, 0].set(10 + t)
            si = jnp.full((1, 1, 8), -1, jnp.int32).at[0, 0, 0].set(t % 8)

            lv, li, ring_l, pl = X.exchange_local_delayed(
                codec, ring_l, sv, si, jnp.int32(t), delays, inf)

            def f(rv, ri, rd, v, i):
                dv, di, ring, pend = X.exchange_dist_delayed(
                    codec, X.DelayRing(rv[0], ri[0], rd[0]), v[0], i[0],
                    jnp.int32(t), delays[0], "workers", inf)
                return (dv, di, ring.vals[None], ring.ids[None],
                        ring.due[None], pend)

            dv, di, rv_, ri_, rd_, pd = jax.jit(shard_map(
                f, mesh=mesh, in_specs=(P(), P(), P(), P(), P()),
                out_specs=P(), check_vma=False))(
                ring_d.vals[None], ring_d.ids[None], ring_d.due[None],
                sv, si)
            ring_d = X.DelayRing(rv_[0], ri_[0], rd_[0])
            np.testing.assert_array_equal(np.asarray(lv[0]), np.asarray(dv))
            np.testing.assert_array_equal(np.asarray(li[0]), np.asarray(di))
            assert int(pl) == int(pd)


# ======================================================================
class TestCrowdedFixpoints:
    """§3.3 under emulated crowding: delayed + reordered delivery (and
    throttled budgets) must leave the fixpoint bit-identical to the
    zero-latency run for every idempotent program x EVERY profile.  The
    non-idempotent pagerank (float SUM) has no bitwise claim — reordered
    (+) moves low bits — but delivery through the ring is exactly-once,
    so the fixpoint stays inside the push_eps error ball."""

    @settings(max_examples=8, deadline=None)
    @given(st.sampled_from(sorted(PR.PROGRAMS)),
           st.sampled_from(PROFILES), st.integers(0, 10))
    def test_fixpoint_invariant_under_latency(self, name, profile, seed):
        small = ({"num_vertices": 256, "avg_degree": 4}
                 if name == "pagerank" else {})
        cfg = _cfg(name, seed=seed, **small)
        g = G.build_sharded_graph(cfg)
        prog = PR.get_program(cfg)
        _, base, t0 = _run(cfg, graph=g)
        assert t0["converged"]
        lat = L.make_latency_model(profile, cfg.num_shards,
                                   slow_fraction=0.5, link_delay=3,
                                   intensity=3, seed=seed)
        _, out, tot = _run(cfg, graph=g, latency=lat)
        assert tot["converged"] and tot["pending"] == 0, (name, profile)
        if prog.aggregator.idempotent:
            np.testing.assert_array_equal(out, base)
        else:
            n = g.num_real_vertices
            l1 = float(np.abs(out.astype(np.float64) / n
                              - base.astype(np.float64) / n).sum())
            assert l1 < 2 * prog.push_eps / (1 - 0.85), (profile, l1)

    def test_ring_defers_then_drains(self):
        """Uniform link delay: messages visibly queue in the ring
        (pending > 0 mid-run) and the run only reports convergence once
        the ring has drained."""
        cfg = _cfg("cc")
        g = G.build_sharded_graph(cfg)
        lat = L.make_latency_model("uniform", cfg.num_shards, link_delay=3)
        _, out, tot = _run(cfg, graph=g, latency=lat, collect_log=True)
        assert tot["converged"] and tot["pending"] == 0
        assert max(e["pending"] for e in tot["log"]) > 0
        oracle = G.cc_oracle(g.num_real_vertices, csr_edges(g))
        assert (out == oracle).all()

    def test_crowded_log_reports_per_shard_work(self):
        cfg = _cfg("cc", latency_profile="stragglers")
        g = G.build_sharded_graph(cfg)
        _, _, tot = _run(cfg, graph=g, collect_log=True)
        assert tot["converged"]
        assert all(len(e["shard_work"]) == cfg.num_shards
                   for e in tot["log"])
        assert sum(sum(e["shard_work"]) for e in tot["log"]) > 0


# ======================================================================
class TestSlowdownInjection:
    def test_window_semantics(self):
        plan = FaultPlan(fail_fraction=0.0, slow_fraction=0.5, slow_delay=3,
                         slow_intensity=4, slow_start=2, slow_stop=6)
        base_d = np.zeros((4, 4), np.int32)
        base_t = np.ones((4,), np.int32)
        assert max_injected_delay(plan) == 3
        assert max_injected_delay(None) == 0
        d, t = apply_slowdown(plan, 1, base_d, base_t)
        assert (d == 0).all() and (t == 1).all()  # before the window
        d, t = apply_slowdown(plan, 3, base_d, base_t)
        slow = plan.slow_shards(4)
        assert len(slow) == 2
        for p in slow:
            assert (d[p, :] == 3).all() and t[p] == 4
        assert (base_d == 0).all()  # base untouched (copy-on-write)
        d, t = apply_slowdown(plan, 6, base_d, base_t)
        assert (d == 0).all() and (t == 1).all()  # after the window

    def test_overlay_never_lowers_base_condition(self):
        plan = FaultPlan(fail_fraction=0.0, slow_fraction=1.0, slow_delay=1,
                         slow_intensity=2, slow_start=0)
        base_d = np.full((4, 4), 2, np.int32)
        base_t = np.full((4,), 3, np.int32)
        d, t = apply_slowdown(plan, 0, base_d, base_t)
        assert (d == 2).all() and (t == 3).all()  # max(base, injected)

    def test_overlay_cache_tracks_plan_mutation(self):
        """Regression: the overlay cache used to be keyed only on the
        base arrays' identities, so mutating a plan's slow_delay /
        slow_fraction / slow_intensity between runs served the stale
        overlay of the old field values."""
        plan = FaultPlan(fail_fraction=0.0, slow_fraction=1.0, slow_delay=2,
                         slow_intensity=3, slow_start=0)
        base_d = np.zeros((4, 4), np.int32)
        base_t = np.ones((4,), np.int32)
        d, t = apply_slowdown(plan, 0, base_d, base_t)
        assert (d == 2).all() and (t == 3).all()
        plan.slow_delay, plan.slow_intensity = 5, 7
        d, t = apply_slowdown(plan, 0, base_d, base_t)
        assert (d == 5).all() and (t == 7).all()  # not the stale overlay
        plan.slow_fraction = 0.5
        d, t = apply_slowdown(plan, 0, base_d, base_t)
        assert (d == 5).any() and (d == 0).any()  # re-seeded shard choice
        # and the identity fast path still caches: same plan, same bases
        d2, t2 = apply_slowdown(plan, 1, base_d, base_t)
        assert d2 is d and t2 is t

    def test_slowdown_alone_converges_to_exact_fixpoint(self):
        """A slowdown-only plan (no kills) crowds half the shards mid-run;
        the run must converge to the oracle with zero failures."""
        cfg = _cfg("cc")
        g = G.build_sharded_graph(cfg)
        oracle = G.cc_oracle(g.num_real_vertices, csr_edges(g))
        plan = FaultPlan(fail_fraction=0.0, slow_fraction=0.5, slow_delay=2,
                         slow_intensity=3, slow_start=2, slow_stop=20)
        _, out, tot = _run(cfg, graph=g, fault_plan=plan)
        assert tot["converged"] and tot["failures"] == 0
        assert (out == oracle).all()

    def test_throttle_only_slowdown_is_not_a_noop(self):
        """A plan with slow_intensity but slow_delay=0 must still route
        onto the crowded tick and actually throttle (regression: the
        crowded gate used to look only at the injected wire delay)."""
        cfg = _cfg("cc", enforce_fraction=1.0, edge_budget=128)
        g = G.build_sharded_graph(cfg)
        oracle = G.cc_oracle(g.num_real_vertices, csr_edges(g))
        _, base, t0 = _run(cfg, graph=g)
        plan = FaultPlan(fail_fraction=0.0, slow_fraction=0.5,
                         slow_delay=0, slow_intensity=8, slow_start=0)
        _, out, tot = _run(cfg, graph=g, fault_plan=plan)
        assert tot["converged"]
        assert tot["ticks"] > t0["ticks"]  # the throttle bit
        assert (out == oracle).all() and (out == base).all()

    def test_checkpoint_restore_snapshots_inflight_ring(self):
        """self_stabilizing=False + latency + kills: global restore must
        roll back to a consistent cut INCLUDING the delay ring (parked
        messages are never re-sent — their senders' cursors advanced),
        and still reach the exact fixpoint with zero replays."""
        cfg = _cfg("cc", num_shards=8, checkpoint_every=3,
                   replay_log_ticks=32)
        g = G.build_sharded_graph(cfg)
        oracle = G.cc_oracle(g.num_real_vertices, csr_edges(g))
        prog = dataclasses.replace(PR.get_program(cfg),
                                   self_stabilizing=False)
        lat = L.make_latency_model("stragglers", 8, slow_fraction=0.5,
                                   link_delay=3, intensity=2, seed=4)
        plan = FaultPlan(fail_fraction=0.5, start_tick=4, every=4, seed=1)
        state, tot = E.run_to_convergence(cfg, graph=g, prog=prog,
                                          latency=lat, fault_plan=plan)
        assert tot["failures"] >= 1
        assert tot["replayed"] == 0  # replay rejected -> global restore
        assert tot["converged"] and tot["pending"] == 0
        out = merger.extract(state, g, prog)
        assert (out == oracle).all()

    def test_replay_covers_messages_in_flight_at_checkpoint(self):
        """Regression: a message produced BEFORE a shard's checkpoint but
        delivered AFTER it (deferred delivery) is in neither the snapshot
        nor the naive since+1..t replay range — the replay window must
        reach back by the max link delay.  The shipped crowded config's
        reduced variant reproduced the lost improvement (one vertex
        converged to the wrong CC label)."""
        from repro.configs import get_graph_config
        cfg = get_graph_config("asymp_cc_crowded").reduced()
        g = G.build_sharded_graph(cfg)
        oracle = G.cc_oracle(g.num_real_vertices, csr_edges(g))
        for frac in (0.5, 1.0):
            plan = FaultPlan(fail_fraction=frac, start_tick=4, every=6)
            _, out, tot = _run(cfg, graph=g, fault_plan=plan)
            assert tot["converged"] and tot["failures"] >= 2
            assert tot["replayed"] > 0
            assert (out == oracle).all(), frac

    def test_slowdown_composes_with_midrun_replay(self):
        """The satellite scenario: slowdown injection AND a mid-run kill
        recovered by replay, in one plan, on top of a latency profile —
        fixpoint still exact."""
        cfg = _cfg("cc", num_shards=8, checkpoint_every=3,
                   replay_log_ticks=16)
        g = G.build_sharded_graph(cfg)
        oracle = G.cc_oracle(g.num_real_vertices, csr_edges(g))
        lat = L.make_latency_model("stragglers", 8, slow_fraction=0.25,
                                   link_delay=2, intensity=2, seed=5)
        plan = FaultPlan(fail_fraction=0.25, start_tick=5, every=4, seed=2,
                         slow_fraction=0.5, slow_delay=3, slow_intensity=4,
                         slow_start=2, slow_stop=14)
        _, out, tot = _run(cfg, graph=g, latency=lat, fault_plan=plan)
        assert tot["failures"] >= 1
        assert tot["replayed"] > 0  # recovery went through replay
        assert tot["converged"] and tot["pending"] == 0
        assert (out == oracle).all()


# ======================================================================
class TestStragglerScheduler:
    def _phase1_setup(self, demote_penalty=8):
        prog = PR.get_program("cc")
        ep = E.EngineParams(
            num_shards=1, vs=4, max_vertices_per_tick=1, degree_window=2,
            route_capacity=4, enforce_fraction=1.0, priority="disabled",
            priority_scale=4.0, straggler_demote=demote_penalty)
        # every vertex has one edge to vertex 0
        row_ptr = jnp.asarray([0, 1, 2, 3, 4], jnp.int32)
        col_idx = jnp.zeros((4,), jnp.int32)
        values = jnp.asarray([3, 2, 1, 0], jnp.int32)
        cursor = jnp.zeros((4,), jnp.int32)
        return prog, ep, values, cursor, row_ptr, col_idx

    def test_demoted_vertex_yields_selection_slot(self):
        prog, ep, values, cursor, row_ptr, col_idx = self._phase1_setup()
        active = jnp.asarray([True, True, False, False])
        # without demotion, index order picks vertex 0 (it goes inactive)
        a0, *_ = E._phase1_create(prog, ep, values, active, cursor, row_ptr,
                                  col_idx, None, 0)
        assert not bool(a0[0]) and bool(a0[1])
        # demoting vertex 0 hands the only slot to vertex 1
        dem = jnp.asarray([True, False, False, False])
        a1, *_ = E._phase1_create(prog, ep, values, active, cursor, row_ptr,
                                  col_idx, None, 0, demote=dem)
        assert bool(a1[0]) and not bool(a1[1])

    def test_demoted_vertex_not_starved(self):
        """When only demoted work remains, the threshold machinery still
        selects it (demotion reorders, never drops)."""
        prog, ep, values, cursor, row_ptr, col_idx = self._phase1_setup()
        active = jnp.asarray([True, False, False, False])
        dem = jnp.asarray([True, False, False, False])
        a, *_ = E._phase1_create(prog, ep, values, active, cursor, row_ptr,
                                 col_idx, None, 0, demote=dem)
        assert not bool(a[0])  # selected and completed despite demotion

    def test_throttle_caps_per_tick_budget(self):
        prog, ep, values, cursor, row_ptr, col_idx = self._phase1_setup()
        ep = dataclasses.replace(ep, max_vertices_per_tick=4)
        active = jnp.asarray([True, True, True, True])
        a_fast, *_ = E._phase1_create(prog, ep, values, active, cursor,
                                      row_ptr, col_idx, None, 0,
                                      throttle=jnp.int32(1))
        a_slow, *_ = E._phase1_create(prog, ep, values, active, cursor,
                                      row_ptr, col_idx, None, 0,
                                      throttle=jnp.int32(4))
        assert int(jnp.sum(~a_fast)) == 4  # full budget: all 4 drain
        assert int(jnp.sum(~a_slow)) == 1  # throttled to 4 // 4 = 1

    def test_demote_mask_marks_only_slow_link_improvements(self):
        """_demote_row: improved-and-slow-targeted only."""
        from repro.core.semiring import MIN
        ep = E.EngineParams(
            num_shards=2, vs=4, max_vertices_per_tick=2, degree_window=2,
            route_capacity=2, enforce_fraction=1.0, priority="log",
            priority_scale=4.0, straggler_demote=8)
        old = jnp.asarray([5, 5, 5, 5], jnp.int32)
        new = jnp.asarray([1, 5, 2, 5], jnp.int32)  # 0 and 2 improved
        # two receive rows: row 0 slow (targets vertex 0), row 1 fast
        # (targets vertex 2)
        recv_ids = jnp.asarray([[0, -1], [2, -1]], jnp.int32)
        slow_row = jnp.asarray([True, False])
        dem = E._demote_row(MIN, ep, new, old, recv_ids, slow_row)
        assert dem.tolist() == [True, False, False, False]


# ======================================================================
class TestCrowdedDistTick:
    def test_dist_matches_local_on_one_worker_mesh(self):
        """The shard_map crowded tick (sender-side ring + all_to_all)
        must track the local crowded tick bit-for-bit, including the
        delay ring and throttled budgets."""
        cfg = GraphConfig(name="t", algorithm="cc", num_vertices=128,
                          avg_degree=4, generator="rmat", num_shards=1,
                          enforce_fraction=1.0)
        g = G.build_sharded_graph(cfg)
        prog = PR.get_program(cfg)
        ep = E.default_params(cfg, g, prog)
        dg = E.to_device_graph(g)
        mesh = Mesh(np.array(jax.devices()[:1]), ("workers",))
        delays = jnp.asarray([[1]], jnp.int32)
        throttle = jnp.asarray([2], jnp.int32)
        tick_l = E.make_crowded_tick(prog, ep, prog.weighted)
        cs_l = E.init_crowded_state(prog, ep, g, 1)
        tick_d = E.make_crowded_dist_tick(prog, ep, mesh, prog.weighted)
        cs_d = E.init_crowded_dist_state(prog, ep, g, 1)
        done = False
        for _ in range(200):
            cs_l, st_l, _ = tick_l(cs_l, dg, delays, throttle)
            cs_d, st_d, pend_d = tick_d(cs_d, dg, delays, throttle)
            np.testing.assert_array_equal(np.asarray(cs_l.core.values),
                                          np.asarray(cs_d.core.values))
            np.testing.assert_array_equal(np.asarray(cs_l.core.active),
                                          np.asarray(cs_d.core.active))
            assert int(st_l.pending) == int(pend_d)
            if int(st_l.base.active) == 0 and int(st_l.pending) == 0:
                done = True
                break
        assert done
        oracle = G.cc_oracle(g.num_real_vertices, csr_edges(g))
        out = np.asarray(cs_l.core.values).reshape(-1)[:g.num_real_vertices]
        assert (out == oracle).all()


    def test_crowded_dryrun_lowers(self):
        """lower_tick_for_mesh generalizes to the crowded pytree (ring +
        demote + replicated delays/throttle) without real allocation —
        the structural gate behind --graph asymp_cc_crowded_prod."""
        cfg = _cfg("cc", num_shards=1, latency_profile="stragglers",
                   link_delay=2, slow_fraction=1.0, slow_intensity=4)
        mesh2d = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                      ("a", "b"))
        compiled, info = E.lower_tick_for_mesh(cfg, mesh2d, 1)
        assert compiled is not None
        assert info["latency_profile"] == "stragglers"
        assert info["ring_slots"] >= cfg.link_delay + 1
        # the plain sync lowering must remain latency-free
        cfg_plain = _cfg("cc", num_shards=1)
        _, info_plain = E.lower_tick_for_mesh(cfg_plain, mesh2d, 1)
        assert "ring_slots" not in info_plain
