"""Exactly-once SUM aggregation: the push-mode ``pagerank`` program.

The first genuinely non-idempotent workload — what it must prove:

  * the residual-push fixpoint matches the dense pull-mode oracle
    (kernels/ops.pagerank, absorb-dangling convention) within 1e-3 L1
    and conserves probability mass within 1e-5;
  * the SAME verdict holds under a 50% kill plan (checkpoint-restore
    recovery — replay refused), under every latency profile (deferred
    delivery), and under route-capacity starvation (backpressure
    retries), because delivery is exactly-once end to end;
  * the per-tick mass invariant — including mass latched mid-push and
    the absorbed dangling leak — holds at EVERY tick boundary, which is
    the sharp witness that the bounded-queue retry never re-ships a
    delivered message (the pre-fix engine violates it by ~0.9 within 60
    starved ticks);
  * the wire gate refuses every lossy mode for non-idempotent
    aggregators, and the dry-run derives the same EngineParams as
    production.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.configs.base import GraphConfig
from repro.core import engine as E
from repro.core import graph as G
from repro.core import merger
from repro.core import programs as PR
from repro.core import semiring as SR
from repro.core.faults import FaultManager, FaultPlan
from repro.dist import exchange as ex_mod
from repro.dist import latency as L
from repro.kernels.ops import pagerank as dense_pagerank

DAMPING = 0.85
PUSH_EPS = 1e-5
# each run's L1 distance to the true fixpoint is bounded by
# push_eps / (1 - d); two runs are within twice that of each other
RUN_L1_BOUND = 2 * PUSH_EPS / (1 - DAMPING)


def _cfg(**overrides):
    base = dict(name="t-pr", algorithm="pagerank", num_vertices=512,
                avg_degree=5, generator="rmat", num_shards=4,
                enforce_fraction=0.5, checkpoint_every=4)
    base.update(overrides)
    return GraphConfig(**base)


# the normalized mass-balance invariant lives in the product (it is the
# run-integrity check the merger phase exposes); alias it for the tests
mass_balance = merger.mass_balance


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    g = G.build_sharded_graph(cfg)
    oracle = np.asarray(dense_pagerank(g, damping=DAMPING, iters=80,
                                       use_kernel=False, dangling="absorb"))
    return cfg, g, oracle


def _verdict(state, totals, g, oracle):
    """The acceptance checks shared by every scenario: oracle match,
    conservation, quiescence (no latched pushes at convergence)."""
    assert totals["converged"]
    n = g.num_real_vertices
    out = merger.extract(state, g, PR.pagerank())
    l1 = float(np.abs(out.astype(np.float64) / n - oracle).sum())
    assert l1 < 1e-3, f"L1 to oracle {l1:.2e}"
    assert abs(mass_balance(state, g) - 1.0) < 1e-5
    assert (np.asarray(state.aux[:, 1]) == 0).all()  # no push in flight
    assert (np.asarray(state.aux[:, 0]).reshape(-1)[:n] <= PUSH_EPS).all()
    return out


# ======================================================================
class TestFixpoint:
    def test_matches_dense_oracle_and_conserves_mass(self, setup):
        cfg, g, oracle = setup
        state, totals = E.run_to_convergence(cfg, graph=g)
        _verdict(state, totals, g, oracle)

    def test_oracle_normalization_cross_check(self, setup):
        """The absorb-dangling oracle itself: total mass = 1 minus the
        absorbed share, consistent with the engine's leak accounting."""
        cfg, g, oracle = setup
        redis = np.asarray(dense_pagerank(g, damping=DAMPING, iters=80,
                                          use_kernel=False,
                                          dangling="redistribute"))
        assert abs(redis.sum() - 1.0) < 1e-3  # classic convention
        assert oracle.sum() <= redis.sum() + 1e-6  # absorb leaks mass

    def test_reordering_moves_bits_not_the_verdict(self, setup):
        """Float (+) is commutative but not associative: different
        priority schedules reorder delivery and may move low bits —
        unlike the idempotent programs there is NO bitwise invariance,
        but every ordering stays within the push_eps error ball."""
        cfg, g, oracle = setup
        outs = []
        for priority, frac in [("log", 0.5), ("linear", 1.0)]:
            c = dataclasses.replace(cfg, priority=priority,
                                    enforce_fraction=frac)
            state, totals = E.run_to_convergence(c, graph=g)
            outs.append(_verdict(state, totals, g, oracle))
        n = g.num_real_vertices
        pair_l1 = float(np.abs(outs[0].astype(np.float64) / n
                               - outs[1].astype(np.float64) / n).sum())
        assert pair_l1 < RUN_L1_BOUND


# ======================================================================
class TestExactlyOnceUnderBackpressure:
    def test_mass_invariant_every_tick_with_starved_capacity(self, setup):
        """route_capacity=4 forces routing drops every tick; the cursor
        retries exactly the un-shipped suffix.  The per-tick mass
        invariant is the proof-by-test: one double-shipped (or lost)
        message moves it (the pre-fix engine, which kept edges past the
        first drop, violates it by ~0.9 within 60 such ticks)."""
        cfg, g, _ = setup
        cfg = dataclasses.replace(cfg, enforce_fraction=1.0)
        prog = PR.get_program(cfg)
        ep = dataclasses.replace(E.default_params(cfg, g, prog),
                                 route_capacity=4)
        tick = E.make_local_tick(prog, ep, prog.weighted)
        state = E.init_state(prog, g)
        dg = E.to_device_graph(g)
        sent = fetched = 0
        for _ in range(120):
            state, stats, _ = tick(state, dg)
            sent += int(stats.sent)
            fetched += int(stats.fetched)
            assert abs(mass_balance(state, g) - 1.0) < 1e-5
        assert fetched > sent  # drops really happened (edges re-fetched)

    def test_converges_to_oracle_with_small_capacity(self, setup):
        """A capacity small enough to overflow regularly, big enough to
        keep the priority order useful: full convergence, same verdict."""
        cfg, g, oracle = setup
        prog = PR.get_program(cfg)
        ep_roomy = E.default_params(cfg, g, prog)
        ep = dataclasses.replace(ep_roomy, route_capacity=48)
        assert ep.route_capacity < ep_roomy.route_capacity
        tick = E.make_local_tick(prog, ep, prog.weighted)
        state = E.init_state(prog, g)
        dg = E.to_device_graph(g)
        sent = fetched = 0
        converged = False
        for _ in range(30000):
            state, stats, _ = tick(state, dg)
            sent += int(stats.sent)
            fetched += int(stats.fetched)
            if int(stats.active) == 0:
                converged = True
                break
        assert fetched > sent  # backpressure was exercised
        _verdict(state, {"converged": converged}, g, oracle)


# ======================================================================
class TestCheckpointRestoreRecovery:
    def test_recovery_routed_to_checkpoint(self, setup):
        cfg, g, _ = setup
        prog = PR.get_program(cfg)
        ep = E.default_params(cfg, g, prog)
        assert FaultManager(cfg, g, prog, ep).recovery == "checkpoint"

    def test_kill50_same_verdict_bit_for_bit(self, setup):
        """50% rolling kills: recovery is the deterministic global
        rollback + re-execution, so the final fixpoint is not just
        within tolerance but BITWISE the fault-free one — and the
        checkpoint carried the aux planes (residual + latch), or mass
        would have been lost/double-counted."""
        cfg, g, oracle = setup
        cfg = dataclasses.replace(cfg, num_shards=8)
        g8 = G.build_sharded_graph(cfg)
        state0, totals0 = E.run_to_convergence(cfg, graph=g8)
        base = _verdict(state0, totals0, g8, oracle)
        plan = FaultPlan(fail_fraction=0.5, start_tick=4, every=6)
        state, totals = E.run_to_convergence(cfg, graph=g8, fault_plan=plan)
        assert totals["failures"] > 0
        assert totals["replayed"] == 0  # replay refused
        out = _verdict(state, totals, g8, oracle)
        np.testing.assert_array_equal(out, base)

    def test_restore_before_any_checkpoint_reinitializes_aux(self, setup):
        cfg, g, _ = setup
        cfg = dataclasses.replace(cfg, checkpoint_every=1000)
        prog = PR.get_program(cfg)
        ep = E.default_params(cfg, g, prog)
        mgr = FaultManager(cfg, g, prog, ep)
        tick = E.make_local_tick(prog, ep, prog.weighted)
        state0 = E.init_state(prog, g)
        state = state0
        dg = E.to_device_graph(g)
        for _ in range(3):
            state, _, _ = tick(state, dg)
        restored, replayed = mgr.fail_shard(2, state, 1)
        assert replayed == 0
        np.testing.assert_array_equal(np.asarray(restored.values),
                                      np.asarray(state0.values))
        np.testing.assert_array_equal(np.asarray(restored.aux),
                                      np.asarray(state0.aux))


# ======================================================================
class TestDeferredDelivery:
    @pytest.mark.parametrize("profile", ["uniform", "stragglers",
                                         "heavy_tail"])
    def test_same_verdict_under_latency_profile(self, setup, profile):
        """Messages parked in the delay ring are delivered exactly once
        (deliver-once retirement), so the verdict survives every
        emulated cluster condition; bits may move (float reorder)."""
        cfg, g, oracle = setup
        lat = L.make_latency_model(profile, cfg.num_shards,
                                   slow_fraction=0.5, link_delay=2,
                                   intensity=2, seed=1)
        state, totals = E.run_to_convergence(cfg, graph=g, latency=lat)
        assert totals["pending"] == 0
        _verdict(state, totals, g, oracle)

    def test_checkpoint_restore_composes_with_latency(self, setup):
        """Kills on top of a latency profile: the global restore rolls
        back to a consistent cut INCLUDING the delay ring and the aux
        planes; conservation still holds at convergence."""
        cfg, g, oracle = setup
        cfg = dataclasses.replace(cfg, num_shards=8)
        g8 = G.build_sharded_graph(cfg)
        lat = L.make_latency_model("stragglers", 8, slow_fraction=0.5,
                                   link_delay=2, intensity=2, seed=3)
        plan = FaultPlan(fail_fraction=0.5, start_tick=4, every=6)
        state, totals = E.run_to_convergence(cfg, graph=g8, latency=lat,
                                             fault_plan=plan)
        assert totals["failures"] > 0 and totals["replayed"] == 0
        assert totals["pending"] == 0
        _verdict(state, totals, g8, oracle)


# ======================================================================
class TestWireGate:
    @pytest.mark.parametrize("mode", ["int16", "int8"])
    def test_lossy_modes_gated_to_none(self, setup, mode):
        cfg, g, _ = setup
        ep = E.default_params(dataclasses.replace(cfg,
                                                  wire_compression=mode), g)
        assert ep.wire_compression == "none"

    def test_gate_is_aggregator_driven(self):
        # non-idempotent -> "none" regardless of payload kind or bound
        for kind in ("float32", "int32"):
            for mode in ("int8", "int16", "none"):
                assert ex_mod.effective_compression(
                    mode, kind, 100, idempotent=False) == "none"
        # control: the same requests pass for idempotent aggregators
        assert ex_mod.effective_compression(
            "int16", "float32", idempotent=True) == "int16"

    def test_typo_raises_value_error_naming_modes(self):
        with pytest.raises(ValueError, match="int16"):
            ex_mod.effective_compression("int12", "int32", 5)
        with pytest.raises(ValueError, match="wire_compression"):
            E.default_params(_cfg(num_vertices=256, wire_compression="zstd"),
                             G.build_sharded_graph(_cfg(num_vertices=256)))


# ======================================================================
class TestDistTick:
    def test_dist_matches_local_including_aux(self):
        """The tick on a mesh threads the aux planes; on a 1-worker mesh
        it must track the local tick bit-for-bit."""
        cfg = _cfg(num_vertices=128, avg_degree=4, num_shards=1,
                   enforce_fraction=1.0)
        g = G.build_sharded_graph(cfg)
        prog = PR.get_program(cfg)
        ep = E.default_params(cfg, g, prog)
        dg = E.to_device_graph(g)
        mesh = Mesh(np.array(jax.devices()[:1]), ("workers",))
        tick_l = E.make_local_tick(prog, ep, prog.weighted)
        tick_d = jax.jit(E.shard_local_tick(prog, ep, mesh, prog.weighted))
        sl = E.init_state(prog, g)
        sd = E.init_state(prog, g)
        for t in range(120):
            sl, stats, _ = tick_l(sl, dg)
            sd, stats_d, _ = tick_d(sd, dg)
            assert stats_d == stats
            if t % 20 == 0 or t == 119:
                np.testing.assert_array_equal(np.asarray(sl.values),
                                              np.asarray(sd.values))
                np.testing.assert_array_equal(np.asarray(sl.aux),
                                              np.asarray(sd.aux))
                np.testing.assert_array_equal(np.asarray(sl.active),
                                              np.asarray(sd.active))

    def test_dry_run_derives_production_params(self):
        """lower_tick_for_mesh goes through derive_params — the same
        derivation default_params uses — and lowers the aux-carrying
        tick with the SUM wire gating applied."""
        from repro.dist.sharding import vertex_partition
        cfg = _cfg(num_vertices=128, avg_degree=4, num_shards=1,
                   wire_compression="int16")
        mesh = Mesh(np.array(jax.devices()[:1]), ("workers",))
        compiled, info = E.lower_tick_for_mesh(cfg, mesh, 1)
        assert info["wire"] == "none"  # SUM gated the int16 request off
        prog = PR.get_program(cfg)
        vs = vertex_partition(cfg.num_vertices, 1).vs
        es = max(cfg.num_edges * 2 // 1, 1)
        ep = E.derive_params(dataclasses.replace(cfg, num_shards=1),
                             num_shards=1, vs=vs, es=es,
                             num_vertices=cfg.num_vertices, prog=prog)
        assert info["M"] == ep.max_vertices_per_tick
        assert info["cap"] == ep.route_capacity
        assert info["D"] == ep.degree_window


# ======================================================================
class TestElasticResize:
    def test_resize_mid_push_refused_quiescent_resize_allowed(self, setup):
        """Elastic repartition moves the aux planes channel-wise, but its
        cursor reset would re-ship a latched push's delivered prefix —
        the guard must refuse mid-push resizes loudly, and a quiescent
        (converged) state must move with mass intact."""
        from repro.ft.elastic import repartition_state
        cfg, g, _ = setup
        prog = PR.get_program(cfg)
        ep = E.default_params(cfg, g, prog)
        # force a mid-push state: starved capacity guarantees in-flight
        # latches within a few ticks
        ep_tiny = dataclasses.replace(ep, route_capacity=4)
        tick = E.make_local_tick(prog, ep_tiny, prog.weighted)
        state = E.init_state(prog, g)
        dg = E.to_device_graph(g)
        for _ in range(3):
            state, _, _ = tick(state, dg)
        assert (np.asarray(state.aux[:, 1]) != 0).any()
        cfg2 = dataclasses.replace(cfg, num_shards=2)
        g2 = G.build_sharded_graph(cfg2)
        with pytest.raises(ValueError, match="quiescent"):
            repartition_state(state, g, g2)
        # converged state: no latched pushes -> the move is legal
        done, totals = E.run_to_convergence(cfg, graph=g)
        moved = repartition_state(done, g, g2)
        assert moved.aux.shape == (2, 2, g2.vs)
        assert abs(mass_balance(moved, g2) - 1.0) < 1e-5


# ======================================================================
class TestSumAggregator:
    def test_registered_and_not_idempotent(self):
        assert SR.AGGREGATORS["sum"] is SR.SUM
        assert not SR.SUM.idempotent
        assert all(SR.AGGREGATORS[a].idempotent
                   for a in ("min", "max", "or"))
        assert SR.for_semiring("plus_times") is SR.SUM

    def test_scatter_accumulates(self):
        v = jnp.zeros((4,), jnp.float32)
        idx = jnp.asarray([1, 1, 3, 4])  # 4 = out of bounds -> dropped
        vals = jnp.asarray([1.0, 2.0, 5.0, 9.0], jnp.float32)
        out = SR.SUM.scatter(v, idx, vals)
        assert out.tolist() == [0.0, 3.0, 0.0, 5.0]

    def test_program_declares_non_self_stabilizing(self):
        prog = PR.get_program("pagerank")
        assert prog.aggregator is SR.SUM
        assert not prog.self_stabilizing
        assert prog.aux_channels == 2 and prog.init_aux is not None
        assert prog.push_eps > 0
