"""Trainer / optimizer / checkpoint / data-pipeline / compression tests."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data.pipeline import DataPipeline, SyntheticSource
from repro.dist import compression as C
from repro.dist.sharding import ShardingRules
from repro.ft.checkpoint import CheckpointManager
from repro.train import optimizer as opt_mod
from repro.train import trainer as TR


class TestTrainer:
    def test_loss_decreases(self):
        cfg = get_config("qwen3-4b").reduced()
        state, _ = TR.init_state(cfg, jax.random.PRNGKey(0))
        step = jax.jit(TR.make_train_step(cfg, lr=1e-3))
        pipe = DataPipeline(SyntheticSource(cfg.vocab_size, 32), 8)
        losses = []
        for _ in range(5):
            b = {k: jnp.asarray(v) for k, v in pipe.next_batch().items()}
            state, m = step(state, b)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]

    def test_microbatching_matches_full_batch(self):
        cfg = get_config("chatglm3-6b").reduced()
        key = jax.random.PRNGKey(1)
        state1, _ = TR.init_state(cfg, key)
        state2 = jax.tree.map(lambda x: x, state1)
        b = DataPipeline(SyntheticSource(cfg.vocab_size, 16), 8).next_batch()
        batch = {k: jnp.asarray(v) for k, v in b.items()}
        s1, m1 = jax.jit(TR.make_train_step(cfg, lr=1e-3))(state1, batch)
        s2, m2 = jax.jit(TR.make_train_step(cfg, lr=1e-3, microbatches=4)
                         )(state2, batch)
        for a, b_ in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b_, np.float32),
                                       rtol=0.05, atol=5e-3)

    def test_adafactor_converges(self):
        import dataclasses
        cfg = dataclasses.replace(get_config("qwen3-4b").reduced(),
                                  optimizer="adafactor")
        state, _ = TR.init_state(cfg, jax.random.PRNGKey(0))
        step = jax.jit(TR.make_train_step(cfg, lr=1e-2))
        pipe = DataPipeline(SyntheticSource(cfg.vocab_size, 32), 8)
        losses = []
        for _ in range(5):
            b = {k: jnp.asarray(v) for k, v in pipe.next_batch().items()}
            state, m = step(state, b)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]

    def test_adafactor_state_smaller_than_adam(self):
        cfg = get_config("chatglm3-6b").reduced()
        params, _ = TR.init_state(cfg, jax.random.PRNGKey(0))
        ad = opt_mod.AdamW().init(params.params)
        af = opt_mod.Adafactor().init(params.params)
        sz = lambda t: sum(x.size * x.dtype.itemsize
                           for x in jax.tree.leaves(t))
        assert sz(af) < 0.2 * sz(ad)


class TestCheckpoint:
    def test_roundtrip_and_retention(self):
        cfg = get_config("qwen3-4b").reduced()
        state, _ = TR.init_state(cfg, jax.random.PRNGKey(0))
        with tempfile.TemporaryDirectory() as d:
            cm = CheckpointManager(d, keep=2)
            for s in (1, 2, 3, 4):
                cm.save(s, state, metadata={"pipeline": {"offset": s * 8}})
            assert cm.all_steps() == [3, 4]  # retention
            restored, meta = cm.restore()
            assert meta["pipeline"]["offset"] == 32
            for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
                np.testing.assert_array_equal(np.asarray(a, np.float32),
                                              np.asarray(b, np.float32))

    def test_async_commit_is_atomic(self):
        cfg = get_config("qwen3-4b").reduced()
        state, _ = TR.init_state(cfg, jax.random.PRNGKey(0))
        with tempfile.TemporaryDirectory() as d:
            cm = CheckpointManager(d)
            cm.save(7, state, blocking=False)
            cm.wait()
            assert cm.latest_step() == 7
            # a partial dir without manifest must be invisible
            os.makedirs(os.path.join(d, "step_0000000009"))
            assert cm.latest_step() == 7

    def test_exact_batch_replay_after_restore(self):
        """ASYMP step 3 for training: pipeline offsets replay exactly."""
        src = SyntheticSource(1000, 16, seed=3)
        p1 = DataPipeline(src, 4)
        batches = [p1.next_batch() for _ in range(3)]
        snap = p1.snapshot()
        after = [p1.next_batch() for _ in range(2)]
        p2 = DataPipeline(src, 4)
        p2.restore(snap)
        replay = [p2.next_batch() for _ in range(2)]
        for a, b in zip(after, replay):
            np.testing.assert_array_equal(a["tokens"], b["tokens"])


class TestDataPipeline:
    def test_shards_are_disjoint_and_cover(self):
        src = SyntheticSource(1000, 8, seed=1)
        full = DataPipeline(src, 8).next_batch()["tokens"]
        parts = [DataPipeline(src, 8, shard_index=i, num_shards=4
                              ).next_batch()["tokens"] for i in range(4)]
        np.testing.assert_array_equal(np.concatenate(parts), full)

    def test_deterministic(self):
        a = DataPipeline(SyntheticSource(50, 8, seed=5), 4).next_batch()
        b = DataPipeline(SyntheticSource(50, 8, seed=5), 4).next_batch()
        np.testing.assert_array_equal(a["tokens"], b["tokens"])


class TestCompression:
    def test_quantize_roundtrip_error_bounded(self):
        g = jax.random.normal(jax.random.PRNGKey(0), (1000,)) * 0.01
        q, s = C.quantize_int8(g)
        back = C.dequantize_int8(q, s, g.shape, jnp.float32)
        rel = float(jnp.max(jnp.abs(back - g)) / jnp.max(jnp.abs(g)))
        assert rel < 1.0 / 100  # 127-level quantization

    def test_compressed_psum_matches_mean(self):
        """int8 EF all-reduce ~= exact mean; error feedback is carried."""
        devs = jax.devices()
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        mesh = Mesh(np.array(devs[:1]), ("d",))
        g = jax.random.normal(jax.random.PRNGKey(1), (512,)) * 0.1

        def f(g):
            out, err = C.compressed_psum(g, "d")
            return out, err

        out, err = jax.jit(shard_map(f, mesh=mesh, in_specs=P(),
                                     out_specs=P(), check_vma=False))(g)
        np.testing.assert_allclose(np.asarray(out), np.asarray(g),
                                   atol=2e-3)
        # error feedback must equal the quantization residual
        np.testing.assert_allclose(np.asarray(g - out), np.asarray(err),
                                   atol=1e-6)

    def test_trainer_int8_grad_exchange_still_learns(self):
        """The trainer's compressed gradient exchange (EF int8 round-trip
        per microbatch, residual carried) must not stop optimization."""
        cfg = get_config("qwen3-4b").reduced()
        state, _ = TR.init_state(cfg, jax.random.PRNGKey(0))
        step = jax.jit(TR.make_train_step(cfg, lr=1e-3, microbatches=2,
                                          grad_compression="int8"))
        pipe = DataPipeline(SyntheticSource(cfg.vocab_size, 32), 8)
        losses = []
        for _ in range(5):
            b = {k: jnp.asarray(v) for k, v in pipe.next_batch().items()}
            state, m = step(state, b)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]


class TestShardingRules:
    def test_divisibility_fallback(self):
        import jax.sharding as js
        devs = np.array(jax.devices()[:1])
        mesh = js.Mesh(devs.reshape(1, 1), ("data", "model"))
        rules = ShardingRules()
        spec = rules.resolve(mesh, ("batch", "heads", None), (4, 25, 64), "t")
        assert spec == js.PartitionSpec("data", "model", None)
        # heads=25 on model=1 divides; force indivisible via fake mesh shape
        spec2 = rules.resolve(mesh, (None, "kv_seq", None), (1, 7, 3), "t")
        assert spec2[1] == "model"  # 7 % 1 == 0

    def test_axis_used_once(self):
        import jax.sharding as js
        devs = np.array(jax.devices()[:1])
        mesh = js.Mesh(devs.reshape(1, 1), ("data", "model"))
        rules = ShardingRules()
        spec = rules.resolve(mesh, ("kv_seq", "kv_heads"), (8, 8), "t")
        # both want `model`; second must replicate
        assert spec == js.PartitionSpec("model", None)


class TestVertexPartition:
    def test_disjoint_deterministic_covering(self):
        from repro.dist.sharding import vertex_partition
        for n, p in [(1000, 8), (512, 4), (7, 3), (16, 16), (1, 1)]:
            part = vertex_partition(n, p)
            assert part == vertex_partition(n, p)  # deterministic
            ids = np.arange(n)
            owners = part.shard_of(ids)
            locals_ = part.local_of(ids)
            # covering + disjoint: every global id maps to exactly one
            # (shard, slot) and the flattened layout is the identity
            flat = owners * part.vs + locals_
            np.testing.assert_array_equal(flat, ids)
            assert owners.max() < part.num_shards
            assert part.padded_vertices >= n
            lo_hi = part.ranges()
            assert lo_hi[0, 0] == 0 and lo_hi[-1, 1] == n

    def test_matches_graph_builder_layout(self):
        from repro.configs.base import GraphConfig
        from repro.core.graph import build_sharded_graph
        from repro.dist.sharding import vertex_partition
        cfg = GraphConfig(name="t", algorithm="cc", num_vertices=100,
                          avg_degree=4, generator="er", num_shards=3)
        g = build_sharded_graph(cfg)
        part = vertex_partition(cfg.num_vertices, cfg.num_shards)
        assert (g.vs, g.num_vertices) == (part.vs, part.padded_vertices)


class TestExchange:
    """The unified exchange substrate: local/dist transports x wire codecs."""

    def test_compressed_mode_identical_cc_labels(self, rmat_cc_graph):
        """Acceptance: int16 wire vs raw wire on the RMAT test graph must
        produce bit-identical CC labels (the narrowing is lossless below
        the sentinel bound) while shipping ~2x fewer wire bytes."""
        import dataclasses
        from repro.core import engine as E, graph as G, merger, programs as PR
        from conftest import csr_edges

        cfg, g = rmat_cc_graph
        oracle = G.cc_oracle(g.num_real_vertices, csr_edges(g))
        outs, codecs = {}, {}
        for mode in ("none", "int16"):
            cfg_m = dataclasses.replace(cfg, wire_compression=mode)
            ep = E.default_params(cfg_m, g)
            assert ep.wire_compression == mode  # 1024 labels fit int16
            codecs[mode] = E.wire_codec(PR.get_program(cfg_m), ep)
            state, totals = E.run_to_convergence(cfg_m, graph=g)
            assert totals["converged"]
            outs[mode] = merger.extract(state, g, PR.get_program(cfg_m))
        assert (outs["none"] == oracle).all()
        assert (outs["int16"] == outs["none"]).all()
        raw_b = codecs["none"].wire_bytes_per_tick()
        comp_b = codecs["int16"].wire_bytes_per_tick()
        assert comp_b * 2 <= raw_b

    def test_unsafe_int_narrowing_gated_to_none(self):
        from repro.dist import exchange as X
        # 10^6 CC labels cannot ride int16 -> fall back to raw
        assert X.effective_compression("int16", "int32", 10 ** 6) == "none"
        # int8 request on a 10k-label graph degrades to int16, not none
        assert X.effective_compression("int8", "int32", 10 ** 4) == "int16"
        # float payloads always admit quantization (lossy-but-safe)...
        assert X.effective_compression("int8", "float32") == "int8"
        assert X.effective_compression("none", "int32", 5) == "none"
        # ...UNLESS the aggregator is non-idempotent: quantization error
        # compounds under (+), so every lossy mode gates to none
        assert X.effective_compression("int8", "float32",
                                       idempotent=False) == "none"
        assert X.effective_compression("int16", "int32", 5,
                                       idempotent=False) == "none"

    def test_unknown_wire_mode_raises_value_error(self):
        """A typo'd GraphConfig.wire_compression must not die with a
        bare AssertionError; the error names the valid modes."""
        import pytest
        from repro.dist import exchange as X
        with pytest.raises(ValueError, match="'none', 'int16', 'int8'"):
            X.effective_compression("int32", "int32", 5)
        with pytest.raises(ValueError):
            X.make_wire_codec(num_shards=2, capacity=4, vs=8,
                              requested="gzip", value_kind="int32",
                              identity=0, idempotent=True)

    def test_float_wire_never_underestimates(self):
        """Ceil-rounded quantization: decoded >= original (min-semiring
        safety), inf (identity) round-trips exactly."""
        from repro.dist import exchange as X
        key = jax.random.PRNGKey(2)
        vals = jax.random.uniform(key, (3, 5, 16), jnp.float32, 0.0, 50.0)
        vals = vals.at[:, :, -3:].set(jnp.inf)  # empty slots
        ids = jnp.where(jnp.isfinite(vals), 1, -1).astype(jnp.int32)
        for mode in ("int8", "int16"):
            codec = X.make_wire_codec(num_shards=5, capacity=16, vs=100,
                                      requested=mode, value_kind="float32",
                                      identity=float("inf"),
                                      idempotent=True)
            rv, ri = X.exchange_local(codec, vals, ids)
            ref = jnp.swapaxes(vals, 0, 1)
            assert bool(jnp.all(jnp.isinf(rv) == jnp.isinf(ref)))
            assert bool(jnp.all(rv >= ref - 1e-6))
            # error is bounded by one grid step of the per-row scale
            qmax = 126 if mode == "int8" else 32766
            err = jnp.where(jnp.isfinite(ref), rv - ref, 0.0)
            scale = jnp.max(jnp.where(jnp.isfinite(ref), ref, 0.0),
                            axis=-1, keepdims=True)
            assert float(jnp.max(err - scale / qmax)) <= 1e-5, mode

    def test_local_and_dist_transports_agree(self):
        """Same codec, one device or a mesh (the cross-device
        ``all_to_all`` step), bit-identical delivery."""
        import dataclasses
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.dist import exchange as X
        from jax import shard_map
        codec = X.make_wire_codec(num_shards=1, capacity=8, vs=64,
                                  requested="int16", value_kind="int32",
                                  identity=2 ** 31 - 1, max_int_value=64,
                                  idempotent=True)
        sv = jnp.full((1, 1, 8), 2 ** 31 - 1, jnp.int32
                      ).at[0, 0, :3].set(jnp.asarray([5, 63, 0]))
        si = jnp.full((1, 1, 8), -1, jnp.int32).at[0, 0, :3].set(
            jnp.asarray([1, 2, 3]))
        lv, li = X.exchange_local(codec, sv, si)
        mesh = Mesh(np.array(jax.devices()[:1]), ("workers",))
        on_mesh = dataclasses.replace(codec, axis_name="workers")
        f = lambda v, i: X.exchange_local(on_mesh, v, i)
        dv, di = jax.jit(shard_map(f, mesh=mesh, in_specs=P("workers"),
                                   out_specs=P("workers"),
                                   check_vma=False))(sv, si)
        assert dv.dtype == lv.dtype and di.dtype == li.dtype
        np.testing.assert_array_equal(np.asarray(lv), np.asarray(dv))
        np.testing.assert_array_equal(np.asarray(li), np.asarray(di))


class TestElastic:
    def test_graph_engine_resize_mid_run(self):
        """ASYMP elastic restart: checkpoint at 8 shards, resume at 4 (and
        2), converge to the exact fixpoint (self-stabilization covers any
        in-flight messages lost at the resize)."""
        import dataclasses
        from repro.configs.base import GraphConfig
        from repro.core import engine as E, graph as G, merger, programs as PR
        from repro.ft.elastic import repartition_state
        from conftest import csr_edges

        cfg8 = GraphConfig(name="t", algorithm="cc", num_vertices=512,
                           avg_degree=6, generator="rmat", num_shards=8,
                           enforce_fraction=0.5)
        g8 = G.build_sharded_graph(cfg8)
        oracle = G.cc_oracle(g8.num_real_vertices, csr_edges(g8))
        # run half-way on 8 shards
        prog = PR.get_program(cfg8)
        ep = E.default_params(cfg8, g8)
        tick = E.make_local_tick(prog, ep, prog.weighted)
        state = E.init_state(prog, g8)
        dg = E.to_device_graph(g8)
        for _ in range(6):
            state, stats, _ = tick(state, dg)
        for new_shards in (4, 2):
            cfgN = dataclasses.replace(cfg8, num_shards=new_shards)
            gN = G.build_sharded_graph(cfgN)
            s = repartition_state(state, g8, gN)
            # regression: repartition re-activates only the old cut-
            # crossing vertices (the only possible in-flight senders),
            # not the whole graph
            n_active = int(np.asarray(s.active).sum())
            b = np.asarray(g8.boundary).copy()
            b[np.arange(8), np.arange(8)] = False
            n_cut = int(b.any(axis=1).sum())
            n_old_active = int(np.asarray(state.active).sum())
            assert n_active <= n_cut + n_old_active
            assert n_active < gN.num_real_vertices
            epN = E.default_params(cfgN, gN)
            tickN = E.make_local_tick(prog, epN, prog.weighted)
            dgN = E.to_device_graph(gN)
            for _ in range(5000):
                s, st, _ = tickN(s, dgN)
                if int(st.active) == 0:
                    break
            out = merger.extract(s, gN, prog)
            assert (out == oracle).all(), new_shards
