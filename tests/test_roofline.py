"""Unit tests for the HLO collective parser and roofline math."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.roofline import analysis as ra


HLO_SAMPLE = """
  %all-reduce = f32[1024]{0} all-reduce(%x), channel_id=1, replica_groups=[4,2]<=[8], use_global_device_ids=true, to_apply=%add
  %ag = bf16[8,128]{1,0} all-gather(%y), channel_id=2, replica_groups={{0,1,2,3}}, dimensions={0}
  %rs = f32[64]{0} reduce-scatter(%z), channel_id=3, replica_groups=[1,8]<=[8], to_apply=%add
  %a2a = bf16[16,32]{1,0} all-to-all(%w), channel_id=4, replica_groups=[2,4]<=[8]
  %cp = f32[256]{0} collective-permute(%v), channel_id=5, source_target_pairs={{0,1}}
  %ard = f32[12]{0} all-reduce-done(%ar)
"""


class TestCollectiveParser:
    def test_parses_ops_and_groups(self):
        cols = {c.op: c for c in ra.parse_collectives(HLO_SAMPLE)}
        assert cols["all-reduce"].group_size == 2
        assert cols["all-reduce"].result_bytes == 4096
        assert cols["all-gather"].group_size == 4
        assert cols["all-gather"].result_bytes == 8 * 128 * 2
        assert cols["reduce-scatter"].group_size == 8
        assert cols["all-to-all"].group_size == 4
        assert cols["collective-permute"].result_bytes == 1024

    def test_wire_formulas(self):
        # ring all-reduce: 2(n-1)/n * bytes
        assert ra._wire_bytes("all-reduce", 1000, 4) == 1500
        assert ra._wire_bytes("all-gather", 1000, 4) == 750
        assert ra._wire_bytes("reduce-scatter", 100, 4) == 300
        assert ra._wire_bytes("all-to-all", 1000, 4) == 750
        assert ra._wire_bytes("collective-permute", 1000, 4) == 1000
        assert ra._wire_bytes("all-reduce", 1000, 1) == 0

    def test_real_compiled_module(self):
        """Parser agrees with a real lowered psum."""
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        mesh = Mesh(np.array(jax.devices()[:1]), ("d",))

        def f(x):
            return jax.lax.psum(x, "d")

        c = jax.jit(shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                              check_vma=False)
                    ).lower(jnp.zeros((128,), jnp.float32)).compile()
        cols = ra.parse_collectives(c.as_text())
        assert all(c_.op in ra.COLLECTIVE_OPS for c_ in cols)

    def test_analyze_terms(self):
        class Fake:
            def cost_analysis(self):
                return {"flops": 197e12, "bytes accessed": 819e9}

            def as_text(self):
                return HLO_SAMPLE

        r = ra.analyze(Fake())
        assert abs(r.compute_s - 1.0) < 1e-9
        assert abs(r.memory_s - 1.0) < 1e-9
        assert r.dominant in ("compute", "memory", "collective")


class TestModelFlops:
    def test_train_flops(self):
        from repro.configs import SHAPES, get_config
        cfg = get_config("qwen3-4b")
        mf = ra.model_flops(cfg, SHAPES["train_4k"], "train")
        expect = 6 * cfg.param_count() * 256 * 4096
        assert abs(mf - expect) / expect < 1e-6

    def test_moe_uses_active_params(self):
        from repro.configs import SHAPES, get_config
        cfg = get_config("deepseek-v3-671b")
        mf = ra.model_flops(cfg, SHAPES["train_4k"], "train")
        assert mf < 6 * cfg.param_count() * 256 * 4096 * 0.1  # 37B of 671B
