"""asymplint: every rule fires on a minimal reproduction of its
motivating bug, suppressions work and go stale loudly, the baseline
round-trips with staleness teeth, and the committed tree is clean
modulo the committed baseline (the same sweep CI runs).

Fixture snippets live in strings; the suppression scanner reads
comments via ``tokenize``, so the ``disable=`` markers inside these
strings are invisible to the sweep that lints this very file.
"""
from __future__ import annotations

import textwrap
from pathlib import Path

from tools import report
from tools.asymplint import RULES, lint_paths, lint_source, rule_infos
from tools.asymplint import baseline as bl
from tools.asymplint import config as al_config
from tools.asymplint.cli import main as asymplint_main

REPO = Path(__file__).resolve().parent.parent


dd = textwrap.dedent


def run(code: str, path: str = "src/repro/fake.py"):
    return lint_source(textwrap.dedent(code), path)


def rules_hit(code: str, path: str = "src/repro/fake.py") -> set[str]:
    return {f.rule for f in run(code, path).findings}


# ======================================================================
# registry sanity
# ======================================================================
class TestRegistry:
    def test_eight_rules_unique_ids_and_codes(self):
        infos = rule_infos()
        assert len(infos) >= 8
        assert len({i.id for i in infos}) == len(infos)
        assert len({i.code for i in infos}) == len(infos)
        assert all(i.code.startswith("ASL") for i in infos)

    def test_every_rule_documented_in_architecture(self):
        # the "Enforced invariants" table must name every rule id
        text = (REPO / "docs" / "ARCHITECTURE.md").read_text()
        for info in rule_infos():
            assert f"`{info.id}`" in text, info.id

    def test_syntax_error_is_a_finding_not_a_crash(self):
        res = run("def broken(:\n")
        assert [f.rule for f in res.findings] == ["syntax"]


# ======================================================================
# ASL001 jit-purity
# ======================================================================
JIT_NP = """
    import jax
    import numpy as np

    def make_tick(prog):
        def tick(x):
            return np.sum(x)
        return jax.jit(tick)
"""


class TestJitPurity:
    def test_np_inside_jitted_closure_fires(self):
        assert rules_hit(JIT_NP) == {"jit-purity"}

    def test_walks_the_module_call_graph(self):
        # the np use hides one call away from the traced function
        assert rules_hit("""
            import jax
            import numpy as np

            def _helper(x):
                return np.asarray(x)

            def make_tick():
                def tick(x):
                    return _helper(x) + 1
                return jax.jit(tick)
        """) == {"jit-purity"}

    def test_partial_jit_decorator_and_time_call(self):
        assert rules_hit("""
            import time
            from functools import partial
            import jax

            @partial(jax.jit, static_argnames=("n",))
            def step(x, n):
                time.sleep(0.1)
                return x
        """) == {"jit-purity"}

    def test_pallas_partial_kernel_is_walked(self):
        assert rules_hit("""
            import functools
            import numpy as np
            from jax.experimental import pallas as pl

            def _kernel(ref, o_ref, *, semiring):
                o_ref[...] = np.maximum(ref[...], 0)

            def spmv(x):
                kernel = functools.partial(_kernel, semiring="min")
                return pl.pallas_call(kernel, grid=(1,))(x)
        """) == {"jit-purity"}

    def test_host_side_np_is_fine(self):
        assert rules_hit("""
            import jax
            import jax.numpy as jnp
            import numpy as np

            def prepare(x):          # host side: np is the right tool
                return np.asarray(x)

            def make_tick():
                def tick(x):
                    return jnp.sum(x)
                return jax.jit(tick)
        """) == set()

    def test_engine_span_inside_jit_fires(self):
        assert rules_hit("""
            import jax
            from repro.core import trace

            def make_tick():
                def tick(x):
                    with trace.span(trace.STEP):
                        return x + 1
                return jax.jit(tick)
        """) == {"jit-purity"}

    def test_suppressed_inline(self):
        res = run(JIT_NP.replace(
            "return np.sum(x)",
            "return np.sum(x)  # asymplint: disable=jit-purity"))
        assert not res.findings and len(res.suppressed) == 1


# ======================================================================
# ASL002 aux-parity
# ======================================================================
STATE_DEF = """
    from typing import NamedTuple

    class EngineState(NamedTuple):
        values: object
        active: object
        cursor: object
        tick: object
        aux: object
"""


class TestAuxParity:
    def test_builder_dropping_aux_fires(self):
        # the PR-4 bug: a dist tick that threads everything except aux
        res = run(dd(STATE_DEF) + dd("""
            def make_dist_tick(prog):
                def tick(state):
                    return (state.values, state.active, state.cursor,
                            state.tick + 1)
                return tick
        """))
        assert {f.rule for f in res.findings} == {"aux-parity"}
        assert "aux" in res.findings[0].message

    def test_full_threading_is_clean(self):
        assert rules_hit(dd(STATE_DEF) + dd("""
            def make_local_tick(prog):
                def tick(state):
                    return EngineState(state.values, state.active,
                                       state.cursor, state.tick + 1,
                                       state.aux)
                return tick
        """)) == set()

    def test_keyword_threading_counts(self):
        assert rules_hit(dd(STATE_DEF) + dd("""
            def make_async_tick(prog):
                def tick(state):
                    return state._replace(values=state.values,
                                          active=state.active,
                                          cursor=state.cursor,
                                          tick=state.tick + 1,
                                          aux=state.aux)
                return tick
        """)) == set()

    def test_ignored_without_an_engine_state_class(self):
        assert rules_hit("""
            def make_other_tick():
                return 1
        """) == set()

    def test_suppressed_inline(self):
        res = run(dd(STATE_DEF) + dd("""
            # asymplint: disable=aux-parity
            def make_stats_tick(prog):
                def tick(state):
                    return state.values
                return tick
        """))
        assert not res.findings and len(res.suppressed) == 1


# ======================================================================
# ASL003 wire-gate
# ======================================================================
class TestWireGate:
    def test_lossy_without_idempotent_fires(self):
        assert rules_hit("""
            def build(vs):
                return make_wire_codec(num_shards=2, capacity=4, vs=vs,
                                       requested="int8",
                                       value_kind="float32", identity=0.0)
        """) == {"wire-gate"}

    def test_gated_by_effective_compression_is_clean(self):
        assert rules_hit("""
            def build(cfg, prog):
                mode = effective_compression(
                    cfg.wire_compression, "float32",
                    idempotent=prog.aggregator.idempotent)
                return make_wire_codec(num_shards=2, capacity=4, vs=8,
                                       requested=mode,
                                       value_kind="float32", identity=0.0)
        """) == set()

    def test_none_and_engine_params_attr_are_clean(self):
        assert rules_hit("""
            def build_none(vs):
                return make_wire_codec(num_shards=2, capacity=4, vs=vs,
                                       requested="none",
                                       value_kind="int32", identity=0)

            def wire_codec(prog, ep: EngineParams):
                return make_wire_codec(num_shards=ep.num_shards,
                                       capacity=4, vs=8,
                                       requested=ep.wire_compression,
                                       value_kind="int32", identity=0)
        """) == set()

    def test_explicit_idempotent_is_clean(self):
        assert rules_hit("""
            def build(vs):
                return make_wire_codec(num_shards=2, capacity=4, vs=vs,
                                       requested="int16",
                                       value_kind="int32", identity=0,
                                       idempotent=True)
        """) == set()

    def test_direct_wirecodec_outside_home_module_fires(self):
        assert rules_hit("""
            def sneaky():
                return WireCodec(compression="int8", capacity=4)
        """) == {"wire-gate"}

    def test_direct_wirecodec_in_defining_module_is_clean(self):
        assert rules_hit("""
            class WireCodec:
                pass

            def make_wire_codec(requested="none"):
                return WireCodec()
        """) == set()


# ======================================================================
# ASL004 pin-balance
# ======================================================================
PIN_LEAK = """
    def handler(store, epoch):
        store.pin(epoch)
        return store.values(epoch)
"""


class TestPinBalance:
    def test_unbalanced_pin_fires(self):
        # the PR-9 class: an exception between pin and use leaks the pin
        assert rules_hit(PIN_LEAK, "src/repro/serve/fake.py") == \
            {"pin-balance"}

    def test_try_finally_release_is_clean(self):
        assert rules_hit("""
            def reader(store, epoch):
                store.pin(epoch)
                try:
                    return store.values(epoch)
                finally:
                    store.unpin(epoch)
        """) == set()

    def test_store_internals_exempt(self):
        # view() transfers ownership to the FixpointView; the class
        # defining both pin and unpin owns its refcount protocol
        assert rules_hit("""
            class FixpointStore:
                def pin(self, epoch):
                    return True

                def unpin(self, epoch):
                    pass

                def view(self, epoch):
                    self.pin(epoch)
                    return epoch
        """) == set()

    def test_suppressed_inline(self):
        res = run(PIN_LEAK.replace(
            "store.pin(epoch)",
            "store.pin(epoch)  # asymplint: disable=pin-balance"))
        assert not res.findings and len(res.suppressed) == 1


# ======================================================================
# ASL005 tick-keying
# ======================================================================
class TestTickKeying:
    def test_host_loop_counter_fires(self):
        # the PR-6 bug: firing pattern keyed by the host step counter
        assert rules_hit("""
            class Session:
                def drive(self, n):
                    for t in range(n):
                        fire = self._inter.fire_mask(t)
        """) == {"tick-keying"}

    def test_host_attribute_counter_fires(self):
        assert rules_hit("""
            class Session:
                def step(self):
                    fire = self._inter.fire_mask(self._t)
        """) == {"tick-keying"}

    def test_device_tick_key_is_clean(self):
        assert rules_hit("""
            class Session:
                def step(self, throttle):
                    dev_tick = int(self._astate.core.tick)
                    fire = self._inter.fire_mask(dev_tick,
                                                 rates=throttle)
        """) == set()

    def test_out_of_scope_in_tests(self):
        # tests may drive fire_mask as a pure function of a loop index
        assert rules_hit("""
            def test_fire(inter):
                for t in range(60):
                    fire = inter.fire_mask(t)
        """, "tests/test_fake.py") == set()


# ======================================================================
# ASL006 cursor-latch
# ======================================================================
class TestCursorLatch:
    def test_latch_without_cursor_fires(self):
        # the PR-8 zero-mass shape: latch ignores the edge cursor
        assert rules_hit("""
            def phase1(sel_valid, pushv, sel_safe):
                latch = sel_valid & (pushv[sel_safe] == 0)
                return latch
        """) == {"cursor-latch"}

    def test_cursor_coupled_latch_is_clean(self):
        assert rules_hit("""
            def phase1(sel_valid, pushv, sel_safe, cur):
                latch = sel_valid & (pushv[sel_safe] == 0) & (cur == 0)
                return latch
        """) == set()

    def test_out_of_scope_in_tests(self):
        assert rules_hit("""
            def test_latch():
                latch = True
        """, "tests/test_fake.py") == set()


# ======================================================================
# ASL007 registry-contract
# ======================================================================
class TestRegistryContract:
    def test_sum_without_self_stabilizing_false_fires(self):
        assert rules_hit("""
            def pagerank(weighted):
                return VertexProgram("pagerank", "float32", SUM, weighted,
                                     init, combine, priority_value)
        """) == {"registry-contract"}

    def test_sum_with_checkpoint_recovery_is_clean(self):
        assert rules_hit("""
            def pagerank(weighted):
                return VertexProgram("pagerank", "float32", SUM, weighted,
                                     init, combine, priority_value,
                                     self_stabilizing=False,
                                     aux_channels=2)
        """) == set()

    def test_idempotent_program_needs_no_declaration(self):
        assert rules_hit("""
            def cc():
                return VertexProgram("cc", "int32", MIN, False, init,
                                     combine, priority_value)
        """) == set()


# ======================================================================
# ASL008 bench-rows
# ======================================================================
class TestBenchRows:
    def test_module_level_rows_store_fires(self):
        # the PR-7 global: rows aggregated across areas double-report
        assert rules_hit("""
            ROWS = []

            def main():
                ROWS.append({"name": "x"})
        """, "benchmarks/bench_fake.py") == {"bench-rows"}

    def test_import_time_emit_fires(self):
        assert rules_hit("""
            from benchmarks.common import emit

            emit(name="cell/x", us_per_call=1.0)
        """, "benchmarks/bench_fake.py") == {"bench-rows"}

    def test_collect_scoped_emit_is_clean(self):
        assert rules_hit("""
            from benchmarks.common import bench_cli, emit

            def main(smoke):
                emit(name="cell/x", us_per_call=1.0)

            if __name__ == "__main__":
                bench_cli("fake", main, main)
        """, "benchmarks/bench_fake.py") == set()

    def test_out_of_scope_outside_benchmarks(self):
        assert rules_hit("ROWS = []\n", "src/repro/fake.py") == set()


# ======================================================================
# suppressions: staleness has teeth, strings are inert
# ======================================================================
class TestSuppressions:
    def test_stale_suppression_is_an_error(self):
        res = run("x = 1  # asymplint: disable=wire-gate\n")
        assert [f.rule for f in res.findings] == \
            [al_config.STALE_SUPPRESSION]
        assert res.findings[0].severity == report.ERROR

    def test_disable_all_wildcard(self):
        res = run(PIN_LEAK.replace(
            "store.pin(epoch)",
            "store.pin(epoch)  # asymplint: disable=all"))
        assert not res.findings and len(res.suppressed) == 1

    def test_markers_inside_strings_are_inert(self):
        # fixture snippets quoted in test files must not register
        res = run('SNIPPET = """\nx = 1  # asymplint: disable=all\n"""\n')
        assert not res.findings and not res.suppressed


# ======================================================================
# baseline: round-trip, grandfathering, staleness, shrink
# ======================================================================
def _violating_tree(tmp_path: Path, body: str = None) -> Path:
    mod = tmp_path / "src" / "repro" / "serve" / "mod.py"
    mod.parent.mkdir(parents=True)
    mod.write_text(textwrap.dedent(body or PIN_LEAK))
    return mod


class TestBaseline:
    def test_round_trip_and_grandfathering(self, tmp_path):
        _violating_tree(tmp_path)
        res = lint_paths(["src"], str(tmp_path))
        assert len(res.findings) == 1
        entries = bl.from_findings(res.findings, str(tmp_path),
                                   justification="known leak, PR pending")
        path = tmp_path / "baseline.json"
        bl.save(entries, str(path))
        assert bl.load(str(path)) == entries
        new, grandfathered, health = bl.apply(res.findings, entries,
                                              str(tmp_path))
        assert not new and len(grandfathered) == 1 and not health

    def test_line_shift_does_not_churn(self, tmp_path):
        mod = _violating_tree(tmp_path)
        res = lint_paths(["src"], str(tmp_path))
        entries = bl.from_findings(res.findings, str(tmp_path))
        mod.write_text("# a comment pushed everything down\n" +
                       mod.read_text())
        res2 = lint_paths(["src"], str(tmp_path))
        new, grandfathered, health = bl.apply(res2.findings, entries,
                                              str(tmp_path))
        assert not new and len(grandfathered) == 1 and not health

    def test_fixed_line_turns_entry_stale(self, tmp_path):
        mod = _violating_tree(tmp_path)
        res = lint_paths(["src"], str(tmp_path))
        entries = bl.from_findings(res.findings, str(tmp_path))
        mod.write_text(textwrap.dedent("""
            def handler(store, epoch):
                return store.values(epoch)
        """))
        stale = bl.validate(entries, str(tmp_path))
        assert [f.rule for f in stale] == [al_config.STALE_BASELINE]
        assert stale[0].severity == report.ERROR

    def test_missing_file_turns_entry_stale(self, tmp_path):
        entries = [bl.Entry(rule="pin-balance", path="src/gone.py",
                            line=3, text="store.pin(epoch)",
                            justification="x")]
        stale = bl.validate(entries, str(tmp_path))
        assert [f.rule for f in stale] == [al_config.STALE_BASELINE]

    def test_fixed_finding_is_a_shrink_warning(self, tmp_path):
        # the pinned text still exists (the pin is now balanced), but
        # no finding matches it: shrink opportunity, warn-only
        mod = _violating_tree(tmp_path)
        res = lint_paths(["src"], str(tmp_path))
        entries = bl.from_findings(res.findings, str(tmp_path))
        mod.write_text(textwrap.dedent("""
            def handler(store, epoch):
                store.pin(epoch)
                try:
                    return store.values(epoch)
                finally:
                    store.unpin(epoch)
        """))
        res2 = lint_paths(["src"], str(tmp_path))
        assert not res2.findings
        new, grandfathered, health = bl.apply(res2.findings, entries,
                                              str(tmp_path))
        assert not new and not grandfathered
        assert [f.rule for f in health] == [al_config.BASELINE_SHRINK]
        assert health[0].severity == report.WARN

    def test_version_mismatch_rejected(self, tmp_path):
        path = tmp_path / "baseline.json"
        path.write_text('{"version": 99, "entries": []}')
        try:
            bl.load(str(path))
            assert False, "must reject unknown versions"
        except ValueError:
            pass


# ======================================================================
# CLI + the committed tree
# ======================================================================
class TestCli:
    def test_violating_tree_fails_then_baselines_clean(self, tmp_path):
        _violating_tree(tmp_path)
        base = str(tmp_path / "baseline.json")
        args = ["--root", str(tmp_path), "--baseline", base, "src"]
        assert asymplint_main(args) == report.EXIT_FINDINGS
        assert asymplint_main(args + ["--write-baseline"]) == \
            report.EXIT_OK
        assert asymplint_main(args) == report.EXIT_OK
        assert asymplint_main(
            ["--root", str(tmp_path), "--baseline", base,
             "--validate-baseline"]) == report.EXIT_OK

    def test_unknown_path_is_a_usage_error(self, tmp_path):
        assert asymplint_main(["--root", str(tmp_path), "nope"]) == \
            report.EXIT_USAGE

    def test_committed_tree_is_clean_modulo_baseline(self):
        # the exact sweep CI runs: new findings, stale suppressions or
        # stale baseline entries anywhere in the repo fail this test
        assert asymplint_main(["--root", str(REPO),
                               "src", "tests", "benchmarks"]) == \
            report.EXIT_OK

    def test_committed_baseline_validates(self):
        assert asymplint_main(["--root", str(REPO),
                               "--validate-baseline"]) == report.EXIT_OK
