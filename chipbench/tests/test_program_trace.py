"""CPU tests of ``chipbench/program_trace.py``: the reduction on known
intervals, the check that a trace is the run's own, and the readers on a
traced run at scale 9 (which has no device planes on the CPU, so only the
host spans' counts can be read there)."""
from __future__ import annotations

import glob
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import program_trace, run, spec, trace_reduce

MS = 1e6
TICK_PATHS = {
    "select": "jit(tick)/vmap(tick.select)/add",
    "exchange": "jit(tick)/tick.exchange/tick.exchange/transpose",
    "receive": "jit(tick)/vmap(tick.receive)/scatter-min",
}
NEW = ("select_device_ms.mine", "fetch_device_ms.mine",
       "route_device_ms.mine", "exchange_device_ms.mine",
       "receive_device_ms.mine", "sync_idle_ms.mine",
       "dispatch_idle_ms.mine", "host_pulls_per_tick.mine",
       "log_idle_ms.mine", "replay_idle_ms.mine", "log_kb_per_tick.mine")


def _events():
    ops = [("fusion.0 s32[8]", TICK_PATHS["select"], 0, 4 * MS),
           ("fusion.1 s32[8]", TICK_PATHS["select"], 10 * MS, 20 * MS),
           ("fusion.2 s32[64]", TICK_PATHS["exchange"], 20 * MS, 25 * MS),
           ("fusion.3 s32[]", "reduce_sum", 25 * MS, 30 * MS),
           ("fusion.4 s32[8]", TICK_PATHS["receive"], 60 * MS, 70 * MS)]
    spans = [("bench:session.tick_until_quiescent", 0, 100 * MS, {}),
             ("asymp:session.step", 5 * MS, 50 * MS, {"tick": 0}),
             ("asymp:session.dispatch", 5 * MS, 12 * MS, {}),
             ("asymp:session.sync", 30 * MS, 45 * MS, {"pulls": 4}),
             ("asymp:session.step", 50 * MS, 95 * MS, {"tick": 1}),
             ("asymp:recovery.log", 50 * MS, 58 * MS,
              {"pulls": 2, "bytes": 100}),
             ("asymp:session.sync", 58 * MS, 65 * MS, {"pulls": 4})]
    return [ops], spans, (2 * MS, 98 * MS)


def test_reduction_on_known_intervals():
    out = program_trace.reduce_events(*_events())
    assert out["window_s"] == pytest.approx(0.096) and out["devices"] == 1
    # fusion.0 is cut to the window (2-4 ms)
    assert out["scope_s"] == pytest.approx({
        "tick.select": 0.012, "tick.exchange": 0.005,
        "tick.receive": 0.010, "outside": 0.005})
    assert out["outside_ops"] == [["fusion.3 s32[]", pytest.approx(0.005)]]
    # gaps 4-10, 30-60 and 70-98 ms, split by overlap: 30-60 straddles
    # sync (30-45), step (45-50), log (50-58) and sync again (58-60)
    assert out["idle_s"] == pytest.approx({
        "bench:session.tick_until_quiescent": 0.004,
        "asymp:session.dispatch": 0.005, "asymp:session.sync": 0.017,
        "asymp:session.step": 0.030, "asymp:recovery.log": 0.008})
    assert sum(out["idle_s"].values()) == pytest.approx(
        out["window_s"] - 0.032)
    assert out["spans"] == {
        "asymp:recovery.log": {"count": 1, "pulls": 2, "bytes": 100,
                               "replayed": 0},
        "asymp:session.dispatch": {"count": 1, "pulls": 0, "bytes": 0,
                                   "replayed": 0},
        "asymp:session.step": {"count": 2, "pulls": 0, "bytes": 0,
                               "replayed": 0},
        "asymp:session.sync": {"count": 2, "pulls": 8, "bytes": 0,
                               "replayed": 0}}


def test_innermost_span_labels_each_instant():
    spans = [("bench:a", 0, 10, {}), ("asymp:b", 2, 8, {}),
             ("asymp:c", 2, 4, {}), ("bench:d", 5, 6, {})]
    # an asymp span is innermost over the bench span opened inside it
    assert program_trace.labels(spans, 0, 12) == [
        (0, 2, "bench:a"), (2, 4, "asymp:c"), (4, 8, "asymp:b"),
        (8, 10, "bench:a"), (10, 12, "outside")]
    assert program_trace.split([(1, 3), (9, 11)], program_trace.labels(
        spans, 0, 12)) == {"bench:a": 2, "asymp:c": 1, "outside": 1}
    assert program_trace.scope("jit(tick)/vmap(tick.route)/jit(argsort)") \
        == "tick.route"
    assert program_trace.scope("jit(tick)/mytick.route/x") == "outside"


def _record(path, window_ms: float = 30):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(path), profiler_options=options)
    with run.span("window"):
        with jax.profiler.TraceAnnotation("asymp:session.sync", pulls=3):
            time.sleep(window_ms / 1e3)
    jax.profiler.stop_trace()


def test_reader_refuses_a_trace_that_is_not_the_runs(tmp_path, monkeypatch):
    monkeypatch.setattr(program_trace, "TRACE_ROOT", str(tmp_path))
    _record(tmp_path / "cell")
    window = trace_reduce.read(str(tmp_path / "cell"))["window_s"]
    jobs = [{"ticks": 3}]
    red = program_trace.read({"trace": {"window_s": window}, "jobs": jobs})
    assert red["spans"]["asymp:session.sync"]["pulls"] == 3
    ctx = {"trace": {"window_s": window + 1e-6}, "jobs": jobs}
    assert program_trace.read(ctx) is None
    assert program_trace.arg_per_tick(ctx, "pulls") is None
    assert program_trace.read({"trace": None, "jobs": jobs}) is None


def test_scope_of_a_fusion_from_the_programs_hlo(tmp_path):
    """A fusion whose root a compiler pass made has no ``op_name`` of its
    own; its scope comes from what it fuses, in the HLO the trace holds."""
    @jax.named_scope("tick.receive")
    def receive(x):
        return jnp.sin(x) * 2

    fn = jax.jit(lambda x: jax.vmap(receive)(x) + 1)
    x = jnp.ones((4, 3))
    fn(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    fn(x).block_until_ready()
    jax.profiler.stop_trace()
    with open(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)[0], "rb") as f:
        meta = program_trace.metadata(f.read())
    (proto,) = [stats["Hlo Proto"] for name, stats in
                meta["/host:metadata"].items() if "lambda" in name]
    module = dict(program_trace.fields(proto))[1]  # HloProto.hlo_module
    scopes = program_trace.hlo_scopes(module)
    fusions = {n: s for n, s in scopes.items() if "fusion" in n}
    assert fusions and set(fusions.values()) == {"tick.receive"}
    assert {"sin.0": "tick.receive"}.items() <= scopes.items()


class Small(spec.Benchmark):
    def config(self, cell):
        data = super().config(cell)
        data["graph"]["scale"] = 9
        data["engine"]["max_ticks_per_job"] = 400
        return data


@pytest.mark.parametrize("cell", sorted(spec.Benchmark().cells))
def test_traced_run_reads_the_program_spans(cell, tmp_path, monkeypatch):
    monkeypatch.setattr(run, "OUT", str(tmp_path))
    monkeypatch.setattr(program_trace, "TRACE_ROOT",
                        str(tmp_path / "trace"))
    bench = Small()
    result = run.run_cell(bench, cell, 2**31 + 9, 0.3, True,
                          jax.devices()[:1], time.perf_counter())
    assert result["correct"], result["checks"]
    got = result["metrics"]
    kills = bench.traffic(bench.cell(cell))["faults"]
    # no device planes on the CPU: only the spans' counts are read here
    assert set(got) & set(NEW) == ({"host_pulls_per_tick.mine",
                                    "log_kb_per_tick.mine"} if kills
                                   else {"host_pulls_per_tick.mine"})
    pulls = got["host_pulls_per_tick.mine"]["value"]
    if kills:
        assert pulls > 4.0 and got["log_kb_per_tick.mine"]["value"] > 0
    else:
        assert pulls == 4.0
