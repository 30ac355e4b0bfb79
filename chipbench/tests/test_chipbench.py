"""CPU tests of the on-chip benchmark's harness, at tiny sizes.

The cells run here with their graphs cut to scale 9 and the look for a chip
skipped (``run.run_cell`` is everything after it); the controls and the
faults of ``chipbench/control.py`` are planted underneath such runs and must
turn ``correct`` false.
"""
from __future__ import annotations

import json
import re
import threading
import time

import jax
import numpy as np
import pytest

from chipbench import control, peaks, run, spec, trace_reduce
from chipbench.graph500 import kronecker_edges
from chipbench.reference import wcc

BENCH = spec.Benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = sorted(BENCH.cells)
SCALE = 9


class Small(spec.Benchmark):
    """The benchmark with every graph cut to ``SCALE``."""

    def config(self, cell):
        data = super().config(cell)
        data["graph"]["scale"] = SCALE
        data["engine"]["max_ticks_per_job"] = 400
        return data


def small_run(cell: str, seed: int = 2**31 + 5, trace: bool = False,
              seconds: float = 0.3) -> dict:
    return run.run_cell(Small(), cell, seed, seconds, trace,
                        jax.devices()[:1], time.perf_counter())


# ---------------------------------------------------------------- the file
def test_benchmark_file_follows_the_contract():
    d = BENCH.data
    assert list(d) == ["command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"]
    assert d["command"][:2] == ["python3", "-m"]
    assert d["paths"] == ["chipbench"]
    assert 1 <= d["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in d[k]]
    assert all(NAME.match(n) for n in names), names
    assert len(set(names)) == len(names)
    for c in d["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("chipbench/")
        assert all(NAME.match(k) for k in c["reduced"])
    used = {w["config"] for w in d["workloads"]}
    assert used == {c["name"] for c in d["configs"]}
    e2e = {m["name"]: m for m in d["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in d["end_to_end"] + d["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower",
                                                             "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in d["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in d["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    w = BENCH.cell(cell)
    assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    config, traffic = BENCH.config(w), BENCH.traffic(w)
    assert config["name"] == w["config"]
    assert set(BENCH.configs[w["config"]]["reduced"]) <= set(
        config["reduced"])
    assert hasattr(spec.loop(traffic["loop"]), "Workload")
    ref = spec.reference(config["reference"])
    assert callable(ref.solve) and callable(ref.compare)
    e2e = BENCH.metrics(w, "end_to_end")
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    layer = BENCH.metrics(w, "per_layer")
    assert layer
    for m in e2e + layer:
        assert callable(BENCH.reader(m["name"]).read)
    for m in layer:  # what a per-layer metric moves is reported here
        assert m["moves"] in {x["name"] for x in e2e}


# ------------------------------------------------------------ whole runs
@pytest.mark.parametrize("trace", [False, True], ids=["e2e", "traced"])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end_on_a_small_graph(cell, trace):
    result = small_run(cell, trace=trace)
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    kind = "per_layer" if trace else "end_to_end"
    w = BENCH.cell(cell)
    wanted = {m["name"] for m in BENCH.metrics(w, kind)}
    assert set(result["metrics"]) <= wanted
    if trace:  # the trace-fed metrics have no device planes to read here
        assert "ticks_per_job" in result["metrics"]
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
        assert result["device"]["window_s"] > 0
        kills = BENCH.traffic(w)["faults"]
        assert ("replayed_per_job" in result["metrics"]) == bool(kills)
    else:
        assert set(result["metrics"]) == wanted
        assert all(m["value"] > 0 for m in result["metrics"].values())
    json.dumps(result)


def test_same_seed_same_inputs():
    a = kronecker_edges(8, 16, (0.57, 0.19, 0.19, 0.05), 2**31 + 11)
    b = kronecker_edges(8, 16, (0.57, 0.19, 0.19, 0.05), 2**31 + 11)
    c = kronecker_edges(8, 16, (0.57, 0.19, 0.19, 0.05), 2**31 + 12)
    assert a.shape == (16 << 8, 2) and a.min() >= 0 and a.max() < 256
    assert np.array_equal(a, b) and not np.array_equal(a, c)


def test_seed_orders_the_graphs_and_draws_the_kill_plans():
    cell = next(c for c in CELLS if BENCH.traffic(BENCH.cell(c))["faults"])
    small, w = Small(), BENCH.cell(cell)
    config, traffic = small.config(w), small.traffic(w)
    loop = spec.loop(traffic["loop"])
    a, b, c = (loop.Workload(config, traffic, s)
               for s in (2**31 + 3, 2**31 + 3, 2**31 + 4))
    assert sorted(a.graph_seeds) == sorted(config["graph"]["seeds"])
    assert a.graph_seeds == b.graph_seeds
    plans = [[x.kill_plan().seed for _ in range(4)] for x in (a, b, c)]
    assert plans[0] == plans[1] != plans[2]
    # one padded width for every graph, so one compiled tick
    assert len({t.graph.es for t in a.templates}) == 1


def test_reference_labels_components_by_their_least_vertex():
    edges = np.array([[5, 1], [2, 3], [3, 2], [4, 4]])
    assert wcc.min_labels(7, edges).tolist() == [0, 1, 2, 2, 4, 1, 6]
    truth = wcc.solve(7, edges)
    assert wcc.compare(truth, truth) == 0
    assert wcc.compare(truth, truth[:3]) == 7


# ----------------------------------------- the control and planted faults
def _breakers(cell: str):
    kills = BENCH.traffic(BENCH.cell(cell))["faults"]
    out = [("early_return", control.early_return)]
    if kills:
        out.append(("no_replay", control.no_replay))
    return out + sorted(control.FAULTS.items())


@pytest.mark.parametrize("cell,name,breaker", [
    (c, n, b) for c in CELLS for n, b in _breakers(c)],
    ids=[f"{c}-{n}" for c in CELLS for n, _ in _breakers(c)])
def test_check_fails_when_the_program_is_broken(cell, name, breaker):
    with breaker():
        result = small_run(cell)
    assert not result["correct"], (name, result["checks"])
    assert result["checks"]["no_work"]["value"] == 0


# ---------------------------------------------------------- the device
def test_measuring_path_refuses_a_cpu(capsys):
    with pytest.raises(SystemExit) as exc:
        run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out == ""


# ---------------------------------------------------------- the trace
def test_trace_reduction_on_known_intervals():
    ms = 1e6
    devices = [[("a", 10 * ms, 30 * ms), ("b", 20 * ms, 40 * ms),
                ("a", 60 * ms, 70 * ms)],
               [("a", 0, 50 * ms)]]
    spans = [("fork", 0, 55 * ms), ("tick", 55 * ms, 100 * ms),
             ("inner", 80 * ms, 90 * ms)]
    out = trace_reduce.reduce_events(devices, spans, (5 * ms, 100 * ms))
    # device 0: busy 10-40 and 60-70 = 40 ms; device 1: busy 5-50 = 45 ms
    assert out["busy_s"] == pytest.approx(0.0425)
    assert out["window_s"] == pytest.approx(0.095)
    assert dict((n, v) for n, v in out["device_ops"]) == pytest.approx(
        {"a": 0.0375, "b": 0.01})
    # gaps by midpoint: dev0 5-10 and 40-60 (fork), 70-100 (inner);
    # dev1 50-100 (tick); each halved over the two devices
    assert dict((n, v) for n, v in out["idle_gaps"]) == pytest.approx(
        {"fork": 0.0125, "inner": 0.015, "tick": 0.025})


def test_trace_reduction_on_a_recorded_trace(tmp_path):
    """Sleeps on a second thread stand in for device ops: 20 + 30 ms
    busy, inside a window whose host spans are known."""
    def fake_device():
        for d in (0.02, 0.03):
            with jax.profiler.TraceAnnotation("op.sleep"):
                time.sleep(d)
            time.sleep(0.02)

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    with run.span("window"):
        t = threading.Thread(target=fake_device)
        with run.span("host_wait"):
            t.start()
            t.join()
    jax.profiler.stop_trace()

    def sleeps(plane, line):
        return any(ev.name == "op.sleep" for ev in line.events)

    out = trace_reduce.read(str(tmp_path), device_line=sleeps)
    assert out["devices"] == 1
    assert out["busy_s"] == pytest.approx(0.05, abs=0.01)
    assert out["window_s"] == pytest.approx(0.09, abs=0.03)
    assert out["device_ops"][0][0] == "op.sleep"
    gaps = dict((n, v) for n, v in out["idle_gaps"])
    assert set(gaps) <= {"host_wait", "outside"}
    assert gaps["host_wait"] == pytest.approx(out["window_s"] - out["busy_s"],
                                              abs=0.01)


def test_idle_gaps_go_to_the_innermost_span():
    assert trace_reduce.label(5, [("a", 0, 10), ("b", 4, 6)]) == "b"
    assert trace_reduce.label(50, [("a", 0, 10)]) == "outside"
    assert trace_reduce.op_name(
        "%fusion.13 = s32[299968]{0:T(1024)} fusion(s32[8]{0} %a), "
        "kind=kCustom") == "fusion.13 s32[299968]"


def test_peaks_are_keyed_by_device_kind():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("no such chip")
