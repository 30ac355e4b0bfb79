"""CPU tests of ``metrics/receive_slot_share.mine.py``: the share on a
recorded trace with known args, nothing where the program opens no totals
span, and the share on a traced run of every cell at scale 9."""
from __future__ import annotations

import time

import jax
import pytest

from chipbench import program_trace, run, spec, trace_reduce

SPAN = "asymp:session.totals"
READER = spec.Benchmark().reader("receive_slot_share.mine")


def _record(path, totals):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(path), profiler_options=options)
    with jax.profiler.TraceAnnotation(SPAN, pulls=1, recv_slots=7,
                                      recv_budget=70):
        pass  # before the window: not counted
    with run.span("window"):
        with jax.profiler.TraceAnnotation("asymp:session.sync", pulls=4):
            time.sleep(0.01)
        for slots, budget in totals:
            with jax.profiler.TraceAnnotation(SPAN, pulls=1,
                                              recv_slots=slots,
                                              recv_budget=budget):
                time.sleep(0.001)
    jax.profiler.stop_trace()
    return {"trace": {"window_s": trace_reduce.read(str(path))["window_s"]},
            "jobs": [{"ticks": 3}]}


@pytest.mark.parametrize("totals, share", [
    ([(30, 600), (10, 200)], 5.0),
    ([(600, 600)], 100.0),
    ([], None),
], ids=["two_jobs", "full_width", "no_span"])
def test_share_of_the_windows_totals(tmp_path, monkeypatch, totals, share):
    monkeypatch.setattr(program_trace, "TRACE_ROOT", str(tmp_path))
    ctx = _record(tmp_path / "cell", totals)
    assert READER.read(ctx) == share
    assert READER.read({**ctx, "trace": None}) is None


class Small(spec.Benchmark):
    def config(self, cell):
        data = super().config(cell)
        data["graph"]["scale"] = 9
        data["engine"]["max_ticks_per_job"] = 400
        return data


@pytest.mark.parametrize("cell", sorted(spec.Benchmark().cells))
def test_traced_run_reads_the_share(cell, tmp_path, monkeypatch):
    monkeypatch.setattr(run, "OUT", str(tmp_path))
    monkeypatch.setattr(program_trace, "TRACE_ROOT",
                        str(tmp_path / "trace"))
    result = run.run_cell(Small(), cell, 2**31 + 19, 0.3, True,
                          jax.devices()[:1], time.perf_counter())
    assert result["correct"], result["checks"]
    share = result["metrics"]["receive_slot_share.mine"]
    assert share["unit"] == "%" and 0 < share["value"] <= 100
