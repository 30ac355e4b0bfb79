"""The engine's host spans in a recorded CPU trace.

A scale-9 session runs to quiescence under ``jax.profiler``, healthy and
under a ``FaultPlan``; the ``asymp:`` events of its trace must follow the
interface of ``docs/ARCHITECTURE.md`` ("Spans and counters"): one step span
per tick, each pull of the step counted once in a span's ``pulls``, the
receive slots pulled once per run in a totals span, the message log's
``bytes`` equal to the send buffers', and recovery spans only where a plan
kills shards.  The profiler must not change what the session computes.
"""
import glob
import os

import jax
import numpy as np
import pytest

from repro.configs.base import GraphConfig
from repro.core import engine as E
from repro.core import trace
from repro.core.faults import FaultPlan

# pulls a tick on a tick that kills nothing (on the plain schedule the
# last tick's pull of ``accepted``, 0 there, is the totals span's instead)
SYNC_PULLS = {"plain": 4, "crowded": 5, "async": 8}
PLAN = dict(fail_fraction=0.5, start_tick=4, every=6, seed=11)


def _cfg(schedule: str) -> GraphConfig:
    kw = dict(name="t-trace", algorithm="cc", num_vertices=512,
              avg_degree=8, num_shards=8, seed=5, max_ticks=400)
    if schedule == "crowded":
        kw.update(latency_profile="stragglers", slow_fraction=0.5,
                  link_delay=2)
    if schedule == "async":
        kw.update(schedule="async")
    return GraphConfig(**kw)


def _session(schedule: str, plan: bool) -> E.EngineSession:
    return E.EngineSession(_cfg(schedule),
                           fault_plan=FaultPlan(**PLAN) if plan else None)


def _spans(path) -> list[tuple[str, int, int, dict]]:
    from jax.profiler import ProfileData

    pb = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(pb[0]).planes:
        for line in plane.lines:
            out += [(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
                    for ev in line.events if ev.name.startswith("asymp:")]
    return sorted(out, key=lambda s: s[1])


def _traced(tmp_path, schedule: str, plan: bool):
    session = _session(schedule, plan)
    session.step()  # compile outside the trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    totals = session.tick_until_quiescent()
    jax.profiler.stop_trace()
    return session, totals, _spans(str(tmp_path))


def _named(spans, name):
    return [s for s in spans if s[0] == name]


@pytest.fixture(scope="module")
def healthy(tmp_path_factory):
    return _traced(tmp_path_factory.mktemp("healthy"), "plain", False)


@pytest.fixture(scope="module")
def killed(tmp_path_factory):
    return _traced(tmp_path_factory.mktemp("killed"), "plain", True)


def test_one_step_span_per_tick(healthy):
    session, totals, spans = healthy
    steps = _named(spans, trace.STEP)
    # the fixture's first tick ran before the trace
    assert len(steps) == totals["ticks"] - 1 > 10
    assert [s[3]["tick"] for s in steps] == list(range(1, totals["ticks"]))
    for name in (trace.DISPATCH, trace.SYNC):
        inner = _named(spans, name)
        assert len(inner) == len(steps)
        assert all(a[1] <= b[1] and b[2] <= a[2]
                   for a, b in zip(steps, inner))


@pytest.mark.parametrize("schedule", sorted(SYNC_PULLS))
def test_healthy_tick_pulls_are_counted_once(tmp_path, schedule):
    session, totals, spans = _traced(tmp_path, schedule, False)
    steps = len(_named(spans, trace.STEP))
    assert steps == totals["ticks"] - 1
    pulls = sum(s[3].get("pulls", 0) for s in spans)
    assert pulls == SYNC_PULLS[schedule] * steps
    totals_span = {trace.TOTALS} if schedule == "plain" else set()
    assert {s[0] for s in spans} == {trace.STEP, trace.DISPATCH,
                                     trace.SYNC} | totals_span


def test_receive_slots_are_pulled_once_a_call(healthy):
    session, totals, spans = healthy
    ep = session.ep
    (pull,) = _named(spans, trace.TOTALS)
    assert pull[3] == {"pulls": 1, "recv_slots": totals["recv_slots"],
                       "recv_budget": totals["ticks"] * ep.num_shards ** 2
                       * ep.route_capacity}
    assert 0 < totals["recv_slots"] <= pull[3]["recv_budget"]
    # it follows the last tick, whose sync skips the pull of ``accepted``
    syncs = _named(spans, trace.SYNC)
    assert [s[3]["pulls"] for s in syncs] == [4] * (len(syncs) - 1) + [3]
    assert pull[1] >= syncs[-1][2]


def test_each_call_pulls_its_receive_slots(tmp_path):
    session = _session("plain", False)
    session.step()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    calls = [session.tick_until_quiescent(5) for _ in range(3)]
    session.tick_until_quiescent()  # the rest
    quiet = session.tick_until_quiescent()  # quiescent: no tick, no pull
    jax.profiler.stop_trace()
    pulls = _named(_spans(str(tmp_path)), trace.TOTALS)
    assert len(pulls) == 4 and all(s[3]["pulls"] == 1 for s in pulls)
    per_tick = session.ep.num_shards ** 2 * session.ep.route_capacity
    # the first call's pull also holds the step before the trace
    assert [s[3]["recv_budget"] for s in pulls[:3]] == [
        6 * per_tick, 5 * per_tick, 5 * per_tick]
    assert sum(s[3]["recv_slots"] for s in pulls) == quiet["recv_slots"]
    assert calls[0]["recv_slots"] < calls[1]["recv_slots"] \
        < calls[2]["recv_slots"] < quiet["recv_slots"]


def test_recovery_log_bytes_equal_the_send_buffers(killed):
    session, totals, spans = killed
    ep = session.ep
    send = 2 * ep.num_shards * ep.num_shards * ep.route_capacity * 4
    logs = _named(spans, trace.LOG)
    assert len(logs) == totals["ticks"] - 1
    assert all(s[3] == {"pulls": 2, "bytes": send} for s in logs)
    snaps = _named(spans, trace.SNAPSHOT)
    every = session.cfg.checkpoint_every
    assert len(snaps) == len(range(every, totals["ticks"], every))
    state = session.state  # values int32, active bool, cursor int32
    assert all(s[3] == {"pulls": 3, "bytes": state.values.nbytes
                        + state.active.nbytes + state.cursor.nbytes}
               for s in snaps)


def test_kill_spans_count_the_kills_and_their_replay(killed):
    session, totals, spans = killed
    kills = _named(spans, trace.KILL)
    assert totals["failures"] == len(kills) == 4
    assert sum(s[3]["replayed"] for s in kills) == totals["replayed"] > 0
    assert all(s[3]["pulls"] >= 3 and s[3]["bytes"] > 0 for s in kills)
    # each kill re-counts the active frontier once, in its own sync span;
    # the last tick's pull of ``accepted`` is the totals span's instead
    syncs = _named(spans, trace.SYNC) + _named(spans, trace.TOTALS)
    assert sum(s[3]["pulls"] for s in syncs) == 4 * (totals["ticks"] - 1) \
        + len(kills)


def test_recovery_spans_only_with_a_plan(healthy, killed):
    recovery = {trace.LOG, trace.SNAPSHOT, trace.KILL}
    assert not recovery & {s[0] for s in healthy[2]}
    assert recovery <= {s[0] for s in killed[2]}


@pytest.mark.parametrize("plan", [False, True], ids=["healthy", "killed"])
def test_results_identical_with_the_profiler_on_and_off(tmp_path, plan):
    traced, totals, _ = _traced(tmp_path, "plain", plan)
    plain = _session("plain", plan)
    assert plain.tick_until_quiescent() == totals
    for a, b in zip(plain.state, traced.state):
        if a is not None:
            assert np.array_equal(np.asarray(a), np.asarray(b))
