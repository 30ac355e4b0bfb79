"""The plain tick's receive over the occupied prefix of its buffers.

``_receive_prefix`` runs the idempotent receive on the narrowest static
width (``receive_widths``) that holds every valid id of the block.  It must
give the full-width receive's values, frontier, cursors and accepted
counts bit for bit: for int min (cc), float min (weighted sssp) and float
max (widest path), at every extent around each width, on buffers with
holes, on one device and inside ``shard_map`` on a one-device mesh.  A
whole job takes the same ticks, messages and labels as the full-width
path, and the session counts the slots it processed.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import GraphConfig
from repro.core import engine as E
from repro.core import graph as G
from repro.core import programs as PR
from conftest import csr_edges

PN, VS, CAP = 4, 64, 1100
WIDTHS = (128, 256, CAP)
PROGRAMS = {"cc": PR.connected_components, "sssp": PR.sssp,
            "widest_path": PR.widest_path}
# one past the last valid position of the block; "holes" has ids spread
# over the rows with empty slots between them
EXTENTS = [0, 1, 127, 128, 129, 255, 256, 257, CAP - 1, CAP, "holes"]


def _params(cap: int = CAP) -> E.EngineParams:
    return E.EngineParams(num_shards=PN, vs=VS, max_vertices_per_tick=16,
                          degree_window=4, route_capacity=cap,
                          enforce_fraction=1.0, priority="disabled",
                          priority_scale=1.0)


def _block(prog, extent, seed: int = 0):
    """A receiver block: state ``[PN, VS]`` and buffers ``[PN, PN, CAP]``
    whose valid ids end exactly at ``extent``."""
    rng = np.random.default_rng(seed)
    if prog.dtype == "int32":
        values = rng.integers(0, 256, (PN, VS)).astype(np.int32)
        rv = rng.integers(0, 256, (PN, PN, CAP)).astype(np.int32)
    else:
        values = rng.uniform(0, 10, (PN, VS)).astype(np.float32)
        values[rng.random((PN, VS)) < 0.2] = prog.identity
        rv = rng.uniform(0, 10, (PN, PN, CAP)).astype(np.float32)
    ids = rng.integers(0, VS, (PN, PN, CAP)).astype(np.int32)
    last = None
    if extent == "holes":
        ri = np.where(rng.random((PN, PN, CAP)) < 0.01, ids, -1)
        ri[:, :, 701:] = -1
        last = (1, 2, 700)
    else:
        ri = np.where((np.arange(CAP) < extent) & (rng.random(
            (PN, PN, CAP)) < 0.5), ids, -1)
        if extent:
            last = (2, 3, extent - 1)
    if last is not None:  # the last message improves its vertex
        ri[last] = ids[last]
        rv[last] = -1 if prog.aggregator.name == "min" else 1000
    rv = np.where(ri >= 0, rv, prog.identity).astype(rv.dtype)
    active = rng.random((PN, VS)) < 0.3
    cursor = rng.integers(0, 5, (PN, VS)).astype(np.int32)
    return tuple(map(jnp.asarray, (values, active, cursor, rv, ri)))


def _full_width(prog, ep, values, active, cursor, rv, ri):
    return jax.jit(jax.vmap(lambda v, a, c, rvals, rids: E._phase2_receive(
        prog, ep, v, a, c, rvals, rids)))(values, active, cursor, rv, ri)


def _on_one_device(prog, ep, *block):
    return jax.jit(lambda *b: E._receive_prefix(prog, ep, *b))(*block)


def _on_a_mesh(prog, ep, *block):
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("workers",))
    rows = P("workers")
    fn = shard_map(lambda *b: E._receive_prefix(prog, ep, *b), mesh=mesh,
                   in_specs=(rows,) * 5,
                   out_specs=(rows, rows, rows, rows, P()), check_vma=False)
    return jax.jit(fn)(*block)


def test_widths_follow_the_capacity():
    assert E.receive_widths(9761) == (256, 1280, 9761)
    assert E.receive_widths(19045) == (384, 2432, 19045)
    assert E.receive_widths(CAP) == WIDTHS
    assert E.receive_widths(640) == (128, 640)
    assert E.receive_widths(80) == (80,)


@pytest.mark.parametrize("place", [_on_one_device, _on_a_mesh],
                         ids=["one_device", "mesh"])
@pytest.mark.parametrize("extent", EXTENTS, ids=str)
@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_prefix_receive_equals_the_full_width(name, extent, place):
    prog, ep = PROGRAMS[name](), _params()
    block = _block(prog, extent)
    want = _full_width(prog, ep, *block)
    *got, slots = place(prog, ep, *block)
    for w, g in zip(want, got):
        assert w.dtype == g.dtype
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g))
    reach = 701 if extent == "holes" else extent
    width = min(w for w in WIDTHS if w >= reach)
    assert int(slots) == width * PN * PN


def _job_cfg() -> GraphConfig:
    # scale 9; its rows fill all three widths of a 1,100-slot capacity
    return GraphConfig(name="t-recv", algorithm="cc", num_vertices=512,
                       avg_degree=32, num_shards=4, seed=5, max_ticks=400,
                       route_capacity=CAP)


@pytest.fixture(scope="module")
def job():
    cfg = _job_cfg()
    graph = G.build_sharded_graph(cfg)
    session = E.EngineSession(cfg, graph=graph)
    return graph, session, session.tick_until_quiescent()


def test_a_whole_job_equals_the_full_width_path(job, monkeypatch):
    graph, session, totals = job
    monkeypatch.setattr(E, "receive_widths", lambda cap: (cap,))
    full = E.EngineSession(_job_cfg(), graph=graph)
    want = full.tick_until_quiescent()
    assert totals["converged"] and want["converged"]
    for key in ("ticks", "sent", "accepted", "fetched"):
        assert totals[key] == want[key], key
    labels = np.asarray(session.state.values).reshape(-1)
    np.testing.assert_array_equal(
        labels, np.asarray(full.state.values).reshape(-1))
    n = graph.num_real_vertices
    np.testing.assert_array_equal(
        labels[:n], G.cc_oracle(n, csr_edges(graph)))
    ep = session.ep
    assert want["recv_slots"] == want["ticks"] * PN * PN * ep.route_capacity


def test_the_session_counts_the_widths_it_took(job):
    """``totals["recv_slots"]`` is the sum over the ticks of the width
    each took, times P x P, where the width is the narrowest that holds the
    tick's last valid id (read here from the send buffers, whose transpose
    the receive buffers are)."""
    graph, session, totals = job
    prog, ep = session.prog, session.ep
    tick = E.make_local_tick(prog, ep, prog.weighted)
    state, g = E.init_state(prog, graph), E.to_device_graph(graph)
    taken = []
    for _ in range(totals["ticks"]):
        state, stats, (_, si) = tick(state, g)
        occupied = np.nonzero((np.asarray(si) >= 0).any(axis=(0, 1)))[0]
        reach = occupied.max() + 1 if occupied.size else 0
        taken.append(min(w for w in WIDTHS if w >= reach))
    assert int(stats.active) == 0
    assert set(taken) == set(WIDTHS)
    assert totals["recv_slots"] == sum(taken) * PN * PN
    assert totals["recv_slots"] <= totals["ticks"] * PN * PN * CAP


def test_a_fork_carries_the_uncounted_slots():
    cfg = _job_cfg()
    a = E.EngineSession(cfg)
    for _ in range(3):
        a.step()
    b = a.fork()
    assert b.totals_snapshot()["recv_slots"] \
        == a.totals_snapshot()["recv_slots"] > 0


@pytest.mark.parametrize("on_mesh", [False, True], ids=["one_device", "mesh"])
def test_nothing_compiles_after_the_first_tick(on_mesh):
    """The benchmark warms a cell up with one tick: the sum of the receive
    slots must compile there, with the tick, and never in a job."""
    from repro.launch.mesh import make_worker_mesh
    compiles = []

    def count(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(count)
    try:
        template = E.EngineSession(
            _job_cfg(), mesh=make_worker_mesh(1) if on_mesh else None)
        template.fork().step()
        warm = len(compiles)
        template.fork().tick_until_quiescent()
        assert warm > 0 and len(compiles) == warm
    finally:
        jax.monitoring.unregister_event_duration_listener(count)
