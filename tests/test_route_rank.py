"""The routing rank: dense prefix sums against a stable argsort.

``engine._route_rank`` gives each message its slot in its destination
shard's send buffer: the number of earlier slots, in flat ``(m, d)`` order,
bound for the same shard.  It counts with prefix sums over a one-hot of the
destination shard.  It must give a stable argsort's ranks on every slot
that carries a message, and so the same send buffers, cursors and frontiers
under backpressure as the argsort rank it replaced.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import GraphConfig
from repro.core import engine as E
from repro.core import graph as G
from repro.core import programs as PR

def _reference_rank(dst_shard: np.ndarray, Pn: int) -> np.ndarray:
    """Rank by a NumPy stable argsort over the flat slots."""
    flat = dst_shard.reshape(-1)
    order = np.argsort(flat, kind="stable")
    starts = np.searchsorted(flat[order], np.arange(Pn + 1))
    rank = np.empty_like(flat)
    rank[order] = np.arange(flat.size) - starts[flat[order]]
    return rank.reshape(dst_shard.shape)


def _argsort_rank(dst_shard, Pn: int):
    """The same rank in JAX by an argsort, three gathers and an
    inverse-permutation scatter: the engine's rank before the counting
    sort."""
    flat = dst_shard.reshape(-1)
    order = jnp.argsort(flat)
    so = flat[order]
    starts = jnp.searchsorted(so, jnp.arange(Pn + 1))
    rank_sorted = jnp.arange(flat.shape[0]) - starts[so]
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0]))
    return rank_sorted[inv].reshape(dst_shard.shape)


@pytest.mark.parametrize("invalid", [0.9, 0.3])
# 8 shards as the benchmark cells, 256 as the production dry-run
@pytest.mark.parametrize("Pn", [1, 2, 8, 32, 256])
def test_rank_matches_a_stable_argsort(Pn, invalid):
    rng = np.random.default_rng(Pn * 100 + int(invalid * 10))
    M, D = 97, 16
    dst = rng.integers(0, Pn, (M, D), dtype=np.int32)
    dst[rng.random((M, D)) < invalid] = Pn  # no message in the slot
    rank = np.asarray(jax.jit(E._route_rank, static_argnums=1)(
        jnp.asarray(dst), Pn))
    assert rank.dtype == np.int32 and rank.shape == (M, D)
    valid = dst < Pn
    assert valid.any()
    np.testing.assert_array_equal(rank[valid],
                                  _reference_rank(dst, Pn)[valid])


def _phase1(prog, ep, g, states):
    """``_phase1_create``'s outputs for each state, vmapped over shards as
    the local tick runs it."""
    push = not prog.aggregator.idempotent
    w = g.weights if prog.weighted else None
    f = jax.jit(jax.vmap(
        lambda v, a, c, r, ci, wt, s, ax: E._phase1_create(
            prog, ep, v, a, c, r, ci, wt, s, aux=ax),
        in_axes=(0, 0, 0, 0, 0, 0 if prog.weighted else None, 0,
                 0 if push else None)))
    return [jax.device_get(f(st.values, st.active, st.cursor, g.row_ptr,
                             g.col_idx, w, jnp.arange(ep.num_shards),
                             st.aux if push else None))
            for st in states]


@pytest.mark.parametrize("algorithm", ["cc", "pagerank"])
def test_phase1_is_bit_identical_on_both_paths(monkeypatch, algorithm):
    """Under a starved ``route_capacity`` (drops every tick, cursors that
    stop mid-list, push mode's shipped prefix) the counting rank and the
    argsort rank give the same send buffers, cursors, frontier and
    sent/fetched counts."""
    cfg = GraphConfig(name="t-rank", algorithm=algorithm, num_vertices=256,
                      avg_degree=6, generator="rmat", num_shards=4,
                      enforce_fraction=1.0, seed=3)
    graph = G.build_sharded_graph(cfg)
    prog = PR.get_program(cfg)
    ep = dataclasses.replace(E.default_params(cfg, graph, prog),
                             route_capacity=4)
    dg = E.to_device_graph(graph)
    tick = E.make_local_tick(prog, ep, prog.weighted)
    states = [E.init_state(prog, graph)]
    for _ in range(12):
        states.append(tick(states[-1], dg)[0])

    counted = _phase1(prog, ep, dg, states)
    monkeypatch.setattr(E, "_route_rank", _argsort_rank)
    sorted_ = _phase1(prog, ep, dg, states)

    drops = 0
    for a, b in zip(counted, sorted_):
        # active, cursor, send_vals, send_ids, sent, fetched, values, aux
        for x, y in zip(a, b):
            if x is not None:
                np.testing.assert_array_equal(x, y)
        drops += int(np.sum(a[5]) - np.sum(a[4]))
    assert drops > 0  # the capacity really starved the router
