"""The Graph500 Kronecker edge generator, drawn from a run's seed.

This follows the specification's reference generator (Graph500 spec,
section 3, "Graph Generation"; the Octave `kronecker_generator.m`): for each
of the ``edgefactor * 2**scale`` edges and each of the ``scale`` bit levels,
pick a quadrant of the adjacency matrix with probabilities A, B, C and
D = 1 - A - B - C; then permute the vertex labels at random and shuffle the
edge list.  The result keeps self-loops and duplicate edges, as the
specification's does; the system under test symmetrizes and deduplicates.

The benchmark makes its data here, and not with the program's own generator,
so that the reference it checks against takes nothing the program made.
"""
from __future__ import annotations

import numpy as np


def kronecker_edges(scale: int, edgefactor: int, initiator, seed: int
                    ) -> np.ndarray:
    """``[edgefactor * 2**scale, 2]`` int64 edge list (src, dst)."""
    a, b, c = (float(x) for x in initiator[:3])
    n = 1 << scale
    m = edgefactor * n
    rng = np.random.default_rng(seed)
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    ij = np.zeros((2, m), np.int64)
    for level in range(scale):
        ii = rng.random(m) > ab
        jj = rng.random(m) > np.where(ii, c_norm, a_norm)
        ij[0] += ii.astype(np.int64) << level
        ij[1] += jj.astype(np.int64) << level
    ij = rng.permutation(n)[ij]
    return np.ascontiguousarray(ij[:, rng.permutation(m)].T)
