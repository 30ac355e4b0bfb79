"""``repro.dist`` — the distribution substrate every other layer builds on.

Four small modules, layered bottom-up:

  * :mod:`repro.dist.sharding`    — *where data lives*: logical-axis ->
    mesh-axis resolution for parameters/activations (``ShardingRules``),
    and the contiguous-range vertex partition used by the graph engine
    (``vertex_partition``).  Both produce disjoint, deterministic,
    covering shards with divisibility fallback.
  * :mod:`repro.dist.compression` — *what goes on the wire*: int8/int16
    quantized buffers, error-feedback helpers, compressed psum.
  * :mod:`repro.dist.latency`     — *how long the wire takes*: seeded
    per-link delay / per-shard throttle models for crowded-cluster
    emulation (paper §5.4).
  * :mod:`repro.dist.exchange`    — *how it moves*: one routing API over
    the engine's two transports (single-device transpose, ``all_to_all``
    over a workers mesh) with optional wire compression and, for crowded
    runs, the deferred-delivery ring that consults the latency model.

Submodules are imported explicitly (``from repro.dist import exchange``)
rather than re-exported here: the package sits below ``repro.core`` and
``repro.models`` in the layering and must stay import-cycle-free —
nothing in this package may import from ``repro.core``, ``repro.models``
or any other layer above it.
"""
