"""The exchange substrate: one routing API over the engine's two transports.

The ASYMP engine produces, per shard, a pair of send buffers
``(values [Pn, cap], ids [Pn, cap])`` — row ``q`` holds the messages bound
for shard ``q``, ``ids`` are destination-local vertex slots (-1 = empty).
Delivery is a shard transpose: receiver ``q`` ends with row ``p`` from
every sender ``p``.  :func:`exchange_local` implements it over a block of
shards ``[P, Pn, cap]``:

  * with no mesh axis on the codec, the block is every shard and the
    transpose is ``swapaxes(0, 1)``;
  * with ``codec.axis_name`` (inside ``shard_map``), the block is one
    device's shards: one ``lax.all_to_all`` over the axis (the scope
    ``tick.wire``) first regroups the blocks by destination device, then
    the same transpose runs on what arrived.

Both run the *same* wire codec so their results are bit-identical:

  * ``none``  — int32 values + int32 ids (the raw baseline);
  * ``int16``/``int8`` — integer payloads (CC/BFS/label-prop labels,
    reachability bits) narrow losslessly when the value bound fits
    (sentinel = the program's aggregation identity), float payloads
    (SSSP distances, widest-path widths) quantize per destination row
    rounded in the aggregator's direction (ceil for min-monotone, floor
    for max-monotone — see ``compression.quantize_rows``): the
    self-stabilizing relaxation tolerates the lossy round-trip because a
    decoded value never crosses the fixpoint from the wrong side.  Ids
    narrow to int16 whenever the shard width fits.

``effective_compression`` is the gate — the single wire-safety decision
point: a requested mode that cannot be carried safely (e.g. int16 labels
on a 10^6-vertex graph, or ANY lossy mode under a non-idempotent
aggregator like pagerank's SUM, whose quantization error would compound
with every (+)) falls back to ``none`` rather than produce wrong
fixpoints; an unknown mode raises ``ValueError``.

**Deferred delivery (crowded-cluster emulation).**  Both transports also
come in a *delayed* flavour (:func:`exchange_local_delayed` /
:func:`exchange_dist_delayed`) that consults a per-link delay matrix from
``repro.dist.latency``: a send buffer produced at tick ``t`` for link
``p -> q`` is parked in a :class:`DelayRing` and delivered at tick
``t + delays[p, q]``.  The ring is indexed by *send* tick modulo its
length, with an explicit per-row due tick, so arbitrary time-varying
delays (fault-injected slowdowns that start and stop mid-run) can never
overwrite an in-flight message — a slot is only reused ``ring_len`` ticks
after it was written, by which time its occupant (delay <= ring_len - 1)
has been delivered.  Messages are never dropped, only deferred, so the
§3.3 self-stabilization argument (fixpoint invariant under delay and
reordering) applies and delayed runs converge to bit-identical fixpoints.

Layer contract: ``repro.dist`` sits below ``repro.core`` and
``repro.models``; this module imports only ``repro.dist`` siblings
(``compression``) and must never import from the layers above it.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.dist import compression as C

_INT_SENTINEL = {8: 127, 16: 32767}


def effective_compression(requested: str, value_kind: str,
                          max_int_value: int = 0,
                          idempotent: bool = True) -> str:
    """Gate a requested wire mode against what the payload can carry.

    THE wire-safety decision point: every subsystem that picks a wire
    mode (engine params, dry-run lowering, codec construction) routes
    through this function, so there is exactly one place the rules live:

    * an unknown mode is a config typo -> ``ValueError`` (never a bare
      assert — the message names the valid modes);
    * a non-idempotent aggregator (``idempotent=False``, e.g. pagerank's
      SUM) admits NO lossy mode: quantization error compounds with every
      (+) instead of being absorbed at the fixpoint, and neither ceil
      nor floor is a safe rounding direction for a sum -> ``"none"``;
    * int payloads ("int32": CC labels, BFS hops) only narrow when every
      real value stays below the sentinel code — otherwise distinct
      labels would alias and the fixpoint would change -> ``"none"``
      (an int8 request on a graph whose labels fit int16 degrades to
      int16 rather than all the way off);
    * float payloads under an idempotent aggregator always admit
      quantization (lossy but safe, see module docstring).
    """
    if requested in (None, "", "none"):
        requested = "none"
    elif requested not in ("int8", "int16"):
        raise ValueError(
            f"unknown wire_compression {requested!r}; "
            f"valid modes: 'none', 'int16', 'int8'")
    if requested == "none" or not idempotent:
        return "none"
    if value_kind == "float32":
        return requested
    bits = 8 if requested == "int8" else 16
    if max_int_value < _INT_SENTINEL[bits]:
        return requested
    if max_int_value < _INT_SENTINEL[16]:
        return "int16"  # requested int8 can't hold the labels; int16 can
    return "none"


@dataclasses.dataclass(frozen=True)
class WireCodec:
    """Static description of one exchange's wire format (hashable; closed
    over by jit alongside EngineParams)."""
    num_shards: int
    capacity: int
    compression: str  # effective: "none" | "int16" | "int8"
    value_kind: str  # "int32" | "float32"
    identity: float  # decode target for the sentinel code
    compress_ids: bool  # ids as int16 (requires vs <= 32766)
    # float rounding direction, from the program's aggregator: "up" keeps
    # min-monotone values from under-estimating, "down" keeps max-monotone
    # values from over-estimating (never cross the fixpoint)
    quantize_direction: str = "up"
    # the mesh axis the blocks cross (None: every shard on one device)
    axis_name: Optional[str] = None

    @property
    def bits(self) -> int:
        return 8 if self.compression == "int8" else 16

    # ------------------------------------------------------------------
    def encode(self, vals: jnp.ndarray
               ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
        if self.compression == "none":
            return vals, None
        if self.value_kind == "int32":
            return C.narrow_int(vals, self.bits, self.identity), None
        return C.quantize_rows(vals, self.bits, self.quantize_direction)

    def decode(self, payload: jnp.ndarray,
               scales: Optional[jnp.ndarray]) -> jnp.ndarray:
        if self.compression == "none":
            return payload
        if self.value_kind == "int32":
            return C.widen_int(payload, self.bits, self.identity, jnp.int32)
        return C.dequantize_rows(payload, scales, self.bits, self.identity,
                                 jnp.float32)

    def encode_ids(self, ids: jnp.ndarray) -> jnp.ndarray:
        return ids.astype(jnp.int16) if self.compress_ids else ids

    def decode_ids(self, ids: jnp.ndarray) -> jnp.ndarray:
        return ids.astype(jnp.int32) if self.compress_ids else ids

    # ------------------------------------------------------------------
    def wire_bytes_per_tick(self) -> int:
        """Bytes crossing the wire per tick, all shard pairs (stats only —
        the scale sidecar is counted, padding/empty slots are, too, since
        fixed-capacity buffers really do ship their full extent)."""
        slots = self.num_shards * self.num_shards * self.capacity
        if self.compression == "none":
            val_b, id_b, scale_b = 4, 4, 0
        else:
            val_b = 1 if self.compression == "int8" else 2
            id_b = 2 if self.compress_ids else 4
            scale_b = (4 if self.value_kind == "float32" else 0)
        per_pair_scale = self.num_shards * self.num_shards * scale_b
        return slots * (val_b + id_b) + per_pair_scale


def make_wire_codec(num_shards: int, capacity: int, vs: int,
                    requested: str, value_kind: str, identity,
                    max_int_value: int = 0,
                    quantize_direction: str = "up",
                    idempotent: bool = True,
                    axis_name: Optional[str] = None) -> WireCodec:
    mode = effective_compression(requested, value_kind, max_int_value,
                                 idempotent)
    return WireCodec(
        num_shards=num_shards, capacity=capacity, compression=mode,
        value_kind=value_kind, identity=float(identity)
        if value_kind == "float32" else int(identity),
        compress_ids=(mode != "none" and vs <= _INT_SENTINEL[16] - 1),
        quantize_direction=quantize_direction, axis_name=axis_name)


# ======================================================================
# Transports
# ======================================================================
@jax.named_scope("tick.wire")
def _across_devices(axis_name: str, *blocks):
    """One device's ``[Pd, Pn, ...]`` blocks (its senders, every
    destination shard) -> ``[Pn, Pd, ...]`` (every sender, this device's
    destinations): the one cross-device step, an ``all_to_all`` over
    ``axis_name``."""
    return [None if b is None
            else jax.lax.all_to_all(b, axis_name, 1, 0, tiled=True)
            for b in blocks]


@jax.named_scope("tick.exchange")
def exchange_local(codec: WireCodec, send_vals: jnp.ndarray,
                   send_ids: jnp.ndarray
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[P, Pn, cap] send buffers -> [Pn, P, cap] receive buffers.

    The encode/decode round-trip runs even though no wire is crossed, so
    local and distributed executions of the same codec are bit-identical
    (this is what lets single-device tests certify the production path).
    With ``codec.axis_name`` (inside ``shard_map``) the ``P`` senders are
    one device's block of ``Pd`` shards, and the result is ``[Pd, Pn,
    cap]``: each of the device's shards hears every sender of the mesh.
    """
    enc_v, scales = codec.encode(send_vals)
    enc_i = codec.encode_ids(send_ids)
    if codec.axis_name is not None:
        enc_v, enc_i, scales = _across_devices(codec.axis_name, enc_v,
                                               enc_i, scales)
    rv = jnp.swapaxes(enc_v, 0, 1)
    ri = jnp.swapaxes(enc_i, 0, 1)
    rs = jnp.swapaxes(scales, 0, 1) if scales is not None else None
    return codec.decode(rv, rs), codec.decode_ids(ri)


# ======================================================================
# Deferred delivery (crowded-cluster emulation — see module docstring)
# ======================================================================
class DelayRing(NamedTuple):
    """In-flight message store for the delayed transports.

    Local mode shapes: ``vals/ids [ring_len, P, Pn, cap]``,
    ``due [ring_len, P, Pn]``; dist mode drops the sender axis
    (each shard rings only its own sends): ``vals/ids
    [ring_len, Pn, cap]``, ``due [ring_len, Pn]``.  ``due == -1``
    marks an empty (or already-delivered) row."""

    vals: jnp.ndarray
    ids: jnp.ndarray
    due: jnp.ndarray


def init_delay_ring(max_delay: int, num_senders: int, num_shards: int,
                    capacity: int, identity, dtype) -> DelayRing:
    """An empty ring able to carry any per-link delay <= ``max_delay``.

    ``num_senders`` is ``P`` for the local transport (all shards in one
    array) and ``0`` for the per-shard dist transport (sender axis
    dropped)."""
    L1 = max_delay + 1
    lead = (L1, num_senders) if num_senders else (L1,)
    return DelayRing(
        jnp.full(lead + (num_shards, capacity), identity, dtype),
        jnp.full(lead + (num_shards, capacity), -1, jnp.int32),
        jnp.full(lead + (num_shards,), -1, jnp.int32))


def _ring_push_pop(ring: DelayRing, send_vals, send_ids, tick, delays,
                   identity, recv_gate=None):
    """Shared ring mechanics: park this tick's sends, surface every row
    whose due tick has arrived (masked to empty otherwise), retire it.

    ``recv_gate`` (optional, ``[Pn]`` bool) keys the pop on per-shard
    clocks — the async scheduler's contract: a due row is only surfaced
    (and retired) on a step its *receiver* fires, otherwise it stays
    parked.  The ring must then be sized ``max_delay + max_stall`` slots
    (not the synchronous ``max_delay + 1``): a due message can wait up
    to ``max_stall - 1`` extra steps for its receiver, and its slot must
    not be reused before it is consumed.  ``due`` broadcasts against a
    trailing receiver axis in both ring layouts (local ``[L, P, Pn]``,
    dist ``[L, Pn]``), so one gate expression serves both transports.

    Returns ``(deliver_vals, deliver_ids, ring', pending)`` where the
    deliverables keep the full ring extent (leading ``ring_len`` axis) —
    non-due rows carry the aggregation identity and ids of -1, which the
    receive phase drops, so delivery shape stays static under jit."""
    L1 = ring.vals.shape[0]
    slot = tick % L1
    vals = ring.vals.at[slot].set(send_vals)
    ids = ring.ids.at[slot].set(send_ids)
    due = ring.due.at[slot].set(tick + jnp.minimum(delays, L1 - 1))
    ready = (due >= 0) & (due <= tick)
    if recv_gate is not None:
        ready = ready & recv_gate  # [Pn] broadcasts onto the receiver axis
    dv = jnp.where(ready[..., None], vals, jnp.asarray(identity, vals.dtype))
    di = jnp.where(ready[..., None], ids, -1)
    due = jnp.where(ready, -1, due)
    pending = jnp.sum((ids >= 0) & (due >= 0)[..., None])
    return dv, di, DelayRing(vals, ids, due), pending


@jax.named_scope("tick.exchange")
def exchange_local_delayed(codec: WireCodec, ring: DelayRing,
                           send_vals: jnp.ndarray, send_ids: jnp.ndarray,
                           tick, delays, identity, recv_gate=None
                           ) -> Tuple[jnp.ndarray, jnp.ndarray, DelayRing,
                                      jnp.ndarray]:
    """Deferred-delivery local transport.

    ``send_vals/send_ids [P, Pn, cap]`` are parked in ``ring`` and every
    due row is delivered through the same wire codec as the immediate
    transport: receiver ``q`` gets ``[ring_len * P, cap]`` buffers whose
    row ``l * P + p`` is sender ``p``'s buffer from ring slot ``l`` (empty
    rows carry ids of -1).  ``delays [P, Pn]`` may change tick to tick
    (fault-injected slowdowns); values above the ring's capacity clamp.
    ``recv_gate [Pn]`` (async mode) keys delivery on the receivers'
    firing steps — see :func:`_ring_push_pop`.
    Returns ``(recv_vals, recv_ids, ring', pending)`` with ``pending`` =
    messages still in flight after this delivery."""
    dv, di, ring, pending = _ring_push_pop(ring, send_vals, send_ids, tick,
                                           delays, identity, recv_gate)
    L1, P_ = dv.shape[0], dv.shape[1]
    rv, ri = exchange_local(codec, dv.reshape((L1 * P_,) + dv.shape[2:]),
                            di.reshape((L1 * P_,) + di.shape[2:]))
    return rv, ri, ring, pending


@jax.named_scope("tick.exchange")
def exchange_dist_delayed(codec: WireCodec, ring: DelayRing,
                          send_vals: jnp.ndarray, send_ids: jnp.ndarray,
                          tick, delays_row, axis_name: str, identity,
                          recv_gate=None
                          ) -> Tuple[jnp.ndarray, jnp.ndarray, DelayRing,
                                     jnp.ndarray]:
    """Deferred-delivery dist transport (sender-side ring, must run inside
    ``shard_map``).

    Each shard parks its own ``[Pn, cap]`` sends (``delays_row [Pn]`` is
    its outgoing row of the delay matrix) and ships every due row through
    ``all_to_all`` each tick, so receive shapes stay static: the result is
    ``[ring_len * Pn, cap]`` with row ``l * Pn + q`` = sender ``q``'s ring
    slot ``l`` — the same row order (and the same codec arithmetic, hence
    bit-identical delivery) as :func:`exchange_local_delayed`.
    ``recv_gate [Pn]`` rides replicated (every sender needs the full
    firing vector to gate its per-receiver rows)."""
    dv, di, ring, pending = _ring_push_pop(ring, send_vals, send_ids, tick,
                                           delays_row, identity, recv_gate)
    a2a = lambda x: jax.lax.all_to_all(x, axis_name, 1, 1, tiled=True)
    enc_v, scales = codec.encode(dv)
    rv = a2a(enc_v)
    ri = a2a(codec.encode_ids(di))
    rs = a2a(scales) if scales is not None else None
    rv, ri = codec.decode(rv, rs), codec.decode_ids(ri)
    L1, Pn = rv.shape[0], rv.shape[1]
    return (rv.reshape((L1 * Pn,) + rv.shape[2:]),
            ri.reshape((L1 * Pn,) + ri.shape[2:]), ring, pending)
