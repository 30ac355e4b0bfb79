"""The engine's spans and scopes: names an operator can read in any
``jax.profiler`` trace (``docs/ARCHITECTURE.md``, "Spans and counters").

Host spans (``asymp:*``) are ``jax.profiler.TraceAnnotation`` events, on the
device trace's clock; their keyword args are counts the host already holds.
Open them in host code only, never inside a jitted function.  Device scopes
(``tick.*``) are ``jax.named_scope`` names, carried in each op's ``op_name``
metadata; ``dist/exchange.py`` spells ``tick.exchange`` itself, since
``repro.dist`` imports nothing from above it, and, on a mesh,
``tick.wire`` inside it: the cross-device ``all_to_all``.  ``SCOPES`` are
the phases every tick has.
"""
import jax

STEP = "asymp:session.step"          # one engine tick; arg tick
DISPATCH = "asymp:session.dispatch"  # the call of the jitted tick
SYNC = "asymp:session.sync"          # the step's device->host pulls; pulls
TOTALS = "asymp:session.totals"      # receive slots' pull; pulls, recv_*
LOG = "asymp:recovery.log"           # message-log pull; pulls, bytes
SNAPSHOT = "asymp:recovery.snapshot"  # checkpoint pull; pulls, bytes
KILL = "asymp:recovery.kill"         # a tick that kills; pulls, bytes, replayed

SCOPES = ("tick.select", "tick.fetch", "tick.route", "tick.exchange",
          "tick.receive")


def span(name: str, **counts) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation(name, **counts)


def pulled(*arrays) -> dict:
    """``pulls`` and ``bytes`` of pulling the device arrays among
    ``arrays`` to the host, from their shapes alone."""
    dev = [a for a in arrays if isinstance(a, jax.Array)]
    return {"pulls": len(dev), "bytes": sum(a.nbytes for a in dev)}
