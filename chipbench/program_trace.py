"""The program's own spans and scopes in a traced run's profile.

The engine names its tick phases with ``jax.named_scope`` (``tick.select``,
``tick.fetch``, ``tick.route``, ``tick.exchange``, ``tick.receive``), which
reach each device op's ``op_name`` metadata, and opens host spans
``asymp:<name>`` (``jax.profiler.TraceAnnotation``) whose args count what
the host pulled from the device (``pulls``, ``bytes``, ``replayed``).  This
module reduces them, inside the benchmark's ``bench:window`` span:

* device time per ``tick.*`` scope (the innermost in the op's ``op_name``;
  ``outside`` where it has none), averaged over devices.  A TPU trace keeps
  an op's ``op_name`` as the ``tf_op`` stat of the op's event metadata,
  and each program's optimized HLO in its ``/host:metadata`` plane; a
  fusion whose root a compiler pass made has no ``op_name`` of its own and
  takes the scope of what it fuses (``op_scopes``);
* device idle split by the innermost span open at each instant: an
  ``asymp:`` span, else a ``bench:`` span, else ``outside``.  Each gap is
  split by its overlap with those spans, not given whole to its midpoint;
* the count of each span that starts in the window, and its args summed.

A run's trace is its newest ``.xplane.pb`` under ``.chipbench/trace/`` (the
run clears its cell's directory before it traces).  It is read once, kept
by path and modification time, and used only if its ``bench:window`` is as
long as the one ``trace_reduce`` read for the run.

    python3 -m chipbench.program_trace   # the newest trace's reduction
"""
from __future__ import annotations

import glob
import json
import math
import os
import re
import sys
from collections import Counter, defaultdict

from chipbench import spec, trace_reduce

TRACE_ROOT = os.path.join(spec.ROOT, ".chipbench", "trace")
SPAN = "asymp:"
ARGS = ("pulls", "bytes", "replayed")
# a path component, or its vmapped form ``vmap(tick.select)``
SCOPE = re.compile(r"(?:^|[/(])(tick\.[a-z]+)(?=[/)]|$)")
OUTSIDE = "outside"
_read: dict[str, dict | None] = {}


def scope(op_name: str) -> str:
    """The innermost ``tick.*`` scope of an ``op_name`` path."""
    found = SCOPE.findall(op_name)
    return found[-1] if found else OUTSIDE


def labels(spans, lo: float, hi: float) -> list[tuple[float, float, str]]:
    """``[lo, hi]`` cut into disjoint segments, each labelled with the
    innermost span ``(name, start, end, ...)`` open over it: the open span
    that started last, an ``asymp:`` span before a ``bench:`` one."""
    events = []
    for i, (name, s, e, *_) in enumerate(spans):
        s, e = max(s, lo), min(e, hi)
        if s < e:
            tier = 1 if name.startswith(SPAN) else 0
            events.append((s, 1, -e, tier, i))
            events.append((e, 0, 0, tier, i))
    events.append((hi, 0, 0, 0, -1))
    events.sort()
    out, open_, t = [], [], lo
    for seq, (at, starts, _, tier, i) in enumerate(events):
        if at > t:
            top = max(open_, default=None)
            name = spans[top[2]][0] if top else OUTSIDE
            if out and out[-1][2] == name:
                out[-1] = (out[-1][0], at, name)
            else:
                out.append((t, at, name))
            t = at
        if starts:
            open_.append((tier, seq, i))
        else:
            open_ = [x for x in open_ if x[2] != i]
    return out


def split(gaps, segments) -> dict[str, float]:
    """Each gap's length split over the labelled segments it overlaps;
    both lists sorted and disjoint."""
    out: dict[str, float] = defaultdict(float)
    j = 0
    for gs, ge in gaps:
        while j < len(segments) and segments[j][1] <= gs:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < ge:
            s, e, name = segments[k]
            out[name] += min(e, ge) - max(s, gs)
            k += 1
    return out


def reduce_events(devices: list[list[tuple[str, str, float, float]]],
                  spans: list[tuple[str, float, float, dict]],
                  window: tuple[float, float], top: int = 10) -> dict:
    """The reduction, on events in nanoseconds: ``devices`` holds one list
    of ``(op, op_name path, start, end)`` per device, ``spans`` the host
    spans ``(name, start, end, args)`` without the window's own."""
    lo, hi = window
    segments = labels(spans, lo, hi)
    per_scope, idle = defaultdict(float), defaultdict(float)
    outside_ops, scopes = defaultdict(float), {}
    for ops in devices:
        inside = [(op, path, max(s, lo), min(e, hi))
                  for op, path, s, e in ops if e > lo and s < hi]
        for op, path, s, e in inside:
            if path not in scopes:
                scopes[path] = scope(path)
            sc = scopes[path]
            per_scope[sc] += e - s
            if sc == OUTSIDE:
                outside_ops[op] += e - s
        merged = trace_reduce.merge((s, e) for _, _, s, e in inside)
        for name, v in split(trace_reduce.gaps(merged, lo, hi),
                             segments).items():
            idle[name] += v
    k = max(len(devices), 1)
    counts: dict[str, dict] = {}
    for name, s, _, args in spans:
        if name.startswith(SPAN) and lo <= s < hi:
            c = counts.setdefault(name, dict.fromkeys(("count",) + ARGS, 0))
            c["count"] += 1
            for a in ARGS:
                c[a] += args.get(a, 0)
    return {"window_s": (hi - lo) / 1e9, "devices": len(devices),
            "scope_s": {n: v / k / 1e9 for n, v in sorted(per_scope.items())},
            "idle_s": {n: v / k / 1e9 for n, v in sorted(idle.items())},
            "outside_ops": [[n, v / k / 1e9] for n, v in sorted(
                outside_ops.items(), key=lambda kv: -kv[1])[:top]],
            "spans": dict(sorted(counts.items()))}


def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def fields(buf):
    """``(field number, value)`` of a protobuf message: an int, or the
    bytes of a length-delimited field."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        else:  # fixed 64 or 32 bits
            n = 8 if wire == 1 else 4
            value, i = buf[i:i + n], i + n
        yield key >> 3, value


def _ints(value) -> list[int]:
    """A repeated int64 field, packed or not."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        x, i = _varint(value, i)
        out.append(x)
    return out


def metadata(xspace) -> dict[str, dict[str, dict]]:
    """``{plane: {event metadata name: {stat name: value}}}``: the stats
    that a trace keeps on each event's metadata, which ``ProfileData`` does
    not show (XSpace, XPlane, XEventMetadata and XStat by their field
    numbers)."""
    out = {}
    for f, plane in fields(memoryview(xspace)):
        parts = list(fields(plane)) if f == 1 else []
        names = {}
        for g, v in parts:
            if g == 5:  # stat_metadata: id -> XStatMetadata (name = 2)
                entry = dict(fields(v))
                names[entry.get(1)] = bytes(
                    dict(fields(entry.get(2, b""))).get(2, b"")).decode()
        events = {}
        for g, v in parts:
            meta = dict(fields(v)).get(2) if g == 4 else None
            if meta is None:  # event_metadata: id -> XEventMetadata
                continue
            name, stats = "", {}
            for h, w in fields(meta):
                if h == 2:
                    name = bytes(w).decode()
                elif h == 5:
                    stat = dict(fields(w))
                    key = names.get(stat.get(1), "")
                    if 5 in stat:
                        stats[key] = bytes(stat[5]).decode()
                    elif 7 in stat:  # a string kept as a stat name
                        stats[key] = names.get(stat[7], "")
                    elif 6 in stat:
                        stats[key] = bytes(stat[6])
                    else:
                        stats[key] = stat.get(3, stat.get(4))
            events[name] = stats
        name = next((bytes(v).decode() for g, v in parts if g == 2), "")
        if f == 1:
            out[name] = events
    return out


def hlo_scopes(module: bytes) -> dict[str, str]:
    """``{instruction: scope}`` of one HloModuleProto: an instruction's own
    ``op_name`` scope, else (a fusion whose root a compiler pass made) the
    scope most of the instructions it calls have."""
    calls, own, computations = {}, {}, {}
    for f, comp in fields(module):
        if f != 3:  # HloModuleProto.computations
            continue
        body = []
        for g, v in fields(comp):
            if g == 5:  # HloComputationProto.id
                computations[v] = body
            elif g == 2:  # instructions
                name, called, path = "", [], ""
                for h, w in fields(v):
                    if h == 1:
                        name = bytes(w).decode()
                    elif h == 7:  # metadata: OpMetadata.op_name = 2
                        path = bytes(dict(fields(w)).get(2, b"")).decode()
                    elif h == 38:  # called_computation_ids
                        called += _ints(w)
                body.append(name)
                own[name], calls[name] = scope(path), called

    def votes(ids, seen) -> Counter:
        out = Counter()
        for cid in ids:
            if cid in seen:
                continue
            seen.add(cid)
            for name in computations.get(cid, []):
                if own[name] != OUTSIDE:
                    out[own[name]] += 1
                out += votes(calls[name], seen)
        return out

    out = {}
    for name, sc in own.items():
        if sc == OUTSIDE:
            top = votes(calls[name], set()).most_common(1)
            sc = top[0][0] if top else OUTSIDE
        out[name] = sc
    return out


def op_scopes(meta: dict) -> dict[str, tuple[str, str]]:
    """``{device op: (scope, where the trace holds it)}``: the op's own
    ``op_name``, which a TPU trace keeps as the ``tf_op`` stat of the op's
    metadata; else its program's HLO, which the ``/host:metadata`` plane
    holds as ``Hlo Proto`` for the programs it saw, keyed ``name(id)`` by
    the op's ``program_id``."""
    programs = {}
    for name, stats in meta.get("/host:metadata", {}).items():
        found = re.search(r"\((\d+)\)$", name)
        if found and isinstance(stats.get("Hlo Proto"), bytes):
            # HloProto: hlo_module = 1
            programs[int(found.group(1))] = hlo_scopes(
                dict(fields(stats["Hlo Proto"])).get(1, b""))
    out = {}
    for plane, events in meta.items():
        if not trace_reduce.is_device(plane):
            continue
        for name, stats in events.items():
            tf_op = stats.get("tf_op")
            if isinstance(tf_op, str) and SCOPE.search(tf_op):
                out[name] = (scope(tf_op), "tf_op")
                continue
            instruction = trace_reduce.op_name(name).split(" ")[0]
            sc = programs.get(stats.get("program_id"), {}).get(instruction)
            out[name] = ((sc, "program HLO") if sc and sc != OUTSIDE
                         else (OUTSIDE, "none"))
    return out


def load(path: str) -> dict:
    """Reduce one ``.xplane.pb`` file (see ``op_scopes`` for where an
    op's scope comes from)."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    scopes = op_scopes(metadata(raw))
    devices, spans, window = [], [], None
    source: dict[str, float] = defaultdict(float)
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        for line in plane.lines:
            if trace_reduce.xla_ops(plane.name, line):
                ops, seen = [], {}
                for ev in line.events:
                    if ev.name not in seen:
                        seen[ev.name] = (trace_reduce.op_name(ev.name),
                                         *scopes.get(ev.name,
                                                     (OUTSIDE, "none")))
                    op, sc, where = seen[ev.name]
                    source[where] += ev.duration_ns
                    ops.append((op, sc, ev.start_ns, ev.end_ns))
                devices.append(ops)
            elif plane.name.startswith("/host:"):
                for ev in line.events:
                    if ev.name == trace_reduce.WINDOW and window is None:
                        window = (ev.start_ns, ev.end_ns)
                    elif ev.name.startswith((SPAN, trace_reduce.PREFIX)):
                        spans.append((ev.name, ev.start_ns, ev.end_ns,
                                      dict(ev.stats)))
    if window is None:
        raise ValueError(f"no {trace_reduce.WINDOW!r} span in {path}")
    out = reduce_events(devices, spans, window)
    k = max(len(devices), 1)
    out.update(path=path, scope_source_s={
        n: v / k / 1e9 for n, v in sorted(source.items())})
    return out


def newest() -> str | None:
    paths = glob.glob(os.path.join(TRACE_ROOT, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def read(ctx: dict) -> dict | None:
    """This run's reduction, or None where the run was not traced or the
    newest trace is not this run's."""
    trace, path = ctx.get("trace"), newest()
    if not trace or path is None:
        return None
    stat = os.stat(path)
    key = f"{path}:{stat.st_mtime_ns}:{stat.st_size}"
    if key not in _read:
        try:
            _read[key] = load(path)
        except Exception as e:  # noqa: BLE001 — a metric left out, not a
            # failed run
            print(f"chipbench: {path}: {type(e).__name__}: {e}",
                  file=sys.stderr)
            _read[key] = None
    out = _read[key]
    if out is None or not math.isclose(out["window_s"], trace["window_s"],
                                       rel_tol=1e-12):
        return None
    return out


def ticks(ctx: dict) -> int:
    return sum(j["ticks"] for j in ctx.get("jobs", []))


def phase_ms(ctx: dict, name: str) -> float | None:
    """Device time of the ops under scope ``name``, per tick, in ms."""
    red = read(ctx)
    if not red or not ticks(ctx) or name not in red["scope_s"]:
        return None
    return 1e3 * red["scope_s"][name] / ticks(ctx)


def idle_ms(ctx: dict, *names: str) -> float | None:
    """Device idle inside the spans ``names``, per tick, in ms; None where
    none of them was opened or no device was traced."""
    red = read(ctx)
    if (not red or not red["devices"] or not ticks(ctx)
            or not any(n in red["spans"] for n in names)):
        return None
    return 1e3 * sum(red["idle_s"].get(n, 0.0) for n in names) / ticks(ctx)


def arg_per_tick(ctx: dict, arg: str, prefix: str = SPAN) -> float | None:
    """The sum of ``arg`` over the spans whose names start with
    ``prefix``, per tick; None where no such span was opened."""
    red = read(ctx)
    got = [c[arg] for n, c in red["spans"].items()
           if n.startswith(prefix)] if red else []
    if not got or not ticks(ctx):
        return None
    return sum(got) / ticks(ctx)


def main() -> None:
    path = newest()
    if path is None:
        raise SystemExit(f"no trace under {TRACE_ROOT}")
    print(json.dumps(load(path), indent=1))


if __name__ == "__main__":
    main()
