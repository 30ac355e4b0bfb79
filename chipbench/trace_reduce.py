"""From a ``jax.profiler`` trace (``.xplane.pb``) to device busy and idle
time, device time per operation, and idle gaps labelled by host span.

The benchmark wraps its window in the host span ``bench:window`` and each
call into a layer in ``bench:<layer call>`` (``jax.profiler.TraceAnnotation``).
Everything is measured inside the window span:

* busy: the length of the union of the intervals in which an operation ran
  on a device (each device plane's ``XLA Ops`` line), averaged over devices;
* device ops: summed device time per operation (HLO instruction name and
  result type), averaged over devices;
* idle gaps: the stretches of the window in which no operation ran, each
  attributed to the innermost benchmark span open at its midpoint
  (``outside`` where none is), summed per span.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

PREFIX = "bench:"
WINDOW = PREFIX + "window"
OP_LINE = "XLA Ops"


def is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and "CPU" not in plane_name


def op_name(text: str) -> str:
    """An op's name and result type from the HLO text a TPU trace gives
    as its event name: ``fusion.13 s32[299968]``."""
    if " = " not in text:
        return text
    lhs, rhs = text.split(" = ", 1)
    return f"{lhs.lstrip('%')} {rhs.split('{')[0].split(' ')[0]}"


def merge(intervals) -> list[tuple[float, float]]:
    """Union of ``(start, end)`` intervals, as sorted disjoint intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(t: float, spans) -> str:
    """The innermost span ``(name, start, end)`` that holds instant ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "outside"


def reduce_events(devices: list[list[tuple[str, float, float]]],
                  spans: list[tuple[str, float, float]],
                  window: tuple[float, float], top: int = 10) -> dict:
    """The reduction itself, on events in nanoseconds: ``devices`` holds
    one list of ``(op name, start, end)`` per device, ``spans`` the host
    spans without the window's own."""
    lo, hi = window
    busy, per_op, idle = 0.0, defaultdict(float), defaultdict(float)
    for ops in devices:
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
                  if e > lo and s < hi]
        merged = merge((s, e) for _, s, e in inside)
        busy += sum(e - s for s, e in merged)
        for n, s, e in inside:
            per_op[n] += e - s
        for s, e in gaps(merged, lo, hi):
            idle[label((s + e) / 2, spans)] += e - s
    k = max(len(devices), 1)

    def ranked(d):
        return [[n, v / k / 1e9] for n, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"busy_s": busy / k / 1e9, "window_s": (hi - lo) / 1e9,
            "devices": len(devices), "device_ops": ranked(per_op),
            "idle_gaps": ranked(idle)}


def xla_ops(plane_name: str, line) -> bool:
    """The lines whose events are device operations: ``XLA Ops`` of each
    accelerator plane."""
    return is_device(plane_name) and line.name == OP_LINE


def read(path: str, device_line=xla_ops) -> dict:
    """Reduce one ``.xplane.pb`` file (or the newest one under a profiler
    log directory).  ``device_line(plane name, line)`` picks the lines
    whose events are device operations, one line per device."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = sorted(glob.glob(os.path.join(
            path, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    data = ProfileData.from_file(path)
    spans, devices, window, lines = [], [], None, {}
    for plane in data.planes:
        if is_device(plane.name):
            lines[plane.name] = [line.name for line in plane.lines]
        for line in plane.lines:
            if device_line(plane.name, line):
                devices.append([(op_name(ev.name), ev.start_ns, ev.end_ns)
                                for ev in line.events])
            elif plane.name.startswith("/host:"):
                for ev in line.events:
                    if ev.name == WINDOW and window is None:
                        window = (ev.start_ns, ev.end_ns)
                    elif ev.name.startswith(PREFIX):
                        spans.append((ev.name[len(PREFIX):], ev.start_ns,
                                      ev.end_ns))
    if window is None:
        raise ValueError(f"no {WINDOW!r} span in {path}")
    out = reduce_events(devices, spans, window)
    out["lines"] = lines
    return out
