"""Share of the receive buffers' slots that the tick's receive processed:
the ``recv_slots`` args of the ``asymp:session.totals`` spans that start in
the traced window, summed, over their ``recv_budget`` args (``P * P * cap``
slots for each tick they count), in %.  ``program_trace`` sums only the
args it knows, so this reader takes these two from the trace's host planes
itself."""
from chipbench import program_trace, trace_reduce

SPAN = "asymp:session.totals"


def read(ctx):
    red = program_trace.read(ctx)
    if not red or SPAN not in red["spans"]:
        return None
    from jax.profiler import ProfileData

    window, pulls = None, []
    for plane in ProfileData.from_file(red["path"]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == trace_reduce.WINDOW and window is None:
                    window = (ev.start_ns, ev.end_ns)
                elif ev.name == SPAN:
                    pulls.append((ev.start_ns, dict(ev.stats)))
    lo, hi = window
    inside = [args for start, args in pulls if lo <= start < hi]
    budget = sum(args.get("recv_budget", 0) for args in inside)
    if not budget:
        return None
    return 100 * sum(args.get("recv_slots", 0) for args in inside) / budget
