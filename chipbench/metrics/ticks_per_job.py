"""Engine ticks to quiescence, averaged over the window's jobs (the
session's own ``totals["ticks"]``)."""


def read(ctx):
    jobs = ctx.get("jobs")
    if not jobs:
        return None
    return sum(j["ticks"] for j in jobs) / len(jobs)
