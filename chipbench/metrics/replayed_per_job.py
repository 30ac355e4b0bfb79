"""Messages replayed by recovery, averaged over the window's jobs (the
session's own ``totals["replayed"]``); nothing where no shard was killed."""


def read(ctx):
    jobs = ctx.get("jobs")
    if not jobs or not any(j["failures"] for j in jobs):
        return None
    return sum(j["replayed"] for j in jobs) / len(jobs)
