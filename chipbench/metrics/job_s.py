"""Wall time of a whole job, from the initial state on the device to
verified quiescence with the merger's table on the host: the sum of the
window's job walls over the number of jobs (host clock)."""


def read(ctx):
    jobs = ctx.get("jobs")
    if not jobs:
        return None
    return sum(j["wall_s"] for j in jobs) / len(jobs)
