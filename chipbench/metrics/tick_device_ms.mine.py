"""Device busy time in the traced window over the engine ticks the window's
jobs ran, in milliseconds."""


def read(ctx):
    trace, jobs = ctx.get("trace"), ctx.get("jobs")
    if not trace or not jobs or not trace["busy_s"]:
        return None
    return 1e3 * trace["busy_s"] / sum(j["ticks"] for j in jobs)
