"""Device idle inside the span ``asymp:session.dispatch`` (the call of the
jitted tick), per tick of the traced window, in ms."""
from chipbench import program_trace


def read(ctx):
    return program_trace.idle_ms(ctx, "asymp:session.dispatch")
