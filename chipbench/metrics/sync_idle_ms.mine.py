"""Device idle inside the span ``asymp:session.sync`` (the step's pulls of
the tick's counts to the host), per tick of the traced window, in ms."""
from chipbench import program_trace


def read(ctx):
    return program_trace.idle_ms(ctx, "asymp:session.sync")
