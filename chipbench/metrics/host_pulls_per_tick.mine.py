"""Device-to-host pulls per tick: the ``pulls`` args of every ``asymp:`` span
in the traced window, summed, over the window's ticks."""
from chipbench import program_trace


def read(ctx):
    return program_trace.arg_per_tick(ctx, "pulls")
