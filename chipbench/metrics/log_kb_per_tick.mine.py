"""Bytes that recovery pulls to the host: the ``bytes`` args of the
``asymp:recovery.*`` spans in the traced window, summed, in kB (1,000 bytes)
per tick."""
from chipbench import program_trace


def read(ctx):
    value = program_trace.arg_per_tick(ctx, "bytes", "asymp:recovery.")
    return None if value is None else value / 1e3
