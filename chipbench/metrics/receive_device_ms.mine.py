"""Device time of the ops under ``tick.receive`` (the scatter of the
program's aggregator), per tick of the traced window, in ms."""
from chipbench import program_trace


def read(ctx):
    return program_trace.phase_ms(ctx, "tick.receive")
