"""Device time of the ops under ``tick.exchange`` (the wire codec and the
transpose or all_to_all), per tick of the traced window, in ms."""
from chipbench import program_trace


def read(ctx):
    return program_trace.phase_ms(ctx, "tick.exchange")
