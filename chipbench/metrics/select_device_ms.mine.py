"""Device time of the ops under the engine's ``tick.select`` scope (the
priority-queue selection), per tick of the traced window, in ms."""
from chipbench import program_trace


def read(ctx):
    return program_trace.phase_ms(ctx, "tick.select")
