"""Device time of the ops under ``tick.wire`` (the cross-chip all_to_all
inside ``tick.exchange``), per tick of the traced window and per chip, in
ms."""
from chipbench import program_trace


def read(ctx):
    return program_trace.phase_ms(ctx, "tick.wire")
