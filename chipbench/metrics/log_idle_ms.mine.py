"""Device idle inside the spans ``asymp:recovery.log`` and
``asymp:recovery.snapshot`` (the message-log and checkpoint pulls), per tick of
the traced window, in ms."""
from chipbench import program_trace


def read(ctx):
    return program_trace.idle_ms(ctx, "asymp:recovery.log",
                                 "asymp:recovery.snapshot")
