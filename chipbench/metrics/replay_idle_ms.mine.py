"""Device idle inside the span ``asymp:recovery.kill`` (restore, replay or
boundary fallback, and re-upload of a killed shard), per tick of the traced
window, in ms."""
from chipbench import program_trace


def read(ctx):
    return program_trace.idle_ms(ctx, "asymp:recovery.kill")
