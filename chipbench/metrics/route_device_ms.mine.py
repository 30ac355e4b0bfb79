"""Device time of the ops under ``tick.route`` (bucketing by destination
shard into the send buffers, and the cursor advance), per tick of the traced
window, in ms."""
from chipbench import program_trace


def read(ctx):
    return program_trace.phase_ms(ctx, "tick.route")
