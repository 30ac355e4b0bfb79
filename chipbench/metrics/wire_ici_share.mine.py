"""The cross-chip all_to_all's share of the chip's ICI peak, in percent: the
bytes that leave a chip per tick (``wire_bytes_per_chip_tick`` in the
window's context) over the device time of ``tick.wire`` per tick, against
the peak below."""
from chipbench import program_trace

# one v5e chip's inter-chip interconnect: 1,600 Gbps = 200 GB/s (Google
# Cloud documentation, "TPU v5e", system architecture)
ICI_BYTES_PER_S = 200e9


def read(ctx):
    sent = ctx.get("wire_bytes_per_chip_tick")
    wire_ms = program_trace.phase_ms(ctx, "tick.wire")
    if not sent or not wire_ms:
        return None
    return 100.0 * sent / (wire_ms / 1e3 * ICI_BYTES_PER_S)
