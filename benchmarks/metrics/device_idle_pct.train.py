"""Share of the traced window in which no operation ran on device 0."""

LAYER = "device"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(trace, spans, cell):
    return 100.0 * trace.idle_share(0)
