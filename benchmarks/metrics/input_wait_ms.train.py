"""What the device did, or waited for, between two steps: from the end of one
execution of the step program on device 0 to the start of the next, mean
over the traced steps. It holds the feed's own program and any wait for the
host. (The host-side span round `next()` is not this: the runtime lets the
host run about one step ahead, so `next()` blocks for a whole step while the
device is busy, and that span reads the step time.)"""

LAYER = "train loop"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(trace, spans, cell):
    gaps = trace.between_runs_ns(0)
    if not gaps:
        return None
    return sum(gaps) / len(gaps) / 1e6
