"""Share of device 0's busy time spent in the flash attention kernels:
events of the operations line whose name starts with `flash_`."""

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(trace, spans, cell):
    busy = trace.busy_ns.get(0, 0)
    flash = trace.time_by_prefix("flash_")
    if not busy or not flash:
        return None
    return 100.0 * flash / busy
