"""Share of device 0's busy time spent in the chunked state-space scan's
kernels: events of the operations line whose name starts with `ssd_`
(`ops/ssd.py` names its `pallas_call`s `ssd_fwd`, `ssd_bwd`). The
cumulative sums and transposes round them are unnamed XLA operations and
not in it."""

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(trace, spans, cell):
    busy = trace.busy_ns.get(0, 0)
    ssd = trace.time_by_prefix("ssd_")
    if not busy or not ssd:
        return None  # a program without the scan's kernels
    return 100.0 * ssd / busy
