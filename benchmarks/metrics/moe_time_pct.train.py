"""Share of device 0's busy time spent in the expert layer's grouped
matmul kernels: events of the operations line whose name starts with
`moe_` (`ops/moe.py` names its `pallas_call`s `moe_gmm_*`). The dispatch
and combine gathers around them are unnamed XLA operations and not in it."""

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(trace, spans, cell):
    busy = trace.busy_ns.get(0, 0)
    moe = trace.time_by_prefix("moe_")
    if not busy or not moe:
        return None
    return 100.0 * moe / busy
