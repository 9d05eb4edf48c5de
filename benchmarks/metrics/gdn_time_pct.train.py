"""Share of device 0's busy time spent in the delta rule's decay-a-head
kernels: events of the operations line whose name starts with `gdn_`
(`ops/kda.py` names that form's `pallas_call`s `gdn_fwd`, `gdn_bwd`; the
channel form's are `kda_*` and `kda_time_pct.train`'s): the recurrence over
chunks and a chunk's products, the triangular inverse among them, forward
and backward. The projections, convolutions, gates and norms round them are
not in it: `kda_layer_time_pct.train` holds the whole mixer. None in a
program without those kernels (the parent's)."""

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(trace, spans, cell):
    busy = trace.busy_ns.get(0, 0)
    gdn = trace.time_by_prefix("gdn_")
    if not busy or not gdn:
        return None
    return 100.0 * gdn / busy
