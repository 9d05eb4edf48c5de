"""Share of device 0's busy time spent in the gated delta rule's kernels:
events of the operations line whose name starts with `kda_` (`ops/kda.py`
names its `pallas_call`s `kda_fwd`, `kda_bwd`): the recurrence over chunks
and a chunk's products, the triangular inverse among them, forward and
backward. The cumulative decay before them and the projections,
convolutions, gates and norms round them are XLA's and not in it:
`kda_layer_time_pct.train` holds the whole mixer. None in a program without
those kernels (the parent's)."""

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(trace, spans, cell):
    busy = trace.busy_ns.get(0, 0)
    kda = trace.time_by_prefix("kda_")
    if not busy or not kda:
        return None
    return 100.0 * kda / busy
