"""The decay-a-head delta-rule kernels' share of their roofline on device 0:
the least time the chip could take for the traced `gdn_*` calls' needed
FLOPs and bytes (`lib/flops_gdn.gdn_call_cost`: the chunked algorithm's
products over the causal half of a chunk and against the state a value
head, q and k at the key heads, v, o and their gradients at the value heads,
g and b and their gradients at [tokens, heads] float32, the saved states
once each way; by the CELL's shapes, so a form that widens g or repeats q
and k reads low, never high) over the device time those calls took.
Memory-bound by that count. None in a program without those kernels (the
parent's)."""

from benchmarks.lib import flops, peaks

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(trace, spans, cell):
    try:
        from benchmarks.lib import flops_gdn
    except ImportError:
        return None
    facts, work = cell["facts"], cell["workload"]
    numbers = facts.get("numbers", {})
    chunk = facts.get("gdn", {}).get("chunk")
    if "linear_num_value_heads" not in numbers or not chunk:
        return None
    mesh = work["mesh"]
    shape = dict(
        batch=work["batch"] // (mesh.get("dp", 1) * mesh.get("fsdp", 1)),
        seq_len=work["seq_len"], chunk=chunk,
    )
    peak = peaks.chip_peaks(facts["device_kind"])
    least = took = 0.0
    for name, count in trace.op_count.get(0, {}).items():
        kind = flops_gdn.gdn_kernel_kind(name)
        if kind is None:
            continue
        seconds, _ = flops.least_seconds(
            *flops_gdn.gdn_call_cost(kind, numbers, **shape), peak
        )
        least += count * seconds
        took += trace.op_time_ns[0][name] / 1e9
    if not took:
        return None
    return 100.0 * least / took
