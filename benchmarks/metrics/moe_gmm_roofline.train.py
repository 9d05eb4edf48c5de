"""The grouped matmul kernels' share of their roofline on device 0: the
least time the chip could take for the traced `moe_gmm_*` calls' needed
FLOPs and bytes (`lib/flops_moe.gmm_call_cost`) over the device time those
calls took. The rows of a call are the tokens the program's counters say a
layer routed to the experts held here (mean over the window's records),
never the expectation; which of a layer's three matmuls a call was cannot
be told from its name, and all three are `hidden x width` here."""

from benchmarks.lib import flops, flops_moe, peaks

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(trace, spans, cell):
    facts = cell["facts"]
    if "moe" not in facts:
        return None  # a program that reports no routing counters
    numbers = facts["numbers"]
    peak = peaks.chip_peaks(facts["device_kind"])
    shape = dict(
        rows=facts["moe"]["tokens_held_a_layer"],
        contract=numbers["hidden_size"], cols=numbers["moe_intermediate_size"],
        experts=numbers["num_experts"],
    )
    least = took = 0.0
    for name, count in trace.op_count.get(0, {}).items():
        kind = flops_moe.gmm_kernel_kind(name)
        if kind is None:
            continue
        seconds, _ = flops.least_seconds(
            *flops_moe.gmm_call_cost(kind, **shape), peak
        )
        least += count * seconds
        took += trace.op_time_ns[0][name] / 1e9
    if not took:
        return None
    return 100.0 * least / took
