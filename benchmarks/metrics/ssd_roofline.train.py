"""The scan kernels' share of their roofline on device 0: the least time
the chip could take for the traced `ssd_*` calls' needed FLOPs and bytes
(`lib/flops_hybrid.ssd_call_cost`, from the configuration's shapes on one
device) over the device time those calls took."""

from benchmarks.lib import flops, flops_hybrid, peaks

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(trace, spans, cell):
    facts, work = cell["facts"], cell["workload"]
    numbers = facts.get("numbers", {})
    if "mamba_num_heads" not in numbers:
        return None  # a configuration without state-space layers
    peak = peaks.chip_peaks(facts["device_kind"])
    mesh = work["mesh"]
    shape = dict(
        batch=work["batch"] // (mesh.get("dp", 1) * mesh.get("fsdp", 1)),
        seq_len=work["seq_len"],
    )
    least = took = 0.0
    for name, count in trace.op_count.get(0, {}).items():
        kind = flops_hybrid.ssd_kernel_kind(name)
        if kind is None:
            continue
        seconds, _ = flops.least_seconds(
            *flops_hybrid.ssd_call_cost(kind, numbers, **shape), peak
        )
        least += count * seconds
        took += trace.op_time_ns[0][name] / 1e9
    if not took:
        return None
    return 100.0 * least / took
