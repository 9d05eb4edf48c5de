"""The flash attention kernels' share of their roofline on device 0: the
least time the chip could take for the traced calls' needed FLOPs and
bytes (`lib/flops.flash_call_cost`, from the cell's shapes on one device)
over the device time those calls took."""

from benchmarks.lib import flops, peaks

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def per_device_shape(cell) -> dict:
    """What one device's attention call sees: the batch split over the
    data-parallel axes, the heads over `tp`."""
    work, numbers = cell["workload"], cell["facts"]["numbers"]
    mesh = work["mesh"]
    return dict(
        batch=work["batch"] // (mesh.get("dp", 1) * mesh.get("fsdp", 1)),
        heads=numbers["num_attention_heads"] // mesh.get("tp", 1),
        seq_len=work["seq_len"] // mesh.get("sp", 1),
        head_dim=numbers["head_dim"],
    )


def read(trace, spans, cell):
    peak = peaks.chip_peaks(cell["facts"]["device_kind"])
    shape = per_device_shape(cell)
    least = took = 0.0
    for name, count in trace.op_count.get(0, {}).items():
        kind = flops.flash_kernel_kind(name)
        if kind is None:
            continue
        seconds, _ = flops.least_seconds(
            *flops.flash_call_cost(kind, **shape), peak
        )
        least += count * seconds
        took += trace.op_time_ns[0][name] / 1e9
    if not took:
        return None
    return 100.0 * least / took
