"""The window layers' flash attention kernels' share of their roofline on
device 0: the least time the chip could take for the traced
`flash_*window*` calls' needed FLOPs and bytes
(`lib/flops_window.window_call_cost`: the band's pairs, not the triangle's;
K, V, dK and dV once a K/V head) over the device time those calls took. The
shapes are the cell's: the query heads of its `sliding_attention` layers.
Not MXU occupancy: the kernels' edge blocks compute pairs the band does not
have (`flash_schedule(..., window=)`'s `computed_pairs_over_needed`) and the
fused backward forms the scores again."""

from benchmarks.lib import flops, flops_window, peaks

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def window_shape(cell) -> dict | None:
    """What one device's window attention call sees, or None where the
    configuration has no window layer."""
    work, numbers = cell["workload"], cell["facts"].get("numbers", {})
    kinds = numbers.get("layer_types", [])
    heads = {
        h for h, kind in zip(numbers.get("num_attention_heads_per_layer", []), kinds)
        if kind == "sliding_attention"
    }
    if len(heads) != 1 or not numbers.get("sliding_window"):
        return None
    mesh = work["mesh"]
    return dict(
        batch=work["batch"] // (mesh.get("dp", 1) * mesh.get("fsdp", 1)),
        heads=heads.pop(), kv_heads=numbers["num_key_value_heads"],
        seq_len=work["seq_len"], window=numbers["sliding_window"],
        head_dim=numbers["head_dim"],
    )


def read(trace, spans, cell):
    shape = window_shape(cell)
    if shape is None:
        return None
    peak = peaks.chip_peaks(cell["facts"]["device_kind"])
    least = took = 0.0
    for name, count in trace.op_count.get(0, {}).items():
        kind = flops_window.window_kernel_kind(name)
        if kind is None:
            continue
        seconds, _ = flops.least_seconds(
            *flops_window.window_call_cost(kind, **shape), peak
        )
        least += count * seconds
        took += trace.op_time_ns[0][name] / 1e9
    if not took:
        return None
    return 100.0 * least / took
