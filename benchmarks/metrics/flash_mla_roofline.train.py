"""The two-part flash attention kernels' share of their roofline on device
0: the least time the chip could take for the traced `flash_*mla*` calls'
needed FLOPs and bytes (`lib/flops_mla.mla_call_cost`: the triangle's pairs
at the products' true widths, 192 for the scores and 128 for the values;
the one rope key and its gradient once a call, not once a head) over the
device time those calls took. The shapes are the cell's. Not MXU occupancy:
a 64-lane product fills half the array, a diagonal block computes more
pairs than the triangle has, and the fused backward forms the scores
again. None where the program has no such call (the parent's)."""

from benchmarks.lib import flops, peaks

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(trace, spans, cell):
    try:
        from benchmarks.lib import flops_mla
    except ImportError:
        return None
    work, numbers = cell["workload"], cell["facts"].get("numbers", {})
    if not numbers.get("qk_rope_head_dim"):
        return None
    mesh = work["mesh"]
    shape = dict(
        batch=work["batch"] // (mesh.get("dp", 1) * mesh.get("fsdp", 1)),
        heads=numbers["num_attention_heads"], seq_len=work["seq_len"],
        nope=numbers["qk_nope_head_dim"], rope=numbers["qk_rope_head_dim"],
        v_dim=numbers["v_head_dim"],
    )
    peak = peaks.chip_peaks(cell["facts"]["device_kind"])
    least = took = 0.0
    for name, count in trace.op_count.get(0, {}).items():
        kind = flops_mla.mla_kernel_kind(name)
        if kind is None:
            continue
        seconds, _ = flops.least_seconds(
            *flops_mla.mla_call_cost(kind, **shape), peak
        )
        least += count * seconds
        took += trace.op_time_ns[0][name] / 1e9
    if not took:
        return None
    return 100.0 * least / took
