"""The residual streams' passes' share of their roofline on device 0: the
least time the chip's memory could take for the bytes a step's stream
passes must move if each is taken once (`lib/flops_mla.hc_bytes`: X read
and X' written a sublayer forward, X and dX' read and dX written backward,
the sublayer's input, output and their cotangents once; at 819 GB/s) over
the time a step spends under the `hc.*` scopes (`hc_time_pct.train`'s).
Memory-bound by that count; XLA's fusions read the streams once for the
norm and again for every mix, and the product with `phi` at `highest` is
matmul time, so the share says how many times over the least the passes
cost. None in a program with no streams."""

from benchmarks.lib import loader, peaks, program_trace, scopes

LAYER = "model step"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_span"


def read(trace, spans, cell):
    try:
        from benchmarks.lib import flops_mla
    except ImportError:
        return None
    numbers = cell["facts"].get("numbers", {})
    if not numbers.get("hc_mult"):
        return None
    scoped = scopes.of_cell(trace, cell)
    steps = program_trace.steps_traced(trace)
    if scoped is None or not steps:
        return None
    streams = loader.load_metric("hc_time_pct.train").in_streams
    took = sum(ns for (comp, _, _), ns in scoped.by.items() if streams(comp))
    if not took:
        return None
    work = cell["workload"]
    mesh = work["mesh"]
    tokens = work["batch"] * work["seq_len"] // (
        mesh.get("dp", 1) * mesh.get("fsdp", 1)
    )
    peak = peaks.chip_peaks(cell["facts"]["device_kind"])
    least = flops_mla.hc_bytes(numbers, tokens) / peak.hbm_bytes_per_s
    return 100.0 * least / (took / 1e9 / steps)
