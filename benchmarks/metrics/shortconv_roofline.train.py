"""The recurrent mixers' short convolutions' share of their roofline on
device 0: the least time the chip's memory could take for the bytes a
step's convolutions must move if each array is taken once
(`lib/flops_shortconv.shortconv_bytes`: by the CELL's shapes, the
projection read and the result written forward, the projection and the
cotangent read and the gradient written backward; at 819 GB/s) over the
time a step spends under the `kda.conv` / `ssm.conv` scopes
(`shortconv_time_pct.train`'s). Memory-bound by that count: a forward run
again under a checkpoint, a float32 array that reaches HBM or a pass a tap
lowers it, nothing raises it over 100. None in a program with no such
mixer."""

from benchmarks.lib import loader, peaks, program_trace, scopes

LAYER = "model step"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_span"


def read(trace, spans, cell):
    try:
        from benchmarks.lib import flops_shortconv
    except ImportError:
        return None
    work = cell["workload"]
    mesh = work["mesh"]
    tokens = work["batch"] * work["seq_len"] // (
        mesh.get("dp", 1) * mesh.get("fsdp", 1)
    )
    needed = flops_shortconv.shortconv_bytes(
        cell["facts"].get("numbers", {}), tokens
    )
    if not needed:
        return None
    scoped = scopes.of_cell(trace, cell)
    steps = program_trace.steps_traced(trace)
    if scoped is None or not steps:
        return None
    in_conv = loader.load_metric("shortconv_time_pct.train").in_conv
    took = sum(ns for (comp, _, _), ns in scoped.by.items() if in_conv(comp))
    if not took:
        return None
    peak = peaks.chip_peaks(cell["facts"]["device_kind"])
    return 100.0 * (needed / peak.hbm_bytes_per_s) / (took / 1e9 / steps)
