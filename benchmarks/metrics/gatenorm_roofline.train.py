"""The recurrent mixers' gated norms' share of their roofline on device 0:
the least time the chip's memory could take for the bytes a step's gated
norms must move if each array is taken once
(`lib/flops_gatenorm.gatenorm_bytes`: by the CELL's shapes, 4 + 7 arrays a
state-space layer, 3 + 5 a delta layer; at 819 GB/s) over the time a step
spends under the `ssm.gate_norm` / `kda.gate_norm` scopes
(`gatenorm_time_pct.train`'s). The delta mixer's scope holds the gate's
two thin products (K = d_model onto head_dim onto H·d) too: their time is
in the denominator and their bytes are not in the count, so the share
reads low there, never high. Memory-bound by that count: a forward run
again under a checkpoint, a float32 array that reaches HBM or a copy of a
lane slice lowers it, nothing raises it over 100. None in a program with
no such mixer."""

from benchmarks.lib import (
    flops_gatenorm, loader, peaks, program_trace, scopes,
)

LAYER = "model step"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_span"


def read(trace, spans, cell):
    work = cell["workload"]
    mesh = work["mesh"]
    tokens = work["batch"] * work["seq_len"] // (
        mesh.get("dp", 1) * mesh.get("fsdp", 1)
    )
    needed = flops_gatenorm.gatenorm_bytes(
        cell["facts"].get("numbers", {}), tokens
    )
    if not needed:
        return None
    scoped = scopes.of_cell(trace, cell)
    steps = program_trace.steps_traced(trace)
    if scoped is None or not steps:
        return None
    under = loader.load_metric("gatenorm_time_pct.train").in_gate_norm
    took = sum(ns for (comp, _, _), ns in scoped.by.items() if under(comp))
    if not took:
        return None
    peak = peaks.chip_peaks(cell["facts"]["device_kind"])
    return 100.0 * (needed / peak.hbm_bytes_per_s) / (took / 1e9 / steps)
