"""Share of device 0's busy time under the recurrent mixers' gated-norm
scopes (`ssm.gate_norm`: the state-space mixer's `silu` gate and its
groups' RMS norm with the learned scale; `kda.gate_norm`: the delta
mixer's gate's two thin products, a head's RMS norm and the sigmoid gate),
in every phase, kernels named `gatenorm_*` with them, by the program's own
table of scopes (`lib/scopes.py`): XLA's elementwise passes at PR 42's
program, the `gatenorm_fwd` / `gatenorm_bwd` pair and what XLA does round
it since PR 43. None in a program with no such mixer."""

from benchmarks.lib import scopes

LAYER = "model step"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_span"

SCOPES = ("kda.gate_norm", "ssm.gate_norm")


def in_gate_norm(comp: str) -> bool:
    return any(
        frame in SCOPES or frame.startswith("gatenorm_")
        for frame in comp.split("/")
    )


def read(trace, spans, cell):
    return scopes.share(
        trace, cell, lambda comp, phase, kind: in_gate_norm(comp)
    )
