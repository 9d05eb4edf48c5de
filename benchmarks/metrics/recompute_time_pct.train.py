"""Share of device 0's busy time on instructions whose phase is
`recompute`: under `rematted_computation` inside a `transpose(`, the
forward run again in the backward, every kind, kernels too. The price of
`remat_policy`, which `mfu_pct.train` does not count. None under `remat:
none` (no such phase in the program)."""

from benchmarks.lib import scopes

LAYER = "model step"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_span"


def read(trace, spans, cell):
    return scopes.share(
        trace, cell, lambda comp, phase, kind: phase == "recompute"
    )
