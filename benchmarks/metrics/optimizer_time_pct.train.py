"""Share of device 0's busy time on instructions whose phase is `update`:
under the trainer's `optimizer` scope. A weight gradient's matmul with
AdamW fused into it counts as the matmul's (`backward`), so this is the
update that is NOT fused into a gradient; the `[scopes]` line gives the
fused part beside it (`optimizer_in_matmul_ms`)."""

from benchmarks.lib import scopes

LAYER = "model step"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_span"


def read(trace, spans, cell):
    return scopes.share(trace, cell, lambda comp, phase, kind: phase == "update")
