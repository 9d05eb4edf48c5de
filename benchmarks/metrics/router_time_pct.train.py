"""Share of device 0's busy time under the scopes `moe.route` (the expert
layer's router: its float32 matmuls at `highest`, the top-k, the plan's
inputs) and `attn.gate` (the attention gate's float32 matmul, its sigmoid
and the product), in every phase. None in a program with neither."""

from benchmarks.lib import scopes

LAYER = "model step"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_span"


def read(trace, spans, cell):
    return scopes.share(
        trace, cell, lambda comp, phase, kind: scopes.in_router(comp)
    )
