"""Share of device 0's busy time on XLA's own matmuls outside the head and
the loss: instructions of kind `matmul` (a `dot` or `convolution`, or a
fusion that holds one) whose path is not under `head`, `loss` or the
embedding's gradient: the projections, MLPs, shared experts, routers and
gates, in every phase. The Pallas kernels (`flash_*`, `moe_gmm_*`,
`ssd_*`) are kind `kernel` and not in it."""

from benchmarks.lib import scopes

LAYER = "model step"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_span"


def read(trace, spans, cell):
    return scopes.share(
        trace, cell,
        lambda comp, phase, kind: kind == "matmul"
        and not scopes.in_head_or_loss(comp, phase),
    )
