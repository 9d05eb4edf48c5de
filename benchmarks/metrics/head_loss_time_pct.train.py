"""Share of device 0's busy time under the scopes `head` (the logits'
matmul forward and backward, with the head's weight gradient), `loss`
(`softmax_cross_entropy` over the float32 logits) and, backward only,
`embed` (the embedding's gradient, which a tied head shares): what the
step spends outside every layer on the vocabulary."""

from benchmarks.lib import scopes

LAYER = "model step"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_span"


def read(trace, spans, cell):
    return scopes.share(
        trace, cell,
        lambda comp, phase, kind: scopes.in_head_or_loss(comp, phase),
    )
