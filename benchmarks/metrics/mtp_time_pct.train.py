"""Share of device 0's busy time on instructions whose path lies under the
`mtp` frame (the multi-token module: its projection `mtp.proj`, its block
with the block's usual scopes, its head `mtp.head` and its loss
`mtp.loss`), in every phase, by the program's own table of scopes
(`lib/scopes.py`): the module's whole share of a step. It is a cut ACROSS
the by-kind shares (`dense_matmul_time_pct.train`, `router_time_pct.train`,
`recompute_time_pct.train`, which hold the module's parts like any
layer's), as `kda_layer_time_pct.train` is, and is not to be added to
them; `head_loss_time_pct.train` reads a path's FIRST frame and so the main
head and loss alone. None in a program with no such module."""

from benchmarks.lib import scopes

LAYER = "model step"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_span"


def in_module(comp: str) -> bool:
    return comp.split("/")[0] == "mtp"


def read(trace, spans, cell):
    return scopes.share(trace, cell, lambda comp, phase, kind: in_module(comp))
