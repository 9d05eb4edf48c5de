"""Share of device 0's busy time under the residual streams' scopes
(`hc.maps`: the norm, the product with `phi`, the sigmoids and the Sinkhorn
iterations; `hc.pre`: the weighted sum into a sublayer; `hc.post`: the
streams mixed and the sublayer's output added; `hc.entry` and `hc.exit`),
in every phase, by the program's own table of scopes (`lib/scopes.py`);
kernels named `hc_*` would count with them. None in a program with no
streams."""

from benchmarks.lib import scopes

LAYER = "model step"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_span"


def in_streams(comp: str) -> bool:
    return any(frame.startswith(("hc.", "hc_")) for frame in comp.split("/"))


def read(trace, spans, cell):
    return scopes.share(
        trace, cell, lambda comp, phase, kind: in_streams(comp)
    )
