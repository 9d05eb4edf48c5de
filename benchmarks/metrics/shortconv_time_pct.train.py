"""Share of device 0's busy time under the recurrent mixers' short
convolution scopes (`kda.conv`: the delta mixer's three convolutions, `silu`
and q's and k's norms; `ssm.conv`: the state-space mixer's convolution,
bias and `silu`), in every phase, kernels named `shortconv_*` with them, by
the program's own table of scopes (`lib/scopes.py`): XLA's elementwise
passes at PR 41's program, the `shortconv_fwd` / `shortconv_bwd` pair and
what XLA does round it since PR 42. None in a program with no such mixer."""

from benchmarks.lib import scopes

LAYER = "model step"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_span"

SCOPES = ("kda.conv", "ssm.conv")


def in_conv(comp: str) -> bool:
    return any(
        frame in SCOPES or frame.startswith("shortconv_")
        for frame in comp.split("/")
    )


def read(trace, spans, cell):
    return scopes.share(trace, cell, lambda comp, phase, kind: in_conv(comp))
