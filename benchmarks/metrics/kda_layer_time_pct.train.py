"""Share of device 0's busy time under the delta-rule mixer's scopes
(`kda.proj`, `kda.conv`, `kda.gates`, `kda.scan`, `kda.gate_norm`,
`kda.out_proj`), in every phase, kernels named `kda_*` with them, by the
program's own table of scopes (`lib/scopes.py`): the mixer's whole share,
as `hc_time_pct.train` is the streams'. None in a program with no such
mixer."""

from benchmarks.lib import scopes

LAYER = "model step"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_span"


def in_mixer(comp: str) -> bool:
    return any(
        frame == "kda" or frame.startswith(("kda.", "kda_"))
        for frame in comp.split("/")
    )


def read(trace, spans, cell):
    return scopes.share(trace, cell, lambda comp, phase, kind: in_mixer(comp))
