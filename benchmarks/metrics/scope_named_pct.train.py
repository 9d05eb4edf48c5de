"""Share of device 0's busy time on instructions the step program's table
of scopes knows AND places: `lib/scopes.py` joins the traced `XLA Ops`
events to `profiling.program_scopes` by instruction name; an instruction
the table lacks, or one whose `op_name` gives no module path or
`named_scope`, is not counted. The instrument's own coverage: what the
other `*_time_pct.train` metrics of this layer cannot see is 100 less
this."""

from benchmarks.lib import scopes

LAYER = "model step"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_span"


def read(trace, spans, cell):
    scoped = scopes.of_cell(trace, cell)
    if scoped is None or not scoped.named_ns:
        return None  # a program that registers no step, or names nothing
    return 100.0 * scoped.named_ns / scoped.busy_ns
