"""Share of device 0's busy time (the base `flash_time_pct.train` uses) spent
in collectives: operations of the core's line whose opcode is `all-reduce`,
`all-gather`, `reduce-scatter`, `collective-permute` or `all-to-all`, a
`-start` to `-done` pair counted as one interval (`lib/program_trace`)."""

from benchmarks.lib import program_trace

LAYER = "collectives"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(trace, spans, cell):
    rows = program_trace.cell_collectives(trace, cell)
    if rows is None:
        return None
    return 100.0 * sum(r["time_ns"] for r in rows.values()) / trace.busy_ns[0]
