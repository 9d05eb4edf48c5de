"""Share of device 0's busy time spent in collectives while nothing else ran
on the core's line: what overlap with compute could win back. Prints the
`[collectives]` line: count, time and exposed time a step, by opcode and by
the mesh axes the `replica_groups` run along."""

from benchmarks.lib import program_trace

LAYER = "collectives"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(trace, spans, cell):
    rows = program_trace.cell_collectives(trace, cell)
    if rows is None:
        return None
    steps = program_trace.steps_traced(trace) or 1.0
    print("[collectives] steps=%.2f per_step=%s" % (steps, {
        f"{opcode} {axes}": {
            "n": round(r["n"] / steps, 1),
            "ms": round(r["time_ns"] / steps / 1e6, 3),
            "exposed_ms": round(r["exposed_ns"] / steps / 1e6, 3),
        }
        for (opcode, axes), r in sorted(rows.items())
    }), flush=True)
    return 100.0 * sum(r["exposed_ns"] for r in rows.values()) / trace.busy_ns[0]
