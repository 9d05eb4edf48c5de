"""Share of the traced window in which device 0 ran nothing while the host
was inside one of `fit()`'s `train.data`, `train.readback` or `train.save`
spans. It cannot pass `device_idle_pct.train` (the same idle time over the
same window); the difference is idle time the loop does not explain. Prints
the `[loop_stall]` line: the split by span, and the launches the trace holds
together with their executions (the host runs many programs ahead)."""

from benchmarks.lib import program_trace

LAYER = "train loop"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_span"
STALLS = ("train.data", "train.readback", "train.save")


def read(trace, spans, cell):
    profile = program_trace.of_cell(cell)
    if profile is None or not profile.spans:
        return None  # a program that reports no `train.*` span
    window = trace.window_ns
    busy = program_trace.union(
        [(op.start, op.end) for op in profile.core_ops(0)]
    )
    stalled = program_trace.loop_stall(busy, window, profile.spans, STALLS)
    length = window[1] - window[0]
    idle = length - program_trace.total(busy)
    leads = [
        round((run.start - launch.end) / 1e6, 3)
        for launch, run in profile.linked(0)
        if run.module == trace.main_module(0)
    ]
    print("[loop_stall] idle_ms=%.3f under_ms=%s unexplained_ms=%.3f "
          "spans=%d enqueue_to_start_ms=%s" % (
              idle / 1e6,
              {k: round(v / 1e6, 3) for k, v in stalled.items()},
              (idle - sum(stalled.values())) / 1e6, len(profile.spans), leads,
          ), flush=True)
    return 100.0 * sum(stalled.values()) / length
