"""Seconds the start spent tracing and lowering: the program's
`compile.trace` and `compile.lower` spans (`kubeflow_tpu/utils/
compile_cache.py`, outermost phases only, so no second is counted twice)
that ended before the window's `train.fit` began. The Python a start pays
even from a warm cache: it moves with the count of kernels and layers a step
traces, not with the cache. `lib/start.py` reads the ring once a process and
prints the `[start]` line. None on a program that keeps no such spans, or
where the ring dropped any."""

from benchmarks.lib import start

LAYER = "train loop"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(trace, spans, cell):
    found = start.of_process()
    return None if found is None else found.trace_lower_s
