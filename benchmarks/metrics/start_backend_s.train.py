"""Seconds the start spent in backend compiles: the program's
`compile.backend` spans that ended before the window's `train.fit` began.
Cache reads in a warm run (`cache="hit"`, each with its `retrieval_s`), XLA's
and Mosaic's compiles in a cold one; the `[start]` line (`lib/start.py`)
names the heaviest programs with what the cache said of each. None on a
program that keeps no such spans, or where the ring dropped any."""

from benchmarks.lib import start

LAYER = "train loop"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(trace, spans, cell):
    found = start.of_process()
    return None if found is None else found.backend_s
