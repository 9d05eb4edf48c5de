"""Programs the start compiled and wrote to the cache: the program's
`compile.backend` spans with `cache == "miss"` that ended before the window's
`train.fit` began. A warm run should read 0; a step lowered in a new process
that misses the cache again reads 1 here (`lib/start.py`'s `[start]` line
names it). None on a program that keeps no such spans, or where the ring
dropped any."""

from benchmarks.lib import start

LAYER = "train loop"
UNIT = "programs"
MOVES = "setup_s"
SOURCE = "program_span"


def read(trace, spans, cell):
    found = start.of_process()
    return None if found is None else found.cache_misses
