"""Where compiled programs are kept between processes, and what building
them cost this one.

One helper, called by every entry point that compiles for the chip
(`chip_smoke.py`, `bench.py`, `python -m kubeflow_tpu.serving`, the
launcher's ``--module`` path), so the cache can be placed from outside
and no second directory is ever set in code:

- where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
  this sets nothing;
- where it is not, the cache goes to one fixed directory inside the
  checkout (``.jax_cache/``, git-ignored). The directory is part of the
  cache's key, so it is never built from a temporary name, a pid or the
  time: a path that moves never hits.

**The compile observer.** `observe_compiles()` (called here and by the
first `fit()`, once a process whoever comes first) listens to what JAX
reports through `jax.monitoring` and turns it into finished spans of
`utils/tracing.tracer`, the one ring the platform has:

- ``compile.trace``: a function traced to a jaxpr (`fun_name`);
- ``compile.lower``: a jaxpr lowered to a module (`fun_name`);
- ``compile.backend``: a module compiled, or loaded from the cache
  (`fun_name`, and `cache`: ``"hit"`` with `retrieval_s` and `saved_s`,
  ``"miss"`` for a compile whose result went into the cache, ``"off"``
  for a request the cache neither answered nor kept: no directory, or
  a compile too quick or too small for JAX to keep, which it reports as
  no miss).

Only the OUTERMOST phase on a thread leaves a span. A trace that begins
while another trace or a lowering is open (an inner `jit` traced inside
the outer trace, a kernel's body traced by its lowering rule) is part of
it: an LM step fires thousands of such events, and their seconds summed
would pass the wall time several times over. So the seconds of
``compile.*`` spans of one thread never add up to more than the time
that passed. Each span is a child of whatever span is current on its
thread when the phase ends: `train.init`, the `train.dispatch` of the
step that paid, `train.resize`, or none (a trainer's construction, a
caller's own programs; `fun_name` says whose).

JAX calls the listeners only when something is traced, lowered or
compiled. A step that runs from its executable fires none, so the
observer costs a running job nothing; what it costs a start is in
`docs/perf.md`.
"""

from __future__ import annotations

import contextlib
import os
import pathlib
import threading
import time
from typing import Iterator

from kubeflow_tpu.utils import tracing

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_IN_CHECKOUT = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"

# JAX's names (0.9.0), pinned by `tests/test_compile_spans.py`: each of
# the first three is reported as a scalar at the phase's entry and as a
# duration at its exit, with `fun_name`; the cache's come in between,
# on the thread of the backend compile they belong to.
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"

SPAN_OF = {
    TRACE_EVENT: "compile.trace",
    LOWER_EVENT: "compile.lower",
    BACKEND_EVENT: "compile.backend",
}
_CACHE_OF = {CACHE_HIT_EVENT: "hit", CACHE_MISS_EVENT: "miss"}
_SECONDS_OF = {
    CACHE_RETRIEVAL_EVENT: "retrieval_s", CACHE_SAVED_EVENT: "saved_s",
}

_register_lock = threading.Lock()
_observing = False


class _Thread(threading.local):
    depth = 0       # phases open on this thread
    cache = None    # what the cache said of the backend compile that is open
    sink = None     # `compiled_here()`'s list, while one is open


_thread = _Thread()


def _on_entry(event: str, value, **kwargs) -> None:
    if event in SPAN_OF:
        _thread.depth += 1
        if event == BACKEND_EVENT:
            _thread.cache = {"cache": "off"}


def _on_event(event: str, **kwargs) -> None:
    said = _CACHE_OF.get(event)
    if said is not None and _thread.cache is not None:
        _thread.cache["cache"] = said


def _on_exit(event: str, duration: float, **kwargs) -> None:
    name = SPAN_OF.get(event)
    if name is None:
        key = _SECONDS_OF.get(event)
        if key is not None and _thread.cache is not None:
            _thread.cache[key] = duration
        return
    # Listeners registered while a phase was open hear its exit alone.
    _thread.depth = max(_thread.depth - 1, 0)
    said = None
    if event == BACKEND_EVENT:
        said, _thread.cache = _thread.cache or {"cache": "off"}, None
    if _thread.depth:
        return
    attributes = {"fun_name": kwargs.get("fun_name"), **(said or {})}
    end_ns = time.perf_counter_ns()
    span = tracing.tracer.record(
        name, end_ns - int(duration * 1e9), end_ns, **attributes
    )
    if _thread.sink is not None:
        _thread.sink.append(span)


def observe_compiles() -> None:
    """Register the listeners, once a process however often it is
    called."""
    global _observing
    with _register_lock:
        if _observing:
            return
        import jax.monitoring

        jax.monitoring.register_scalar_listener(_on_entry)
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_exit)
        _observing = True


@contextlib.contextmanager
def compiled_here() -> Iterator[list[tracing.Span]]:
    """The ``compile.*`` spans recorded on THIS thread while the block
    runs, in a list the observer appends to: `fit()` adds them up as
    they come."""
    spans: list[tracing.Span] = []
    outer, _thread.sink = _thread.sink, spans
    try:
        yield spans
    finally:
        _thread.sink = outer


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory.

    Call before the first compilation of the process."""
    observe_compiles()
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(_IN_CHECKOUT))
    return str(_IN_CHECKOUT)
