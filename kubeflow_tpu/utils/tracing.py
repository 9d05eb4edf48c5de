"""Lightweight tracing spans: the one span primitive of the platform.

The reference has no tracing at all (SURVEY.md §5: "No distributed
tracing (no OpenTelemetry/jaeger)"); debugging a slow notebook spawn
meant reading four components' logs. This closes that gap with an
OTel-shaped core small enough to have zero dependencies:

- `Tracer.span(name, **attrs)` — context manager; nesting via a
  contextvar gives parent/child links; each top-level span starts a new
  trace id. Thread- and async-safe (contextvars propagate per thread).
- spans are stamped with `time.perf_counter_ns` (start, end and the
  duration never step with the wall clock); the wall-clock `start` is kept
  for the HTTP consumers, `end` is it plus the monotonic duration. They
  carry attributes and an error flag when the body raises.
- in a process that has already imported `jax`, a span also enters a
  `jax.profiler.TraceAnnotation` of its name and attributes (a
  `StepTraceAnnotation` when it has a `step_num`), so it lands in the
  `.xplane.pb` beside the device planes, on the profiler's clock, whenever
  a profile is being taken. This module never imports `jax`: a controller
  or web process pays nothing. There is no switch: off is "no profile
  running", when the annotation is inert.
- finished spans land in a bounded ring buffer (`export()` drains JSON
  dicts, oldest dropped on overflow) — the in-process collector; ship
  them wherever by draining periodically. `snapshot()` reads the same
  dicts and leaves the ring whole, for a second reader.
- `Tracer.record(name, start_ns, end_ns, **attrs)` takes a span that is
  already over (what `utils/compile_cache.py` hears from JAX once a
  trace, a lowering or a compile has ended) into the same ring, linked
  to the span current on the calling thread. It enters no annotation:
  the profiler cannot be told of the past.
- `trace_header()`/`from_header()` carry the trace id across HTTP hops
  (`x-kftpu-trace-id`, the platform's traceparent analog), so a web
  request's span tree continues into kfam/controllers.

Integration: the controller runtime wraps every reconcile in a span, the
WSGI core wraps every request (controller/key/outcome, method/path/status),
and `train.fit()` wraps every step and what it does in it (`train.*`).
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import itertools
import os
import sys
import threading
import time
from collections import deque
from typing import Any, Iterator

HEADER = "x-kftpu-trace-id"

_current: contextvars.ContextVar["Span | None"] = contextvars.ContextVar(
    "kftpu_current_span", default=None
)


@dataclasses.dataclass
class Span:
    name: str
    trace_id: str
    span_id: str
    parent_id: str | None
    start: float                 # wall clock, seconds
    attributes: dict[str, Any]
    start_ns: int                # time.perf_counter_ns()
    end_ns: int | None = None
    error: str | None = None

    @property
    def duration_ns(self) -> int | None:
        return None if self.end_ns is None else self.end_ns - self.start_ns

    def to_dict(self) -> dict:
        dur = self.duration_ns
        return {
            "name": self.name,
            "traceId": self.trace_id,
            "spanId": self.span_id,
            "parentId": self.parent_id,
            "start": self.start,
            "end": None if dur is None else self.start + dur / 1e9,
            "startNs": self.start_ns,
            "endNs": self.end_ns,
            "durationMs": None if dur is None else dur / 1e6,
            "attributes": dict(self.attributes),
            "error": self.error,
        }


# Ids are a per-process random prefix and a counter (`next()` of an
# `itertools.count` is atomic under the interpreter lock): unique across
# threads and, by the prefix, across processes. A forked child draws its own.
_ids = itertools.count(1)
_prefix = os.urandom(4).hex()


def _reseed_ids() -> None:
    global _prefix
    _prefix = os.urandom(4).hex()


os.register_at_fork(after_in_child=_reseed_ids)


def _new_id() -> str:
    return f"{_prefix}{next(_ids):08x}"


def _annotation(name: str, attributes: dict[str, Any]):
    """The profiler's annotation for a span: inert unless a profile is
    being taken, and nothing at all in a process that has not imported
    jax."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    if profiler is None:
        return contextlib.nullcontext()
    if "step_num" in attributes:
        return profiler.StepTraceAnnotation(name, **attributes)
    return profiler.TraceAnnotation(name, **attributes)


class Tracer:
    def __init__(self, capacity: int = 2048):
        self._lock = threading.Lock()
        self._finished: deque[Span] = deque(maxlen=capacity)
        self.dropped = 0
        self._capacity = capacity

    @contextlib.contextmanager
    def span(
        self,
        name: str,
        *,
        trace_id: str | None = None,
        **attributes: Any,
    ) -> Iterator[Span]:
        parent = _current.get()
        span = Span(
            name=name,
            trace_id=(
                trace_id
                or (parent.trace_id if parent is not None else _new_id())
            ),
            span_id=_new_id(),
            parent_id=parent.span_id if parent is not None else None,
            start=time.time(),
            attributes=attributes,
            start_ns=0,
        )
        token = _current.set(span)
        try:
            # The annotation encloses the ring's stamps, so a profile's
            # event is never shorter than the ring's span.
            with _annotation(name, attributes):
                span.start_ns = time.perf_counter_ns()
                try:
                    yield span
                finally:
                    span.end_ns = time.perf_counter_ns()
        except Exception as e:
            span.error = f"{type(e).__name__}: {e}"
            raise
        finally:
            _current.reset(token)
            self._keep(span)

    def record(
        self, name: str, start_ns: int, end_ns: int, **attributes: Any
    ) -> Span:
        """A span that is already over, on `time.perf_counter_ns`'s
        clock: a child of the calling thread's current span, in its
        trace, or the root of a trace of its own."""
        parent = _current.get()
        span = Span(
            name=name,
            trace_id=parent.trace_id if parent is not None else _new_id(),
            span_id=_new_id(),
            parent_id=parent.span_id if parent is not None else None,
            start=time.time() - (time.perf_counter_ns() - start_ns) / 1e9,
            attributes=attributes,
            start_ns=start_ns,
            end_ns=end_ns,
        )
        self._keep(span)
        return span

    def _keep(self, span: Span) -> None:
        with self._lock:
            if len(self._finished) == self._capacity:
                self.dropped += 1
            self._finished.append(span)

    def export(self) -> list[dict]:
        """Drain all finished spans (oldest first)."""
        with self._lock:
            out = list(self._finished)
            self._finished.clear()
        return [span.to_dict() for span in out]

    def snapshot(self) -> list[dict]:
        """The finished spans (oldest first), left in the ring: what
        `export()` would drain, for a reader that is not the one that
        ships them."""
        with self._lock:
            out = list(self._finished)
        return [span.to_dict() for span in out]

    def pending(self) -> int:
        with self._lock:
            return len(self._finished)


# The process-wide tracer the runtime and web tier report to. Tests may
# instantiate their own.
tracer = Tracer()


def current_trace_id() -> str | None:
    span = _current.get()
    return span.trace_id if span is not None else None


def trace_header() -> dict[str, str]:
    """Headers to propagate the active trace across an HTTP hop."""
    trace_id = current_trace_id()
    return {HEADER: trace_id} if trace_id else {}


def from_header(headers: Any) -> str | None:
    """The inbound trace id, if the caller sent one. `headers` is any
    mapping with a case-insensitive-ish get (WSGI request headers)."""
    if headers is None:
        return None
    get = getattr(headers, "get", None)
    if get is None:
        return None
    return get(HEADER) or get(HEADER.upper()) or None
