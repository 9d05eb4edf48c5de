"""What a trainer knows of the device's memory, stated to the model it
traces.

A model knows its shapes; it does not know the optimizer's moments, how
the state is sharded, nor how much memory the device has. The trainer
does, and says so round the step's trace (`stated`); a model that can
trade memory for time reads it there (`current`:
`models/transformer.remat_plan`). Every number is a constant of the
trainer and the device — never what happens to be in use at the moment
of tracing — so every trace of one step is the same program. A model
applied outside a trainer (serving, `eval`) finds nothing stated.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses

import jax


@dataclasses.dataclass(frozen=True)
class StepMemory:
    """Per-device bytes: the train state resident through the step
    (parameters, optimizer state, statistics), the gradients the step
    forms beside it (one float32 a parameter), and the device's limit
    (None where the backend reports none: the CPU)."""

    state_bytes: int
    grad_bytes: int
    limit_bytes: int | None


_STATED: contextvars.ContextVar[StepMemory | None] = contextvars.ContextVar(
    "step_memory", default=None
)


@contextlib.contextmanager
def stated(memory: StepMemory | None):
    token = _STATED.set(memory)
    try:
        yield
    finally:
        _STATED.reset(token)


def current() -> StepMemory | None:
    return _STATED.get()


def device_limit(mesh) -> int | None:
    """`bytes_limit` of the allocator of a device of `mesh` that THIS
    process addresses. In a job of several processes the mesh's first
    device belongs to one of them and `memory_stats()` raises for every
    other; a job's chips are one kind, so each process reads the same
    number off its own and all trace the same step. None where there is
    nothing to read: the CPU's allocator reports no limit, and a device
    that is only described (`jax.experimental.topologies`) raises though
    it counts as local."""
    try:
        stats = mesh.local_devices[0].memory_stats()
    except jax.errors.JaxRuntimeError:  # a described device
        return None
    return int(stats["bytes_limit"]) if stats and "bytes_limit" in stats else None
