"""What a traced run's profile holds beyond `lib/trace.py`'s one line.

`lib/trace.py` reads the `XLA Ops` line of each device plane and the
benchmark's own `bench:` annotations. This module opens the same
`.xplane.pb` (`.bench_trace/<cell>/`, once per process) and returns

(i)   per device, the operations of EVERY line of the device plane that
      carries operations (an event named by an instruction's whole text,
      `%name = shape opcode(operands), attributes`), each with its opcode,
      its text and the line it sits on. On a v5e the lines are `XLA Ops`
      (the core's own sequence: nothing overlaps on it) and `Async XLA Ops`
      (one event from each `*-start` to its `*-done`: copies and slices
      between memory spaces, which overlap everything); `Steps`, `XLA
      Modules`, `XLA TraceMe` carry no operations;
(ii)  the host plane's `train.*` events, the spans `fit()` reports through
      `kubeflow_tpu/utils/tracing`, with their attributes;
(iii) what links a host launch to a device execution: the host's
      `DoEnqueueProgram` events carry a producer id (`_p`, type 12) and a
      `run_id`, the device's `XLA Modules` events the same id as consumer
      (`_c`) and the `run_id` of that device.

The arithmetic over intervals (`collectives`, `exposed_ns`, `loop_stall`)
takes plain tuples, so `tests/test_program_trace.py` checks it on hand-made
intervals. A program that writes no `train.*` span, or a trace with no
collective, gives empty lists: the readers then return None, never raise.

What counts as hiding a collective: another operation of the core's own
line (`XLA Ops`) running meanwhile. The `Async XLA Ops` intervals are data
movement that the core does not execute and several are always in flight;
counted as cover they would hide every collective by definition.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import pathlib
import re
from typing import NamedTuple

from benchmarks.lib import trace as tracelib

ROOT = pathlib.Path(__file__).resolve().parents[2]
CORE_LINE = tracelib.OPS_LINE
SPAN_PREFIX = "train."
LAUNCH_EVENT = "DoEnqueueProgram"
COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all",
)
# ` all-reduce(`: the first lower-case word before an opening bracket; a
# shape's `T(8,128)` and `S(1)` are upper case and follow no space.
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_NUMBER = re.compile(r"\.\d+$")
_GROUPS = re.compile(r"(?:replica_groups|source_target_pairs)=(\{\{.*?\}\}|\{\}|\[[\d,]+\]<=\[[\d,]+\](?:T\([\d,]+\))?)")
_FIRST_OPERAND = re.compile(r"\(.*?%([^\s,)]+)")


class Op(NamedTuple):
    name: str      # `all-reduce.314`
    opcode: str    # `all-reduce`
    text: str      # the rest of the instruction's text
    start: int     # ns on the trace's clock
    end: int
    line: str      # the device plane's line it sits on


class Span(NamedTuple):
    name: str      # `train.data`
    start: int
    end: int
    attributes: dict


class Launch(NamedTuple):
    context: int   # `_p` of the host event == `_c` of the execution
    run_id: int
    start: int
    end: int


class Execution(NamedTuple):
    module: str
    context: int
    run_id: int
    start: int
    end: int


@dataclasses.dataclass
class ProgramTrace:
    ops: dict[int, list[Op]]                  # device -> operations
    spans: list[Span]                         # the host's `train.*` events
    launches: list[Launch]
    executions: dict[int, list[Execution]]

    def core_ops(self, device: int = 0) -> list[Op]:
        return [op for op in self.ops.get(device, []) if op.line == CORE_LINE]

    def linked(self, device: int = 0) -> list[tuple[Launch, Execution]]:
        """Launches and the executions they caused, where the trace holds
        both (the host runs many programs ahead of the device)."""
        by_context = {x.context: x for x in self.executions.get(device, [])}
        return [
            (l, by_context[l.context]) for l in self.launches
            if l.context in by_context
        ]


def opcode_of(text: str) -> str:
    m = _OPCODE.search(" " + text)
    return m.group(1) if m else ""


def load(path: str) -> ProgramTrace:
    from jax.profiler import ProfileData

    out = ProgramTrace({}, [], [], {})
    parsed: dict[str, tuple[str, str, str]] = {}
    for plane in ProfileData.from_file(path).planes:
        m = tracelib.DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            ops = out.ops.setdefault(dev, [])
            runs = out.executions.setdefault(dev, [])
            for line in plane.lines:
                if line.name == tracelib.MODULES_LINE:
                    for ev in line.events:
                        st = dict(ev.stats)
                        if "_c" in st and "run_id" in st:
                            start = int(ev.start_ns)
                            runs.append(Execution(
                                tracelib.module_name(ev.name), int(st["_c"]),
                                int(st["run_id"]), start,
                                start + int(ev.duration_ns),
                            ))
                    continue
                for ev in line.events:
                    raw = ev.name
                    if not raw.startswith("%"):
                        break  # not a line of operations
                    if raw not in parsed:
                        name, text = tracelib.op_name(raw)
                        parsed[raw] = (name, opcode_of(text), text)
                    start = int(ev.start_ns)
                    ops.append(Op(
                        *parsed[raw], start, start + int(ev.duration_ns),
                        line.name,
                    ))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    if name.startswith(SPAN_PREFIX):
                        start = int(ev.start_ns)
                        out.spans.append(Span(
                            name, start, start + int(ev.duration_ns),
                            dict(ev.stats),
                        ))
                    elif name == LAUNCH_EVENT:
                        st = dict(ev.stats)
                        if "_p" in st and "run_id" in st:
                            start = int(ev.start_ns)
                            out.launches.append(Launch(
                                int(st["_p"]), int(st["run_id"]), start,
                                start + int(ev.duration_ns),
                            ))
    out.spans.sort(key=lambda s: s.start)
    out.launches.sort(key=lambda l: l.start)
    return out


@functools.lru_cache(maxsize=1)
def _load_dir(logdir: str) -> ProgramTrace | None:
    try:
        return load(tracelib.find_xplane(logdir))
    except FileNotFoundError:
        return None


def of_cell(cell: dict) -> ProgramTrace | None:
    """The profile of this process's traced run of `cell`, read once."""
    return _load_dir(str(ROOT / ".bench_trace" / cell["name"]))


# -- intervals ----------------------------------------------------------------

union = tracelib._union


def total(intervals) -> int:
    return sum(b - a for a, b in intervals)


def uncovered_by(cover):
    """`parts(a, b)`: what `cover`, a sorted union, leaves of a..b."""
    ends = [d for _, d in cover]

    def parts(a: int, b: int) -> list[tuple[int, int]]:
        out, at = [], a
        for k in range(bisect.bisect_right(ends, a), len(cover)):
            c, d = cover[k]
            if c >= b:
                break
            if c > at:
                out.append((at, c))
            at = d
        if at < b:
            out.append((at, b))
        return out

    return parts


def subtract(intervals, cover) -> list[tuple[int, int]]:
    """The parts of `intervals` (each kept apart) that `cover` leaves."""
    parts = uncovered_by(cover)
    return [p for a, b in intervals for p in parts(a, b)]


def collective_kind(op: Op) -> tuple[str, str] | None:
    """(`all-reduce`, `sync` | `start` | `done`) of a collective, else
    None. The compiler writes an asynchronous collective as its own
    `all-reduce-start`/`-done` opcodes, or wraps it in `async-start`/
    `async-done`, which then carry the wrapped opcode in the name."""
    word = op.opcode
    if word in ("async-start", "async-done"):
        word = _NUMBER.sub("", op.name)
    for base in COLLECTIVES:
        if word == base:
            return base, "sync"
        if word in (base + "-start", base + "-done"):
            return base, word[len(base) + 1:]
    return None


class Collective(NamedTuple):
    opcode: str    # `all-reduce`
    groups: str    # the text of its replica_groups (or pairs), `` if none
    start: int
    end: int


def collectives(core_ops: list[Op]) -> tuple[list[Collective], list[tuple[int, int]]]:
    """The collectives of one device's core line, a start-to-done pair as
    one interval (a `-done` names its `-start` as first operand; a pair cut
    by the trace's edge is dropped), and the union of everything else that
    ran on that line: what can hide them."""
    found, other, open_starts = [], [], {}
    for op in core_ops:
        kind = collective_kind(op)
        if kind is None:
            other.append((op.start, op.end))
            continue
        base, form = kind
        if form == "sync":
            found.append(Collective(base, groups_text(op.text), op.start, op.end))
        elif form == "start":
            open_starts[op.name] = op
        else:
            m = _FIRST_OPERAND.search(op.text)
            begun = open_starts.pop(m.group(1), None) if m else None
            if begun is not None:
                found.append(Collective(
                    base, groups_text(begun.text), begun.start, op.end
                ))
    return found, union(other)


def exposed_ns(found: list[Collective], cover) -> list[int]:
    """For each collective, the nanoseconds of it during which nothing of
    `cover` ran."""
    parts = uncovered_by(cover)
    return [total(parts(c.start, c.end)) for c in found]


def groups_text(text: str) -> str:
    m = _GROUPS.search(text)
    return m.group(1) if m else ""


def expand_groups(groups: str, n_devices: int) -> list[list[int]]:
    """`{{0,2},{1,3}}`, `[2,2]<=[4]`, `[2,2]<=[2,2]T(1,0)` or `{}` (one
    group of all) as lists of positions in the device assignment."""
    import numpy as np

    if groups in ("", "{}"):
        return [list(range(n_devices))]
    if groups.startswith("{{"):
        return [
            [int(x) for x in g.split(",") if x]
            for g in groups[2:-2].split("},{")
        ]
    m = re.match(r"\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?$", groups)
    shape, dims, perm = (
        [int(x) for x in part.split(",")] if part else None
        for part in m.groups()
    )
    ids = np.arange(int(np.prod(dims))).reshape(dims)
    if perm:
        ids = ids.transpose(perm)
    return ids.reshape(shape).tolist()


def axes_of(groups: str, mesh_sizes: dict[str, int]) -> str:
    """The mesh axes a collective's groups run along, `dp` or `dp+tp`:
    the axes whose coordinate differs inside a group. `mesh_sizes` holds
    the axes in the mesh's own order (the device assignment is the mesh's
    devices, row-major), so positions unravel over it."""
    import numpy as np

    names = list(mesh_sizes)
    sizes = [mesh_sizes[a] for a in names]
    n = int(np.prod(sizes))
    varying = set()
    for group in expand_groups(groups, n):
        coords = np.array(np.unravel_index(group, sizes)).T
        for k in range(len(names)):
            if len(set(coords[:, k])) > 1:
                varying.add(k)
    return "+".join(names[k] for k in sorted(varying)) or "none"


def loop_stall(
    busy: list[tuple[int, int]], window: tuple[int, int], spans: list[Span],
    names: tuple[str, ...],
) -> dict[str, int]:
    """Nanoseconds of the window in which the device was idle (outside
    `busy`, a sorted union) and the host was inside a span of one of
    `names`, by name. A gap under two spans is split between them."""
    idle = subtract([window], busy)
    out = {name: 0 for name in names}
    for span in spans:
        if span.name in out:
            lo, hi = max(span.start, window[0]), min(span.end, window[1])
            if lo < hi:
                out[span.name] += hi - lo - total(subtract([(lo, hi)], idle))
    return out


# -- what the metric readers share ---------------------------------------------


def steps_traced(trace, device: int = 0) -> float:
    """Steps the traced window holds: its length over the median distance
    between two starts of the step program. 0 if fewer than two ran."""
    main = trace.main_module(device)
    starts = sorted(
        s for m, s, _ in trace.module_events.get(device, []) if m == main
    )
    periods = sorted(b - a for a, b in zip(starts, starts[1:]))
    if not periods:
        return 0.0
    return (trace.window_ns[1] - trace.window_ns[0]) / periods[len(periods) // 2]


def mesh_sizes(cell: dict) -> dict[str, int]:
    """The cell's mesh in the program's own axis order."""
    from kubeflow_tpu.parallel.mesh import AXES

    mesh = cell["workload"].get("mesh", {})
    return {a: int(mesh.get(a, 1)) for a in AXES}


def collective_table(core_ops: list[Op], sizes: dict[str, int]) -> dict:
    """`{(opcode, axes): {"n", "time_ns", "exposed_ns"}}` of one device."""
    found, cover = collectives(core_ops)
    axes: dict[str, str] = {}
    table: dict = {}
    for c, exposed in zip(found, exposed_ns(found, cover)):
        if c.groups not in axes:
            axes[c.groups] = axes_of(c.groups, sizes)
        row = table.setdefault(
            (c.opcode, axes[c.groups]),
            {"n": 0, "time_ns": 0, "exposed_ns": 0},
        )
        row["n"] += 1
        row["time_ns"] += c.end - c.start
        row["exposed_ns"] += exposed
    return table


def cell_collectives(trace, cell: dict) -> dict | None:
    """`collective_table` of device 0 in this process's traced run of
    `cell`, or None where there is no profile, no busy time or no
    collective."""
    profile = of_cell(cell)
    if profile is None or not trace.busy_ns.get(0):
        return None
    return collective_table(profile.core_ops(0), mesh_sizes(cell)) or None
