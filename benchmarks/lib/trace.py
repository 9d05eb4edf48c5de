"""From a profiler trace (`.xplane.pb`) to numbers.

Two steps, so that the arithmetic can be checked on a recorded trace
without the profiler (`benchmarks/tests/test_trace.py`):

1. `load_events(path)` reads the file with `jax.profiler.ProfileData` and
   keeps, for every device plane (`/device:TPU:<n>`), the events of its
   operations line and of its modules line, and from the host planes the
   benchmark's own annotations (`bench:<what>`, written with
   `jax.profiler.TraceAnnotation` by the drivers). Times are nanoseconds
   on the trace's one clock.
2. `reduce(events)` turns that into a `ReducedTrace`: the traced window,
   the union of the intervals in which an operation ran on each device
   (busy), time and count by operation name, the executions of each
   module (one per call of a jitted program), and the longest idle gaps
   of device 0, each labelled with the benchmark span that covers most of
   it, or `unattributed`.

A device operation is an event of the line named `XLA Ops`; a module
execution is an event of the line named `XLA Modules`. Operations nest
nowhere on a TPU's line, but the union is taken all the same.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIX = "bench:"
# The operations line names an event by the instruction's whole text,
# `%flash_fwd_compact.8 = (bf16[...]) custom-call(...)`: the name is what
# stands before ` = `, and a Pallas kernel's is its `name=` plus a number.
_INSTRUCTION = re.compile(r"^%(\S+) = (.*)$", re.S)


def op_name(event_name: str) -> tuple[str, str]:
    """(`flash_fwd_compact.8`, the rest of the instruction's text)."""
    m = _INSTRUCTION.match(event_name)
    return (m.group(1), m.group(2)) if m else (event_name, "")


def find_xplane(logdir: str) -> str:
    """The one `.xplane.pb` the profiler wrote under `logdir`."""
    found = sorted(
        glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    if len(found) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {logdir}, found {found}"
        )
    return found[0]


def load_events(path: str) -> dict:
    """`{"devices": {n: {"ops": [[name, start_ns, dur_ns], ...],
    "modules": [...]}}, "spans": [[name, start_ns, dur_ns], ...],
    "layout": {plane: {line: events}}, "details": {op name: text}}` — the
    last two for a reader's eyes."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[int, dict] = {}
    spans = []
    layout: dict[str, dict[str, int]] = {}
    details: dict[str, str] = {}
    for plane in data.planes:
        layout[plane.name] = {
            line.name: sum(1 for _ in line.events) for line in plane.lines
        }
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    name = ev.name
                    if key == "ops":
                        name, text = op_name(name)
                        details.setdefault(name, text[:120])
                    dev[key].append(
                        [name, int(ev.start_ns), int(ev.duration_ns)]
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(
                            [ev.name[len(SPAN_PREFIX):], int(ev.start_ns),
                             int(ev.duration_ns)]
                        )
    return {
        "devices": devices, "spans": spans, "layout": layout,
        "details": details,
    }


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


_MODULE_SUFFIX = re.compile(r"\(\d+\)$")


def module_name(event_name: str) -> str:
    """`jit_train_step(123456789)` -> `jit_train_step`."""
    return _MODULE_SUFFIX.sub("", event_name)


@dataclasses.dataclass
class ReducedTrace:
    window_ns: tuple[int, int]
    busy_ns: dict[int, int]                  # device -> busy nanoseconds
    op_time_ns: dict[int, dict[str, int]]    # device -> name -> ns
    op_count: dict[int, dict[str, int]]      # device -> name -> events
    module_runs_ns: dict[int, dict[str, list[int]]]  # device -> module -> durations
    idle_gaps: list[tuple[str, int]]         # device 0: (label, ns), longest first
    spans: list[tuple[str, int, int]]        # (name, start_ns, end_ns)
    module_events: dict[int, list[tuple[str, int, int]]]  # device -> (module, start, end)
    details: dict[str, str]                  # op name -> its instruction's text

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices that ran anything."""
        if not self.busy_ns:
            return 0.0
        return sum(self.busy_ns.values()) / len(self.busy_ns) / 1e9

    def idle_share(self, device: int = 0) -> float:
        window = self.window_ns[1] - self.window_ns[0]
        return 1.0 - self.busy_ns.get(device, 0) / window

    def time_by_prefix(self, prefix: str, device: int = 0) -> int:
        return sum(
            ns for name, ns in self.op_time_ns.get(device, {}).items()
            if name.startswith(prefix)
        )

    def top_ops(self, n: int = 10, device: int = 0) -> list[list]:
        """Where the device's time went, for a reader: the kinds of
        operation that took most (an operation's kind is its name without
        the number, `all-reduce`, `fusion`, `flash_bwd_fused`: a model of
        many layers has one name a layer), then the single operations that
        took most, each with the start of its instruction's text."""
        times = self.op_time_ns.get(device, {})
        kinds: dict[str, int] = {}
        for name, ns in times.items():
            kind = re.sub(r"\.\d+$", "", name)
            kinds[kind] = kinds.get(kind, 0) + ns
        by_time = lambda d: sorted(d.items(), key=lambda kv: kv[1], reverse=True)
        n_kinds = min(len(kinds), n - min(3, n // 2))
        out = [[f"all {kind}", ns / 1e9] for kind, ns in by_time(kinds)[:n_kinds]]
        out += [
            [f"{name} {self.details.get(name, '')}".strip(), ns / 1e9]
            for name, ns in by_time(times)[: n - n_kinds]
        ]
        return out

    def main_module(self, device: int = 0) -> str | None:
        """The module that took most of the device's time: the step."""
        runs = self.module_runs_ns.get(device, {})
        return max(runs, key=lambda m: sum(runs[m])) if runs else None

    def between_runs_ns(self, device: int = 0) -> list[int]:
        """From the end of one execution of the main module to the start
        of the next: what the device did, or waited for, between steps."""
        main = self.main_module(device)
        runs = sorted(
            (s, e) for m, s, e in self.module_events.get(device, []) if m == main
        )
        return [runs[i + 1][0] - runs[i][1] for i in range(len(runs) - 1)]


def reduce(events: dict, n_gaps: int = 5) -> ReducedTrace:
    devices = {int(k): v for k, v in events["devices"].items()}
    spans = [(name, start, start + dur) for name, start, dur in events["spans"]]
    starts, ends = [], []
    for dev in devices.values():
        for _, start, dur in dev["ops"]:
            starts.append(start)
            ends.append(start + dur)
    if not starts:
        raise ValueError("the trace holds no device operation")
    window = (min(starts), max(ends))

    busy, op_time, op_count, runs, module_events = {}, {}, {}, {}, {}
    merged0: list[tuple[int, int]] = []
    for n, dev in sorted(devices.items()):
        merged = _union([(s, s + d) for _, s, d in dev["ops"]])
        busy[n] = sum(b - a for a, b in merged)
        if n == min(devices):
            merged0 = merged
        times, counts = {}, {}
        for name, _, dur in dev["ops"]:
            times[name] = times.get(name, 0) + dur
            counts[name] = counts.get(name, 0) + 1
        op_time[n], op_count[n] = times, counts
        mods: dict[str, list[int]] = {}
        for name, start, dur in dev["modules"]:
            mods.setdefault(module_name(name), []).append(dur)
        runs[n] = mods
        module_events[n] = [
            (module_name(name), start, start + dur)
            for name, start, dur in dev["modules"]
        ]

    gaps = [
        (merged0[i][1], merged0[i + 1][0]) for i in range(len(merged0) - 1)
    ]
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    labelled = []
    for a, b in gaps[:n_gaps]:
        cover: dict[str, int] = {}
        for name, s, e in spans:
            overlap = min(b, e) - max(a, s)
            if overlap > 0:
                cover[name] = cover.get(name, 0) + overlap
        label = max(cover, key=cover.get) if cover else "unattributed"
        if cover and cover[label] * 2 < (b - a):
            label = "unattributed"
        labelled.append((label, b - a))
    return ReducedTrace(
        window, busy, op_time, op_count, runs, labelled, spans, module_events,
        dict(events.get("details", {})),
    )


def reduce_file(logdir: str) -> tuple[ReducedTrace, dict]:
    """The reduced trace, and the file's layout of planes and lines."""
    events = load_events(find_xplane(logdir))
    return reduce(events), events["layout"]
