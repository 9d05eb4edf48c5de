"""What a run's start cost, by the program's own account.

The program (since its PR 50) hears every trace, lowering and backend
compile from JAX and keeps each as a finished span of its one ring
(`kubeflow_tpu/utils/compile_cache.py`, `kubeflow_tpu/utils/tracing.py`):
`compile.trace`, `compile.lower`, `compile.backend`, each with `fun_name`,
the last with `cache` (`hit`, `miss`, `off`), each a child of the span that
was current on its thread (`train.init`, the `train.dispatch` of the step
that paid, or none), and `fit()` runs under one `train.fit` span a call.

A driver calls `fit()` for its set-up steps and once more for the window, the
reference calls none, and the table of scopes compiles after it. So the
window is the LAST `train.fit` span in the ring, and the start is every
`compile.*` span that ended before that span began (what came after the
window is on the line as `after_s` and `after_misses`, in no metric). `of_process()` reads the
ring once (without draining it: the ring is the program's, and its other
readers find it whole) and prints the `[start]` line; the three
`start_*.train` readers return its numbers.

A ring that lost its oldest spans (`dropped > 0`) would read a start short
of what it was, and a program without the ring's undrained read, or without
a `train.fit` span, has nothing to read: `None` then, with the reason on the
`[start]` line, never a number. `summarize()` is plain arithmetic over span
dictionaries (`tests/test_start_metrics.py` hands it a hand-made list).
"""

from __future__ import annotations

import dataclasses
import functools

PHASES = ("compile.trace", "compile.lower", "compile.backend")
HEAVIEST = 8


@dataclasses.dataclass(frozen=True)
class Start:
    trace_lower_s: float   # compile.trace + compile.lower before the window
    backend_s: float       # compile.backend before the window
    cache_misses: int      # ... of them with cache == "miss"
    under_train_s: dict    # {phase: seconds under a `train.*` span}
    outside_s: dict        # {phase: seconds under no `train.*` span}
    programs: list         # the heaviest, [name, trace, lower, backend, cache]
    spans: int             # `compile.*` spans of the start
    fit_s: float           # the `train.fit` spans before the window
    in_window: int         # `compile.*` spans inside the window's `train.fit`
    after_s: float         # `compile.*` seconds after it: the reference, the table
    after_misses: int      # ... and their backend compiles with cache == "miss"
    dropped: int

    def line(self) -> str:
        def rounded(d):
            return {k.removeprefix("compile."): round(v, 3) for k, v in d.items()}

        return (
            f"[start] trace_lower_s={self.trace_lower_s:.3f} "
            f"backend_s={self.backend_s:.3f} cache_misses={self.cache_misses} "
            f"under_train_s={rounded(self.under_train_s)} "
            f"outside_s={rounded(self.outside_s)} programs={self.programs} "
            f"spans={self.spans} fit_s={self.fit_s:.3f} "
            f"in_window={self.in_window} after_s={self.after_s:.3f} "
            f"after_misses={self.after_misses} dropped={self.dropped}"
        )


def _seconds(span: dict) -> float:
    return (span["endNs"] - span["startNs"]) / 1e9


def _misses(spans: list[dict]) -> int:
    return sum(
        1 for s in spans
        if s["name"] == "compile.backend"
        and s["attributes"].get("cache") == "miss"
    )


def program_of(fun_name) -> str:
    """`train_step` for the trace's `train_step` and the other phases'
    `jit(train_step)` alike."""
    name = str(fun_name)
    if name.endswith(")") and "(" in name:
        head, _, rest = name.partition("(")
        if head.isidentifier():
            return rest[:-1]
    return name


def summarize(spans: list[dict], dropped: int) -> tuple[Start | None, str]:
    """(the start, "") from a ring's span dictionaries, oldest first, or
    (None, why not)."""
    if dropped:
        return None, f"the ring dropped {dropped} span(s): its oldest are gone"
    fits = sorted(
        (s for s in spans if s["name"] == "train.fit"),
        key=lambda s: s["startNs"],
    )
    if not fits:
        return None, "no train.fit span in the ring"
    *before, window = fits
    compiled = [s for s in spans if s["name"] in PHASES]
    start = [s for s in compiled if s["endNs"] <= window["startNs"]]
    after = [s for s in compiled if s["startNs"] >= window["endNs"]]
    train_ids = {s["spanId"] for s in spans if s["name"].startswith("train.")}
    under = dict.fromkeys(PHASES, 0.0)
    outside = dict.fromkeys(PHASES, 0.0)
    programs: dict[str, dict] = {}
    for s in start:
        where = under if s["parentId"] in train_ids else outside
        where[s["name"]] += _seconds(s)
        program = programs.setdefault(
            program_of(s["attributes"].get("fun_name")),
            {**dict.fromkeys(PHASES, 0.0), "cache": []},
        )
        program[s["name"]] += _seconds(s)
        if s["name"] == "compile.backend":
            program["cache"].append(s["attributes"].get("cache"))
    total = {p: under[p] + outside[p] for p in PHASES}
    heaviest = sorted(
        programs.items(), key=lambda kv: -sum(kv[1][p] for p in PHASES)
    )[:HEAVIEST]
    return Start(
        trace_lower_s=total["compile.trace"] + total["compile.lower"],
        backend_s=total["compile.backend"],
        cache_misses=_misses(start),
        under_train_s=under,
        outside_s=outside,
        programs=[
            [name, *(round(p[phase], 3) for phase in PHASES), "/".join(
                str(c) for c in p["cache"]
            )]
            for name, p in heaviest
        ],
        spans=len(start),
        fit_s=sum(_seconds(s) for s in before),
        in_window=sum(
            1 for s in compiled
            if window["startNs"] <= s["startNs"]
            and s["endNs"] <= window["endNs"]
        ),
        after_s=sum(_seconds(s) for s in after),
        after_misses=_misses(after),
        dropped=dropped,
    ), ""


@functools.cache
def of_process() -> Start | None:
    """This process's start, read from the program's ring once; prints the
    `[start]` line. None where the program keeps no such account."""
    from kubeflow_tpu.utils import tracing

    snapshot = getattr(tracing.tracer, "snapshot", None)
    if snapshot is None:
        print("[start] none: the program's ring has no undrained read",
              flush=True)
        return None
    start, why = summarize(snapshot(), tracing.tracer.dropped)
    if start is None:
        print(f"[start] none: {why}", flush=True)
        return None
    print(start.line(), flush=True)
    return start
