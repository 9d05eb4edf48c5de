"""Device time by the program's own scopes.

The profile of a traced run names each device event by an instruction of
the step program (`fusion.20`); the program says where each instruction
came from. `kubeflow_tpu/train/profiling.program_scopes` reads the
compiled step's text into a table, instruction -> `Scope(path, phase,
kind, mixed)`: the Flax module path and `jax.named_scope`s from the
instruction's `op_name` (`layer_3/attn/wq`, `layer_1/moe/moe.route`,
`head/bsd,vd->bsv`, `loss`, `optimizer`), `forward` / `recompute` /
`backward` / `update` / `other`, and `matmul` / `kernel` / `collective` /
`copy` / `elementwise`; a fusion takes the path of the heaviest instruction
it holds (its matmul, if it has one) and lists the others in `mixed`.
`Trainer.make_train_step()` registers the step under the module's name
(`profiling.step_programs()`), so this reader, which runs after the driver
has dropped its trainer, finds it by `trace.main_module(0)`; the table is
made on demand, by lowering the step again at the arguments `fit()` noted
and compiling it: the drivers clear jit's caches before the reference,
and a step with Pallas kernels traced again has another compile-cache key
(a kernel's payload holds the Python frames of its first trace), so on the
chip `table_s` is a lowering and a whole compile, 16-80 s a traced run.

`of_cell(trace, cell)` joins the table to the `XLA Ops` line of device 0
(`program_trace.of_cell(cell).core_ops(0)`: nothing overlaps on it, so
shares of busy time add to 100) by instruction name and returns a
`ScopedTime`: nanoseconds by `(component, phase, kind)`, a component being
a path without its layer's index (`attn/wq`). Made once a process; the
first call prints ONE `[scopes]` line:

- `table_s`: seconds to make the table (lower, load, parse), and
  `program_bytes`, the device memory in use after it less before it;
- `steps`, `busy_ms`: steps in the traced window and busy ms a step;
- `joined_pct`: share of busy time whose instruction the table holds.
  Under 99.9 the table is of another program than the one that ran, and
  the line says `TABLE_OF_ANOTHER_PROGRAM`;
- `named_pct`: share on instructions the table holds AND whose path is not
  empty (`scope_named_pct.train`);
- `kernel_ms`, and `optimizer_in_matmul_ms`: the time of `matmul`
  instructions whose `mixed` holds `optimizer` (AdamW fused into a weight
  gradient), to read beside `optimizer_time_pct.train`;
- `phases` and `by`: ms a step by phase, and by component and phase,
  heaviest first, what is under 0.05 ms summed as `rest`;
- `unnamed`: the heaviest instructions without a path, by opcode;
- `top`: the ten heaviest instructions, each with its path.

An instruction of the trace that the table lacks counts as unnamed and
never raises; with no registered program, no profile or a table that
cannot be made, `of_cell` gives None and every reader None.

The six readers (`metrics/scope_named_pct.train.py`,
`recompute_time_pct.train.py`, `dense_matmul_time_pct.train.py`,
`head_loss_time_pct.train.py`, `router_time_pct.train.py`,
`optimizer_time_pct.train.py`) are `share(trace, cell, keep)` with a
predicate over `(component, phase, kind)`: per cent of device 0's busy
time, None where nothing matches.
"""

from __future__ import annotations

import dataclasses
import re
import time

from benchmarks.lib import program_trace

UNNAMED = "unnamed"
_LAYER = re.compile(r"^layer_\d+/?")
_NUMBER = re.compile(r"\.\d+$")
# The head and the loss: the head's matmul forward and backward, the loss,
# and (backward only) the embedding, whose gradient the tied head shares.
HEAD_LOSS = ("head", "loss")
ROUTERS = ("moe.route", "attn.gate")


def component(path: str) -> str:
    """`layer_3/attn/wq` -> `attn/wq`; an empty path -> `unnamed`."""
    return _LAYER.sub("", path) or (UNNAMED if not path else "layer")


def in_head_or_loss(comp: str, phase: str) -> bool:
    first = comp.split("/")[0]
    return first in HEAD_LOSS or (first == "embed" and phase == "backward")


def in_router(comp: str) -> bool:
    return any(frame in ROUTERS for frame in comp.split("/"))


@dataclasses.dataclass
class ScopedTime:
    busy_ns: int                               # of the core line, device 0
    by: dict[tuple[str, str, str], int]        # (component, phase, kind) -> ns
    joined_ns: int                             # on instructions the table holds
    named_ns: int                              # ... whose path is not empty
    optimizer_in_matmul_ns: int
    instructions: dict[str, int]               # instruction -> ns
    paths: dict[str, str]                      # instruction -> path, `` unknown
    opcodes: dict[str, str]                    # instruction -> opcode

    def share(self, keep) -> float | None:
        """Per cent of busy time on the (component, phase, kind) `keep`
        takes; None where there is none."""
        ns = sum(t for key, t in self.by.items() if keep(*key))
        return 100.0 * ns / self.busy_ns if ns and self.busy_ns else None


def join(core_ops, table: dict) -> ScopedTime:
    """Time of one device's core line by the table's scopes. `table` maps
    an instruction's name to an object with `path`, `phase`, `kind`,
    `mixed`; an operation it lacks is unnamed."""
    out = ScopedTime(0, {}, 0, 0, 0, {}, {}, {})
    for op in core_ops:
        ns = op.end - op.start
        out.busy_ns += ns
        out.instructions[op.name] = out.instructions.get(op.name, 0) + ns
        out.opcodes[op.name] = op.opcode
        scope = table.get(op.name)
        if scope is None:
            key = (UNNAMED, "other", "elementwise")
        else:
            out.joined_ns += ns
            out.paths[op.name] = scope.path
            if scope.path:
                out.named_ns += ns
            if scope.kind == "matmul" and "optimizer" in scope.mixed:
                out.optimizer_in_matmul_ns += ns
            key = (component(scope.path), scope.phase, scope.kind)
        out.by[key] = out.by.get(key, 0) + ns
    return out


def _device_bytes() -> int:
    import jax

    return int((jax.devices()[0].memory_stats() or {}).get("bytes_in_use", 0))


def _line(module: str, scoped: ScopedTime, steps: float, table_s: float,
          program_bytes: int) -> str:
    per_step = lambda ns: round(ns / 1e6 / steps, 3) if steps else None
    pct = lambda ns: round(100.0 * ns / scoped.busy_ns, 3) if scoped.busy_ns else None
    phases: dict[str, int] = {}
    by: dict[str, int] = {}
    for (comp, phase, _), ns in scoped.by.items():
        phases[phase] = phases.get(phase, 0) + ns
        by[f"{comp}|{phase}"] = by.get(f"{comp}|{phase}", 0) + ns
    heaviest = lambda d: sorted(d.items(), key=lambda kv: kv[1], reverse=True)
    shown, rest = {}, 0
    for key, ns in heaviest(by):
        if steps and ns / 1e6 / steps >= 0.05:
            shown[key] = per_step(ns)
        else:
            rest += ns
    shown["rest"] = per_step(rest)
    unnamed: dict[str, int] = {}
    for name, ns in scoped.instructions.items():
        if not scoped.paths.get(name):
            kind = f"{scoped.opcodes[name]}:{_NUMBER.sub('', name)}"
            unnamed[kind] = unnamed.get(kind, 0) + ns
    joined = pct(scoped.joined_ns)
    fields = {
        "module": module, "table_s": round(table_s, 3),
        "program_bytes": program_bytes, "steps": round(steps, 3),
        "busy_ms": per_step(scoped.busy_ns),
        "total_ms": per_step(sum(scoped.by.values())),
        "joined_pct": joined, "named_pct": pct(scoped.named_ns),
        "kernel_ms": per_step(sum(
            ns for (_, _, kind), ns in scoped.by.items() if kind == "kernel"
        )),
        "optimizer_in_matmul_ms": per_step(scoped.optimizer_in_matmul_ns),
        "phases": {k: per_step(v) for k, v in heaviest(phases)},
        "by": shown,
        "unnamed": {k: per_step(v) for k, v in heaviest(unnamed)[:8]},
        "top": [
            [name, scoped.paths.get(name) or UNNAMED, per_step(ns)]
            for name, ns in heaviest(scoped.instructions)[:10]
        ],
    }
    if joined is not None and joined < 99.9:
        fields["TABLE_OF_ANOTHER_PROGRAM"] = True
    return "[scopes] " + " ".join(f"{k}={v}" for k, v in fields.items())


_made: dict[tuple[str, str | None], ScopedTime | None] = {}


def of_cell(trace, cell: dict) -> ScopedTime | None:
    """This process's traced run of `cell` by the step program's scopes,
    made once (the `[scopes]` line is printed then); None where the
    program registers no step, the run left no profile, or the table
    cannot be made."""
    module = trace.main_module(0)
    key = (cell["name"], module)
    if key not in _made:
        _made[key] = _make(trace, cell, module)
    return _made[key]


def _make(trace, cell: dict, module: str | None) -> ScopedTime | None:
    try:
        from kubeflow_tpu.train import profiling

        program = profiling.step_programs().get(module)
    except (ImportError, AttributeError):
        return None  # a program without the table
    profile = program_trace.of_cell(cell)
    if program is None or profile is None:
        return None
    before, t0 = _device_bytes(), time.perf_counter()
    try:
        table = program()
    except Exception as e:  # the traced run's other metrics still stand
        print(f"[scopes] no table of {module}: {type(e).__name__}: {e}",
              flush=True)
        return None
    table_s, after = time.perf_counter() - t0, _device_bytes()
    scoped = join(profile.core_ops(0), table)
    if not scoped.busy_ns:
        return None
    print(_line(module, scoped, program_trace.steps_traced(trace), table_s,
                after - before), flush=True)
    return scoped


def share(trace, cell: dict, keep) -> float | None:
    scoped = of_cell(trace, cell)
    return None if scoped is None else scoped.share(keep)
