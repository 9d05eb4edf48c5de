"""Profiling: first-class jax.profiler trace capture for training jobs.

The reference had no runtime instrumentation — profiling was a *served
workload* (a Tensorboard CR pointed at a logdir, SURVEY.md §5 tracing
row). The TPU-native version completes that loop: the training loop
captures a windowed `jax.profiler` trace (XLA ops, TPU step time, HBM
usage) into the job's logdir in the exact layout TensorBoard's profile
plugin reads (`<logdir>/plugins/profile/<run>/`), and a `Tensorboard` CR
with `logspath` at that directory serves it. Capture is windowed because
tracing is expensive: profile steps [start, start+steps), not the whole
run.

Named regions on the trace timeline are spans of `utils/tracing`
(`tracing.tracer.span`), which `fit()` reports to. Device time by the
program's own scopes is `program_scopes`: the compiled step's instructions
(what a profile's device events are named by) joined to the module path,
phase and kind they came from; `Trainer.step_scopes()` makes the table on
demand, `step_programs()` is how a reader without the trainer finds it,
and `Profiler` writes it beside the profile (`step_scopes.json`). That
table is what the benchmark's per-layer metrics read. Also here, older and
read by `bench.py` alone: the per-phase roofline layer (`time_phase`,
`PhaseRoofline`), the mechanical version of the hand-built phase table in
docs/architecture.md Round 5. A bench
times each phase of a step (attention fwd/bwd, MLP, optimizer) behind a
device fence, attaches the phase's modeled TFLOP and HBM bytes, and the
roofline classifies which hardware resource each phase saturates
against the chip's published peaks (`chip_peaks`, keyed by
`device_kind`) — so "where the ceiling is" is a printed artifact, not a
one-off spreadsheet.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import math
import pathlib
import re
import time
from typing import Any

import jax
import jax.numpy as jnp

log = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class ProfileSchedule:
    """Trace `num_steps` steps, beginning `start_step` steps after this
    process's first step. Relative (not absolute) on purpose: a resumed
    run's first steps pay XLA recompilation, and the warmup skip must
    apply there too."""

    start_step: int = 10  # skip compile + warmup by default
    num_steps: int = 3

    def validate(self) -> None:
        if self.start_step < 0 or self.num_steps < 1:
            raise ValueError("start_step >= 0 and num_steps >= 1 required")


class Profiler:
    """Windowed trace capture driven by the training loop.

    Call `before_step(step)` / `after_step(step)` around each step; the
    profiler starts the trace at `schedule.start_step` and stops it after
    `schedule.num_steps` steps. Stop is crash-safe: `close()` (call in a
    finally) terminates a live trace so a diverging run still leaves a
    readable profile on disk.
    """

    def __init__(
        self,
        logdir: str | pathlib.Path,
        schedule: ProfileSchedule | None = None,
    ):
        self.logdir = pathlib.Path(logdir)
        self.schedule = schedule or ProfileSchedule()
        self.schedule.validate()
        self._active = False
        self._done = False
        self._first_step: int | None = None

    def before_step(self, step: int) -> None:
        if self._first_step is None:
            self._first_step = step
        if (
            not self._done
            and not self._active
            and step >= self._first_step + self.schedule.start_step
        ):
            self.logdir.mkdir(parents=True, exist_ok=True)
            jax.profiler.start_trace(str(self.logdir))
            self._active = True
            self._started_at = step
            log.info("profiler: trace started at step %d", step)

    def after_step(self, step: int) -> None:
        if (
            self._active
            and step + 1 >= self._started_at + self.schedule.num_steps
        ):
            self._stop()

    def _stop(self) -> None:
        jax.profiler.stop_trace()
        self._active = False
        self._done = True
        log.info("profiler: trace written under %s", self.logdir)
        # What the profile's device events are instructions OF: the step's
        # table of scopes, beside the profile.
        write_step_scopes(self.logdir)

    def close(self) -> None:
        if self._active:
            self._stop()

    @property
    def trace_written(self) -> bool:
        return self._done


# -- device time by the program's own scopes ----------------------------------
#
# A device event of a profile names an instruction of the step program
# (`fusion.20`); the compiled program's text says where each instruction
# came from: JAX writes the name stack (Flax's module path, every
# `jax.named_scope`, the transformations round them) into `op_name`, on the
# instructions inside fused computations too.

PHASES = ("forward", "recompute", "backward", "update", "other")
KINDS = ("matmul", "kernel", "collective", "copy", "elementwise")
UPDATE_SCOPE = "optimizer"  # what `Trainer`'s step wraps the update in

_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all", "collective-broadcast",
)
_RELAYOUTS = ("copy", "transpose", "reshape", "bitcast-convert", "slice")
# What a fused computation holds besides its work.
_PLUMBING = (
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "fusion",  # a nested one: its own instructions follow it
)
# Frames of a name stack that are transformations, not places: `jvp(x)`
# and its kin wrap the outermost scope they were applied under, `jit(f)`
# names a function, the bare words are call-like primitives.
_WRAPPER = re.compile(r"^([A-Za-z_][A-Za-z_0-9]*)\((.*)\)$")
_NOT_PLACES = frozenset((
    "jit", "pjit", "checkpoint", "rematted_computation", "remat",
    "custom_vjp_call", "custom_vjp_call_jaxpr", "custom_jvp_call",
    "custom_lin", "shard_map", "closed_call", "core_call", "while", "body",
    "cond", "body_fun", "cond_fun", "scan", "named",
))
_BRANCH = re.compile(r"^branch_\d+_fun$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?(\S+) = (.*)$")
_COMPUTATION = re.compile(r"^(ENTRY )?%?(\S+) \(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_ARRAY = re.compile(r"[a-z]+\d*\[([\d,]*)\]")
_OPERAND = re.compile(r"%([^\s,(){}]+)")
_CALLED = re.compile(
    r"\b(calls|body|condition|to_apply|true_computation|false_computation)"
    r"=%?([^\s,(){}]+)"
)
_CALLED_LIST = re.compile(r"\b(?:branch|called)_computations=\{([^}]*)\}")
_LHS_CONTRACTING = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_DIM_LABELS = re.compile(r"dim_labels=\w+_(\w+)->")
_NUMBERED = re.compile(r"\.\d+$")
_CALLERS = ("while", "conditional", "call", "async-start")


@dataclasses.dataclass(frozen=True)
class Scope:
    """Where one instruction of a compiled program came from."""

    path: str               # `layer_3/attn/wq`, `loss`, `optimizer`; `` unknown
    phase: str              # one of PHASES
    kind: str               # one of KINDS
    mixed: tuple[str, ...] = ()  # other paths in the same fused computation


@dataclasses.dataclass
class _Instruction:
    name: str
    opcode: str
    dims: tuple[int, ...]   # of its (largest) result
    op_name: str
    operands: list[str]
    called: list[str]
    attributes: str         # the text after the operands

    @property
    def elements(self) -> int:
        return math.prod(self.dims)


def _closes(text: str, depth: int = 0) -> int:
    """Where the bracket that is `depth` deep at `text`'s start (or, from
    0, the one `text` opens with) closes; `len(text)` if it never does."""
    for i, c in enumerate(text):
        depth += (c == "(") - (c == ")")
        if depth == 0:
            return i
    return len(text)


def _parse_instruction(name: str, text: str) -> _Instruction:
    # `<result> <opcode>(<operands>), <attributes>`; a tuple's result is
    # bracketed, an array's has no space.
    cut = _closes(text) + 1 if text.startswith("(") else text.index(" ")
    opcode, _, tail = text[cut:].partition("(")
    shapes = [
        tuple(int(d) for d in dims.split(",") if d)
        for dims in _ARRAY.findall(text[:cut])
    ]
    end = _closes(tail, 1)
    attributes = tail[end:]
    called = [m.group(2) for m in _CALLED.finditer(attributes)]
    for group in _CALLED_LIST.findall(attributes):
        called += [c.strip().lstrip("%") for c in group.split(",") if c.strip()]
    found = _OP_NAME.search(attributes)
    return _Instruction(
        name, opcode.strip(), max(shapes, key=math.prod, default=()),
        found.group(1) if found else "", _OPERAND.findall(tail[:end]),
        called, attributes,
    )


def _parse_computations(hlo_text: str) -> tuple[dict[str, list[_Instruction]], str]:
    """Every computation of a module's text by name, and the entry's name."""
    computations: dict[str, list[_Instruction]] = {}
    entry, current = "", None
    for line in hlo_text.splitlines():
        if current is not None:
            m = _INSTRUCTION.match(line)
            if m:
                current.append(_parse_instruction(m.group(1), m.group(2)))
            elif line.startswith("}"):
                current = None
            continue
        m = _COMPUTATION.match(line)
        if m:
            current = computations.setdefault(m.group(2), [])
            if m.group(1):
                entry = m.group(2)
    return computations, entry


def _split_frames(op_name: str) -> list[str]:
    """`a/jvp(b/c)/d` -> [`a`, `jvp(b/c)`, `d`]: a `/` inside brackets
    (an einsum's `bsd,vd->bsv` has none, a lambda's name may) stays."""
    frames, depth, start = [], 0, 0
    for i, c in enumerate(op_name):
        depth += (c == "(") - (c == ")")
        if c == "/" and depth == 0:
            frames.append(op_name[start:i])
            start = i + 1
    frames.append(op_name[start:])
    return frames


def scope_of_op_name(op_name: str, root: str = "") -> tuple[str, str]:
    """(`path`, `phase`) of one `op_name`. The path is the name stack with
    the transformations unwrapped (`jvp(loss)` -> `loss`), the frames that
    are no place dropped (`jit(f)`, `checkpoint`, ...), the primitive at its
    end and the root module's name `root` taken off (and with it whatever
    stands before its last appearance). The phase: `update`
    under the `optimizer` scope; under a `transpose(` `recompute` if also
    under `rematted_computation` (the forward run again; a `checkpoint`
    frame alone marks the backward OF a checkpointed block) and `backward`
    if not; `forward` under a `jvp(`; `other` outside all of them."""
    # Two instructions made one keep both names, `a;b`: the first says where.
    frames = _split_frames(op_name.split(";")[0])
    if len(frames) < 2:  # a bare primitive, or an argument's name
        return "", "other"
    if frames[0] in frames[1:]:
        # A function lowered once and called from many places keeps the
        # stack of its first lowering behind each call's own: the call's
        # comes first, and only the primitive is kept of the rest.
        frames = frames[: frames.index(frames[0], 1)] + frames[-1:]
    transforms, places = set(), []
    for i, frame in enumerate(frames):
        last = i == len(frames) - 1
        while True:
            m = _WRAPPER.match(frame)
            if not m:
                break
            transforms.add(m.group(1))
            if m.group(1) in ("jit", "pjit"):
                frame = ""
                break
            frame = m.group(2)
            last = False  # `jvp(loss)` at the end is a place, not a primitive
        if frame in _NOT_PLACES or _BRANCH.match(frame):
            transforms.add(frame)
            continue
        if root and frame == root:
            # A checkpoint inside a named module traces its body from the
            # root again (`mtp/jvp(TransformerLM)/mtp/checkpoint/block`):
            # the path starts over, or the module's frame stands twice.
            places = []
        elif frame and not last:
            places.append(frame)
    path = "/".join(places)
    if places and places[0] == UPDATE_SCOPE:
        phase = "update"
    elif "transpose" in transforms:
        phase = (
            "recompute" if "rematted_computation" in transforms else "backward"
        )
    elif "jvp" in transforms:
        phase = "forward"
    else:
        phase = "other"
    return path, phase


def _plain_kind(opcode: str, attributes: str) -> str:
    for suffix in ("-start", "-done", "-update"):
        if opcode.endswith(suffix):
            opcode = opcode[: -len(suffix)]
    if opcode in ("dot", "convolution"):
        return "matmul"
    if opcode == "custom-call":
        kernel = 'custom_call_target="tpu_custom_call"' in attributes
        return "kernel" if kernel else "elementwise"
    if opcode in _COLLECTIVES:
        return "collective"
    if opcode in _RELAYOUTS:
        return "copy"
    return "elementwise"


def _matmul_weight(inst: _Instruction, dims_of: dict[str, tuple[int, ...]]) -> int:
    """Result elements x contracted size of a `dot` or `convolution`."""
    contracted = 1
    if inst.opcode == "dot":
        m = _LHS_CONTRACTING.search(inst.attributes)
        lhs = dims_of.get(inst.operands[0], ()) if inst.operands else ()
        for d in (m.group(1).split(",") if m else []):
            if d and int(d) < len(lhs):
                contracted *= lhs[int(d)]
    else:
        # The kernel's elements over its output features: window x inputs.
        m = _DIM_LABELS.search(inst.attributes)
        rhs = dims_of.get(inst.operands[1], ()) if len(inst.operands) > 1 else ()
        if m and "o" in m.group(1) and len(rhs) == len(m.group(1)):
            contracted = math.prod(rhs) // max(1, rhs[m.group(1).index("o")])
    return inst.elements * max(1, contracted)


def _fused_body(
    computations: dict[str, list[_Instruction]], name: str
) -> list[_Instruction]:
    """A fused computation's instructions, those of the fusions it nests
    (a scatter inside a loop fusion is one) behind each nested one."""
    body = []
    for inst in computations.get(name, []):
        body.append(inst)
        if inst.opcode == "fusion" and inst.called:
            body += _fused_body(computations, inst.called[0])
    return body


def _fusion_scope(own: _Instruction, body: list[_Instruction], root: str) -> Scope:
    """The rule for a fusion (see `program_scopes`)."""
    dims_of = {i.name: i.dims for i in body}
    work = [i for i in body if i.opcode not in _PLUMBING]
    matmuls = [i for i in work if i.opcode in ("dot", "convolution")]
    if matmuls:
        kind = "matmul"
    elif any(_plain_kind(i.opcode, i.attributes) == "collective" for i in work):
        kind = "collective"
    elif all(i.opcode in _RELAYOUTS for i in work):
        kind = "copy"
    else:
        kind = "elementwise"
    scoped = [
        (i, *scope_of_op_name(i.op_name, root)) for i in work if i.op_name
    ]
    scoped = [s for s in scoped if s[1]]
    if not scoped:
        path, phase = scope_of_op_name(own.op_name, root)
        return Scope(path, phase, kind)
    named_matmuls = [s for s in scoped if s[0] in matmuls]
    if named_matmuls:
        heaviest = max(
            named_matmuls, key=lambda s: _matmul_weight(s[0], dims_of)
        )
    else:  # the largest result; of equals the one nearest the root
        heaviest = max(
            enumerate(scoped), key=lambda ks: (ks[1][0].elements, ks[0])
        )[1]
    _, path, phase = heaviest
    mixed = tuple(sorted({s[1] for s in scoped} - {path}))
    return Scope(path, phase, kind, mixed)


def program_scopes(hlo_text: str, root: str = "") -> dict[str, Scope]:
    """Where each instruction of a compiled module came from, by the
    instruction's name as a profile prints it (`fusion.20`,
    `flash_fwd_compact.8`, `all-reduce.314`).

    A pure function over `compiled.as_text()`. It covers every instruction
    the device can run as an event: the entry computation's and those of
    the bodies it calls (`while`, `conditional`, `call`, the async
    wrappers), not the insides of fused computations or of reductions.
    `root` is the root module's name (`TransformerLM`), which Flax puts at
    the head of every path and which says nothing: taken off.

    `path` and `phase` come from the instruction's `op_name`
    (`scope_of_op_name`). `kind` is `matmul` (a `dot` or `convolution`, or
    a fusion that holds one), `kernel` (a `tpu_custom_call`), `collective`,
    `copy` (copy / transpose / reshape / bitcast-convert / slice and their
    `-start` / `-done`, or a fusion of nothing else) or `elementwise`.

    **The rule for a fusion.** Its own `op_name` is its root's, and the
    root of a weight gradient with AdamW fused in is the update, while
    nearly all its time is the matmul's. So a fusion's `path` and `phase`
    are those of the heaviest instruction of its fused computation: a
    `dot` / `convolution` if it holds one (the largest by result elements
    x contracted size), else the instruction with the largest result;
    what the fusions it nests hold counts as its own. `mixed` lists the other paths the computation holds: such a gradient
    reads `layer_3/mlp/wi_up`, `backward`, `matmul`, `("optimizer",)`.

    A relayout the compiler added carries no `op_name`: a `copy`-kind
    instruction without one takes the scope of what it moves (its first
    operand's), and is left without a path where that has none either."""
    computations, entry = _parse_computations(hlo_text)
    scopes: dict[str, Scope] = {}
    moved: dict[str, str] = {}  # a relayout without a name -> its operand
    seen, queue = {entry}, [entry]
    while queue:
        for inst in computations.get(queue.pop(), []):
            if inst.opcode == "fusion" and inst.called:
                scope = _fusion_scope(
                    inst, _fused_body(computations, inst.called[0]), root
                )
            else:
                # An async wrapper says what it wraps in its name only.
                opcode = (
                    _NUMBERED.sub("", inst.name)
                    if inst.opcode.startswith("async-") else inst.opcode
                )
                scope = Scope(
                    *scope_of_op_name(inst.op_name, root),
                    _plain_kind(opcode, inst.attributes),
                )
                # A reduction's `to_apply` runs inside its instruction;
                # these bodies' instructions are events of their own.
                if inst.opcode in _CALLERS:
                    fresh = [c for c in inst.called if c not in seen]
                    seen.update(fresh)
                    queue.extend(fresh)
            if not scope.path and scope.kind == "copy" and inst.operands:
                moved[inst.name] = inst.operands[0]
            scopes[inst.name] = scope
    for name in moved:
        source, hops = moved[name], 0
        while source in moved and hops < 8:  # copy-done -> copy-start -> x
            source, hops = moved[source], hops + 1
        found = scopes.get(source)
        if found is not None and found.path:
            scopes[name] = dataclasses.replace(
                scopes[name], path=found.path, phase=found.phase
            )
    return scopes


class StepProgram:
    """A jitted step and the abstract arguments it runs with: calling it
    gives the step's table of scopes, made once and kept. It holds the
    `jax.jit` object and `ShapeDtypeStruct`s, never an array, so a
    process-wide registry may keep it after the trainer is gone."""

    def __init__(self, jitted, root: str = ""):
        self.jitted = jitted
        self.root = root
        self.arguments: tuple | None = None
        self._scopes: dict[str, Scope] | None = None

    def note(self, *arguments) -> None:
        """The shapes, dtypes and shardings the step is called with."""
        noted = jax.tree_util.tree_map(_abstract, arguments)
        if noted != self.arguments:
            self.arguments, self._scopes = noted, None

    def text(self) -> str:
        """The compiled step's text: lowered and compiled at the noted
        arguments. Where the process still holds the real step's trace
        that is the cached lowering and executable; after
        `jax.clear_caches()` it is traced again, and a step with Pallas
        kernels is then compiled again too (a kernel's payload holds the
        Python frames of its first trace, so the compile cache's key is
        another): the same program, instruction for instruction."""
        if self.arguments is None:
            raise ValueError(
                "no arguments noted for this step: fit() notes its first "
                "step's, or hand step_scopes() a batch"
            )
        return self.jitted.lower(*self.arguments).compile().as_text()

    def __call__(self) -> dict[str, Scope]:
        if self._scopes is None:
            self._scopes = program_scopes(self.text(), self.root)
        return self._scopes


def _abstract(x) -> jax.ShapeDtypeStruct:
    if isinstance(x, jax.ShapeDtypeStruct):
        return x
    # An uncommitted array goes where the step's other arguments are.
    sharding = x.sharding if getattr(x, "committed", False) else None
    return jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x), sharding=sharding)


_STEP_PROGRAMS: dict[str, StepProgram] = {}


def step_programs() -> dict[str, StepProgram]:
    """The process's step programs by module name as a profile's `XLA
    Modules` line prints it (`jit_train_step`): each a callable that gives
    the table of `program_scopes`. `Trainer.make_train_step()` sets its
    own; a reader with no handle on the trainer (`Profiler`, a benchmark's
    metric reader) finds it here. The newest step of a name wins."""
    return _STEP_PROGRAMS


def write_step_scopes(logdir: pathlib.Path) -> pathlib.Path | None:
    """`<logdir>/step_scopes.json`: every registered step's table,
    `{module: {instruction: {path, phase, kind, mixed}}}`, beside the
    profile whose device events name those instructions. None (and no
    file) where no step is registered or none can be compiled."""
    tables = {}
    for module, program in step_programs().items():
        try:
            tables[module] = {
                name: dataclasses.asdict(scope)
                for name, scope in program().items()
            }
        except Exception:  # a profile is still worth having without it
            log.exception("profiler: no table of scopes for %s", module)
    if not tables:
        return None
    path = pathlib.Path(logdir) / "step_scopes.json"
    path.write_text(json.dumps(tables))
    return path


# -- per-phase roofline ------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published peaks of ONE chip: bf16 matmul throughput and HBM
    bandwidth."""

    tflops_bf16: float
    hbm_gbps: float
    source: str


# The one table of chip peaks, keyed by `jax.Device.device_kind`. Every
# MFU and roofline share divides by an entry of it; a device that is
# not here is an error (`chip_peaks`), never a default.
CHIP_PEAKS: dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        197.0, 819.0, 'Google Cloud documentation, "TPU v5e"'
    ),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of the chip `jax.devices()[0].device_kind` names."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(CHIP_PEAKS)}); a utilization against "
            "another chip's peak is not a measurement — add the chip "
            "to CHIP_PEAKS with its source"
        ) from None


def time_phase(fn, *args, warmup: int = 2, steps: int = 5) -> float:
    """Milliseconds per call of `fn(*args)`, fence-disciplined.

    Same contract as bench.py's `timed_run`: the warmup ends — and the
    timed window closes — with a scalar device_get of the first output
    leaf, which cannot return before the device has executed."""
    import jax

    out = None
    for _ in range(max(1, warmup)):
        out = fn(*args)
    float(jax.tree_util.tree_leaves(out)[0].sum())
    t0 = time.perf_counter()
    for _ in range(max(1, steps)):
        out = fn(*args)
    float(jax.tree_util.tree_leaves(out)[0].sum())
    return (time.perf_counter() - t0) / max(1, steps) * 1000.0


@dataclasses.dataclass(frozen=True)
class PhaseStat:
    """One measured phase with its modeled work: wall-clock plus the
    analytic TFLOP / GB-moved the phase's schedule says it must do
    (model FLOPs and modeled HBM bytes — recompute is NOT counted,
    matching the MFU convention)."""

    name: str
    ms: float
    tflop: float
    gb: float

    def achieved_tflops(self) -> float:
        return self.tflop / (self.ms / 1000.0) if self.ms > 0 else 0.0

    def achieved_gbps(self) -> float:
        return self.gb / (self.ms / 1000.0) if self.ms > 0 else 0.0


class PhaseRoofline:
    """Mechanical per-phase roofline: add phases, read the table.

    `bound_by` mirrors the classification convention of the hand-built
    Round-5 table (docs/architecture.md): the phase is "HBM" when
    bandwidth utilization dominates compute by >= 0.3 of peak,
    "MXU-side" when compute dominates by >= 0.15, and "mixed → <dominant>"
    in between — the mixed labels name the resource any further win
    must come from. The peaks are the caller's to name (`chip_peaks` of
    the device it ran on); with no peaks (0) the shares read 0 and the
    bound "not measured"."""

    def __init__(self, peak_tflops: float, peak_gbps: float):
        self.peak_tflops = peak_tflops
        self.peak_gbps = peak_gbps
        self.phases: list[PhaseStat] = []

    def add(self, name: str, *, ms: float, tflop: float, gb: float) -> dict:
        self.phases.append(PhaseStat(name, ms, tflop, gb))
        return self.rows()[-1]

    def _bound(self, compute_frac: float, bw_frac: float) -> str:
        if not (self.peak_tflops and self.peak_gbps):
            return "not measured"
        if bw_frac - compute_frac >= 0.3:
            return "HBM"
        if compute_frac - bw_frac >= 0.15:
            return "MXU-side"
        return "mixed → HBM" if bw_frac >= compute_frac else "mixed → MXU"

    def rows(self) -> list[dict]:
        out = []
        for p in self.phases:
            tf = p.achieved_tflops()
            gbps = p.achieved_gbps()
            cf = tf / self.peak_tflops if self.peak_tflops else 0.0
            bf = gbps / self.peak_gbps if self.peak_gbps else 0.0
            out.append(
                {
                    "phase": p.name,
                    "ms": round(p.ms, 2),
                    "tflop": round(p.tflop, 2),
                    "gb": round(p.gb, 2),
                    "achieved_tflops": round(tf, 1),
                    "achieved_gbps": round(gbps, 1),
                    "compute_frac": round(cf, 3),
                    "bw_frac": round(bf, 3),
                    "bound_by": self._bound(cf, bf),
                }
            )
        return out

    def saturated(self) -> str:
        """The step's binding resource: the bound of the phase that
        spends the most wall-clock (what "attack the dominant phase"
        should attack)."""
        if not self.phases:
            return "none"
        rows = self.rows()
        top = max(rows, key=lambda r: r["ms"])
        return f"{top['phase']}: {top['bound_by']}"

    def table(self) -> str:
        """Markdown table, same columns as the Round-5 hand-built one."""
        lines = [
            "| phase | ms | TFLOP | GB moved | achieved | bound by |",
            "|---|---|---|---|---|---|",
        ]
        for r in self.rows():
            lines.append(
                f"| {r['phase']} | {r['ms']:g} | {r['tflop']:g} | "
                f"{r['gb']:g} | {r['achieved_tflops']:g} TF/s "
                f"({r['compute_frac'] * 100:.0f}%), "
                f"{r['achieved_gbps']:g} GB/s "
                f"({r['bw_frac'] * 100:.0f}%) | {r['bound_by']} |"
            )
        return "\n".join(lines)


class MetricsLogger:
    """JSONL metrics sink living next to the profile traces, so one
    `Tensorboard` CR's logspath covers both step metrics and the profile
    plugin (the dashboard's activities view reads the same file)."""

    def __init__(self, logdir: str | pathlib.Path, filename: str = "metrics.jsonl"):
        self.path = pathlib.Path(logdir) / filename
        self.path.parent.mkdir(parents=True, exist_ok=True)

    def __call__(self, step: int, record: dict[str, Any]) -> None:
        with self.path.open("a") as f:
            f.write(
                json.dumps({"ts": time.time(), "step": step, **record}) + "\n"
            )

    def read(self) -> list[dict]:
        if not self.path.exists():
            return []
        return [
            json.loads(line)
            for line in self.path.read_text().splitlines()
            if line.strip()
        ]
