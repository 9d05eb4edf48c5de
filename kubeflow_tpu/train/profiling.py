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
(`tracing.tracer.span`), which `fit()` reports to. Also here: the per-phase
roofline layer (`time_phase`, `PhaseRoofline`): the mechanical version
of the hand-built phase table in docs/architecture.md Round 5. A bench
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
import pathlib
import time
from typing import Any

import jax

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

    def close(self) -> None:
        if self._active:
            self._stop()

    @property
    def trace_written(self) -> bool:
        return self._done


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
