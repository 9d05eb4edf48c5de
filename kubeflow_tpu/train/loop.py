"""The training loop: resume, step, guard, checkpoint, report.

Failure semantics the reference lacked (SURVEY.md §5 "no elastic training,
no preemption handling") — the full matrix lives in docs/resilience.md:

- **Auto-resume.** The loop restores the newest VALID checkpoint
  (`train/checkpoint.py` verifies manifests and falls back past
  corruption) and, when the data iterable implements the resumable-data
  protocol, repositions it from the state saved in that checkpoint — so
  a restarted run neither repeats nor skips batches.
- **Anomaly guard.** A trainer built with an `AnomalyGuard`
  (`train/guard.py`) screens EVERY step on device: non-finite or
  spiking steps are skipped, not applied, so a NaN at step 51 can never
  reach the step-100 checkpoint. On sustained divergence (bounded
  consecutive skips) the loop rolls back to the last checkpoint and
  perturbs the data seed — a different trajectory instead of a dead run.
- **Preemption.** SIGTERM/SIGINT is caught and honored at the next step
  boundary: one forced save (with data state), then a clean exit with a
  distinct `Preempted` result — the TpuJob operator's gang-restart
  policy composes with it to give checkpoint-restart elasticity with
  zero lost work.
- **Elastic resize.** A loop built with an `ElasticResize` can ABSORB a
  preemption instead of dying: when the scheduler has offered a
  shrink-to-fit target (`controllers/tpujob.py` resize proposals), the
  loop reshapes the mesh at the step boundary — rebuild the mesh at the
  new dp, re-shard the live `TrainState` across device sets (no
  checkpoint round-trip; `restore_latest` into the new topology is the
  fallback when a host is already gone), transplant the resumable-data
  state — and keeps training with the SAME global batch, so the
  trajectory (and the (step -> batch position) identity mapping) is
  unchanged. Growing back when capacity returns rides the same
  transition. Steps lost per preemption: ~0, vs a save-interval's worth
  under gang restart.
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import os
import signal as signal_module
import sys
import time
from typing import Any, Callable, Iterable

import jax
import numpy as np

from kubeflow_tpu.train.checkpoint import Checkpointer
from kubeflow_tpu.train.profiling import Profiler
from kubeflow_tpu.train.trainer import Trainer, TrainState
from kubeflow_tpu.utils import compile_cache, tracing

log = logging.getLogger(__name__)

# The spans whose seconds since the last record every `on_metrics` record
# carries (`data_s`, `dispatch_s`, `readback_s`, `save_s`).
_RECORD_SPANS = ("data", "dispatch", "readback", "save")
_COMPILE_SPANS = tuple(compile_cache.SPAN_OF.values())


class _Timings:
    """fit()'s one timing path: a span of the process's tracer (the ring
    always, the profile when one is being taken) whose duration is also
    added up by name, without the `train.` prefix. The `compile.*` spans
    the compile observer records on this thread meanwhile (`compiled`,
    `compile_cache.compiled_here()`'s list) are added up under their own
    names."""

    def __init__(self, compiled: list[tracing.Span]) -> None:
        self.totals: dict[str, dict[str, float]] = {}
        self._compiled = compiled

    def _add(self, name: str, span: tracing.Span) -> None:
        total = self.totals.setdefault(name, {"count": 0, "seconds": 0.0})
        total["count"] += 1
        total["seconds"] += span.duration_ns / 1e9

    @contextlib.contextmanager
    def span(self, name: str, **attributes: Any):
        span = None
        try:
            with tracing.tracer.span(f"train.{name}", **attributes) as span:
                yield span
        finally:
            if span is not None:
                self._add(name, span)

    def absorb(self) -> list[tracing.Span]:
        """Adds up what was traced, lowered and compiled since the last
        call, and returns those spans. Nothing, on a step that ran from
        its executable."""
        if not self._compiled:
            return []
        spans = self._compiled[:]
        del self._compiled[:]
        for span in spans:
            self._add(span.name, span)
        return spans

    def seconds(self, name: str) -> float:
        return self.totals.get(name, {}).get("seconds", 0.0)

    def compiled(self) -> tuple[float, int]:
        """(seconds of `compile.*`, backend compiles or loads) so far."""
        self.absorb()
        return (
            sum(self.seconds(name) for name in _COMPILE_SPANS),
            self.totals.get("compile.backend", {}).get("count", 0),
        )


@contextlib.contextmanager
def _call(total_steps: int):
    """One `fit()` call: its `train.fit` span, and the timings that add up
    what the compile observer records on this thread meanwhile."""
    with tracing.tracer.span(
        "train.fit", total_steps=total_steps
    ) as call, compile_cache.compiled_here() as compiled:
        timings = _Timings(compiled)
        try:
            yield call, timings
        finally:
            # `FitResult.timings` is this dictionary: what the exit paths
            # compiled (a save's programs) is in it when the caller reads.
            timings.absorb()


class TrainingDiverged(RuntimeError):
    """Loss became non-finite (guardless runs) or the anomaly guard hit
    its rollback budget; restart from the last checkpoint with a
    different seed/schedule rather than continuing."""


@dataclasses.dataclass(frozen=True)
class ResizeProposal:
    """One elastic-resize target, honored at the next step boundary.

    `source="live"` re-shards the in-memory TrainState across meshes —
    the happy path, no checkpoint round-trip. `source="checkpoint"` is
    the fallback for when part of the old mesh is ALREADY gone (a host
    died with its shards): restore the newest verified checkpoint into
    the new topology instead — `Restored` states are shape-polymorphic
    on dp because checkpoints hold GLOBAL arrays and restore lays them
    out by the target trainer's NamedShardings."""

    dp: int
    source: str = "live"

    def __post_init__(self) -> None:
        if self.source not in ("live", "checkpoint"):
            raise ValueError(
                f"ResizeProposal.source must be 'live' or 'checkpoint', "
                f"got {self.source!r}"
            )


@dataclasses.dataclass(frozen=True)
class ResizeEvent:
    """One completed mesh resize (FitResult.resizes / on_resize)."""

    step: int           # the boundary the transition ran at
    from_dp: int
    to_dp: int
    source: str         # "live" or "checkpoint"
    seconds: float      # transition wall time
    # The preemption signal this resize absorbed (the gang reshaped
    # instead of dying); None for an unprompted resize (grow-back).
    absorbed_signum: int | None = None
    # source="checkpoint" only: the step actually restored (the steps
    # in between are recomputed — they were never durable anywhere).
    restored_step: int | None = None


@dataclasses.dataclass
class ElasticResize:
    """fit()'s elastic gang-resize driver (docs/resilience.md).

    - ``mesh_factory(dp)`` builds the target mesh — typically
      `parallel.mesh.build_mesh`/`build_hybrid_mesh` over the surviving
      hosts' devices.
    - ``data_factory(mesh, data)`` rebuilds the training iterable on the
      new mesh (the streams' ``rebind(mesh)``); fit() then transplants
      the resumable-data state, so batch content — a pure function of
      (seed, salt, position), never the mesh — continues the identity
      (step -> position) mapping: zero repeated or skipped batches.
    - ``propose(step, preempted)`` is polled at every step boundary.
      ``preempted=True`` means a SIGTERM/SIGINT arrived: returning a
      proposal then ABSORBS the signal (the gang shrinks instead of
      dying — the scheduler's shrink-to-fit ack); returning None lets
      the normal `Preempted` exit happen. With ``preempted=False`` a
      proposal drives an unprompted resize (grow-back when capacity
      returns).
    - ``on_resize(event)`` observes each completed transition (trace
      emission, the controller-facing ack).
    """

    mesh_factory: Callable[[int], Any]
    data_factory: Callable[[Any, Any], Any]
    propose: Callable[[int, bool], ResizeProposal | None]
    on_resize: Callable[[ResizeEvent], None] | None = None


def _mesh_dp(trainer: Trainer) -> int:
    return int(trainer.mesh.shape.get("dp", 1))


@dataclasses.dataclass
class FitResult:
    state: TrainState
    history: list[dict]
    steps_done: int
    resumed_from: int | None
    # Divergence rollbacks taken (guarded runs; 0 otherwise).
    rollbacks: int = 0
    # Elastic mesh resizes performed (ElasticResize runs; [] otherwise).
    resizes: list[ResizeEvent] = dataclasses.field(default_factory=list)
    # This call's `train.*` spans added up by name, prefix dropped, and
    # the `compile.*` spans that ended inside it under their whole names:
    # {"data": {"count": 25, "seconds": 9.9}, "dispatch": ...,
    #  "compile.trace": ..., "compile.lower": ..., "compile.backend": ...}.
    timings: dict[str, dict[str, float]] = dataclasses.field(
        default_factory=dict
    )


@dataclasses.dataclass
class Preempted(FitResult):
    """fit() observed SIGTERM/SIGINT: it stopped at a step boundary
    after an emergency forced save — resume from the checkpoint to
    continue with zero lost work. `isinstance(result, Preempted)`
    distinguishes a preemption from completion."""

    signum: int | None = None


def _data_state(data: Any) -> dict | None:
    sd = getattr(data, "state_dict", None)
    return sd() if callable(sd) else None


def _load_data_state(data: Any, state: dict | None) -> None:
    ld = getattr(data, "load_state_dict", None)
    if state is not None and callable(ld):
        ld(state)


def fit(
    trainer: Trainer,
    data: Iterable[dict],
    total_steps: int,
    *,
    rng: jax.Array | None = None,
    checkpointer: Checkpointer | None = None,
    log_every: int = 50,
    on_metrics: Callable[[int, dict], None] | None = None,
    profiler: "Profiler | None" = None,
    handle_signals: bool = True,
    max_rollbacks: int = 3,
    elastic: ElasticResize | None = None,
) -> FitResult:
    """Train for `total_steps` global steps, resuming if possible.

    `handle_signals=False` opts out of the SIGTERM/SIGINT preemption
    handler (e.g. when the caller owns signal disposition); handlers are
    only ever installed on the main thread and are restored on exit.
    `max_rollbacks` bounds divergence rollbacks before the loop gives up
    and raises `TrainingDiverged`. `elastic` enables elastic gang
    resize: proposals are polled at every step boundary, and a proposal
    arriving with a preemption signal absorbs it — the mesh reshapes
    instead of the process dying (see `ElasticResize`).

    The whole call is one `train.fit` span of `utils/tracing.tracer`, so
    its steps share a trace id; what it traces, lowers and compiles
    arrives there as `compile.*` spans under the `train.*` span that
    paid (`utils/compile_cache.py`), is added up in `FitResult.timings`,
    and every record says what it cost since the last one (`compile_s`,
    `compiles`: docs/perf.md).
    """
    compile_cache.observe_compiles()
    # The body stays in THIS frame: with it in a function of its own, one
    # more Python frame under the step's trace, the chip's host lowered the
    # step 0.4 s slower (PERF.md §6, PR 50).
    with _call(total_steps) as (call, timings):
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        guard = trainer.guard

        resumed_from = None
        state = None
        if checkpointer is not None:
            with timings.span("restore"):
                restored = checkpointer.restore_latest(
                    trainer.abstract_state()
                )
            if restored is not None:
                state, resumed_from = restored.state, int(restored.step)
                _load_data_state(data, restored.data_state)
        if state is None:
            with timings.span("init"):
                state = trainer.init_state(rng)

        start_step = int(state.step)
        call.attributes.update(
            start_step=start_step, resumed_from=resumed_from
        )
        if start_step >= total_steps:
            log.info(
                "checkpoint already at step %d >= total_steps %d; "
                "nothing to do",
                start_step, total_steps,
            )
            return FitResult(
                state=state, history=[], steps_done=0,
                resumed_from=resumed_from, timings=timings.totals,
            )

        with timings.span("init"):
            step_fn = trainer.make_train_step()
        noted = False  # the step's arguments, for `trainer.step_scopes()`
        it = iter(data)
        history: list[dict] = []
        t_last = time.perf_counter()
        examples = 0
        recorded = dict.fromkeys(_RECORD_SPANS, 0.0)
        recorded_compile = (0.0, 0)
        rollbacks = 0
        resizes: list[ResizeEvent] = []
        preempt: dict = {"signum": None}
        installed: dict = {}
        if handle_signals:
            def _restore_handlers() -> None:
                for sig, prev in installed.items():
                    # prev is None when the pre-fit handler was installed
                    # outside Python (sigaction in a launcher/C extension);
                    # signal.signal(sig, None) raises TypeError, so fall
                    # back to SIG_DFL — imperfect, but it neither crashes
                    # nor leaves our flag-setter swallowing signals.
                    signal_module.signal(
                        sig,
                        prev if prev is not None else signal_module.SIG_DFL,
                    )

            def _on_signal(signum, frame):
                if preempt["signum"] is not None:
                    # Second delivery (e.g. Ctrl-C during a multi-minute
                    # XLA compile that never reaches a step boundary):
                    # escalate — restore the pre-fit disposition and
                    # re-deliver so the default behavior (KeyboardInterrupt
                    # / termination) applies instead of a dead flag.
                    _restore_handlers()
                    os.kill(os.getpid(), signum)
                    return
                # Flag only: the loop honors it at the next step boundary
                # (an async save mid-step would tear the state).
                preempt["signum"] = signum

            try:
                for sig in (signal_module.SIGTERM, signal_module.SIGINT):
                    installed[sig] = signal_module.signal(sig, _on_signal)
            except ValueError:  # not the main thread: caller owns signals
                installed = {}

        def check_finite(metrics, step: int) -> float:
            loss = float(metrics["loss"])
            if not np.isfinite(loss):
                # Never persisted: the check runs before any save at this
                # step, so resume always lands on the last finite state.
                raise TrainingDiverged(
                    f"non-finite loss {loss} at step {step}"
                )
            return loss

        def rollback(step: int) -> tuple[TrainState, int]:
            """Divergence: restore the last good checkpoint and perturb the
            data seed so the retried trajectory differs."""
            nonlocal it
            restored = (
                checkpointer.restore_latest(trainer.abstract_state())
                if checkpointer is not None
                else None
            )
            if restored is None:
                raise TrainingDiverged(
                    f"sustained divergence at step {step} and no checkpoint "
                    "to roll back to"
                )
            perturb = getattr(data, "perturb", None)
            if (
                restored.data_state is None
                or not callable(getattr(data, "load_state_dict", None))
                or not callable(perturb)
            ):
                # Without resumable data the replayed steps would silently
                # consume batch positions that don't match their step
                # numbers (a fresh iter() restarts a list, a generator just
                # keeps going); without perturb() the replay is a
                # deterministic re-run that diverges identically — either
                # way, refuse up front rather than burn the rollback budget
                # on wrong or provably futile retries.
                raise TrainingDiverged(
                    f"sustained divergence at step {step}: rollback needs "
                    "resumable, perturbable data (state_dict/"
                    "load_state_dict/perturb — see docs/resilience.md); "
                    "restart manually from the last checkpoint with a "
                    "different data order instead"
                )
            _load_data_state(data, restored.data_state)
            # Monotonic salt: past the checkpoint's own salt (which a prior
            # incarnation's rollback may already have burned) AND past this
            # process's earlier attempts — every retry gets a genuinely new
            # trajectory, never a replay of one that already diverged.
            salt = int(restored.data_state.get("salt", 0)) + rollbacks
            perturb(salt)
            # Make the perturbed salt durable NOW by rewriting the restored
            # step's manifest data_state (checksums untouched): the next
            # periodic save may be a full interval away, and a crash in that
            # window would otherwise resume onto the already-diverged salt
            # and re-burn the whole divergence segment every incarnation.
            checkpointer.update_data_state(
                int(restored.step), _data_state(data)
            )
            it = iter(data)
            log.warning(
                "anomaly guard: sustained divergence at step %d; rolled back "
                "to checkpoint step %d (rollback %d/%d, data salt -> %d)",
                step, restored.step, rollbacks, max_rollbacks, salt,
            )
            return restored.state, int(restored.step)

        result: FitResult | None = None
        step = start_step
        try:
            while step < total_steps:
                # The parent span of one iteration carries the number of the
                # step it completes (the `step` of a record): a
                # StepTraceAnnotation, so a profile's step view groups by it.
                with timings.span("step", step_num=step + 1):
                    with timings.span("data"):
                        try:
                            batch = next(it)
                        except StopIteration:
                            raise ValueError(
                                f"data iterable exhausted at step {step} "
                                f"(needed {total_steps})"
                            ) from None
                    if profiler is not None:
                        profiler.before_step(step)
                    # The first step of the call, and of a resized trainer,
                    # builds its program; any other that does is a finding.
                    builds = not noted
                    if builds:
                        trainer.note_step_arguments(state, batch)
                        noted = True
                    with timings.span("dispatch") as dispatch:
                        state, metrics = step_fn(state, batch)
                    paid = [
                        span for span in timings.absorb()
                        if span.parent_id == dispatch.span_id
                    ]
                    if paid and not builds:
                        log.warning(
                            "step %d compiled again: %s, %.2f s of trace, "
                            "lowering and compile (a batch or a state of "
                            "another shape, dtype or sharding?)",
                            step + 1,
                            ", ".join(dict.fromkeys(
                                str(span.attributes["fun_name"])
                                for span in paid
                            )),
                            sum(span.duration_ns for span in paid) / 1e9,
                        )
                    if profiler is not None:
                        profiler.after_step(step)
                    step += 1
                    examples += trainer.config.batch_size
                    is_last = step == total_steps
                    preempted = preempt["signum"] is not None
                    want_save = checkpointer is not None and (
                        checkpointer.should_save(step) or is_last
                    )
                    # A preempted boundary always logs: the exit step must
                    # reach history/on_metrics before the loop returns.
                    want_log = step % log_every == 0 or is_last or preempted

                    # Guard verdicts are device scalars; read them only where
                    # the host syncs anyway (boundaries), never per step.
                    if guard is not None and (
                        want_save or want_log or preempted
                    ):
                        with timings.span("readback"):
                            diverged = guard.diverged(state.guard)
                        if diverged:
                            if preempted or rollbacks >= max_rollbacks:
                                # Dying or out of budget: the last good
                                # checkpoint stays the recovery point — never
                                # save (or roll back under) a diverged state.
                                raise TrainingDiverged(
                                    f"sustained divergence at step {step} "
                                    f"after {rollbacks} rollback(s)"
                                )
                            rollbacks += 1
                            with timings.span("rollback"):
                                state, step = rollback(step)
                            continue

                    saved = False
                    if want_save:
                        if guard is None:
                            with timings.span("readback"):
                                check_finite(metrics, step)
                        with timings.span("save"):
                            checkpointer.save(
                                step, state,
                                force=is_last or preempted,
                                data_state=_data_state(data),
                            )
                        saved = True
                    if want_log:
                        with timings.span("readback"):
                            if guard is None:
                                loss = check_finite(metrics, step)
                            else:
                                # A skipped step may legitimately log a
                                # non-finite loss — the update was rejected on
                                # device, so the STATE stayed finite; nothing
                                # here can persist it.
                                loss = float(metrics["loss"])
                            rec = {
                                "step": step,
                                "loss": loss,
                                # Absent in train_metrics="loss" mode (LM
                                # trainers skip the per-step full-vocab
                                # argmax).
                                "accuracy": float(
                                    metrics.get("accuracy", float("nan"))
                                ),
                            }
                            if guard is not None:
                                rec["grad_norm"] = float(metrics["grad_norm"])
                                rec["guard_skipped_total"] = int(
                                    metrics["guard_skipped_total"]
                                )
                                rec["rollbacks"] = rollbacks
                            # What the model counted in this step (the
                            # expert layer's routed tokens), summed over
                            # layers.
                            counters = metrics.get("counters", {})
                            for name, value in counters.items():
                                rec[name] = float(value)
                        now = time.perf_counter()
                        rec["examples_per_sec"] = examples / (now - t_last)
                        # Where the host's time went since the last record.
                        for name in _RECORD_SPANS:
                            total = timings.seconds(name)
                            rec[f"{name}_s"] = total - recorded[name]
                            recorded[name] = total
                        # What was traced, lowered and compiled since, and how
                        # many programs: past the first record, a recompile.
                        compile_now = timings.compiled()
                        rec["compile_s"] = compile_now[0] - recorded_compile[0]
                        rec["compiles"] = compile_now[1] - recorded_compile[1]
                        recorded_compile = compile_now
                        history.append(rec)
                        if on_metrics is not None:
                            on_metrics(step, rec)
                        log.info(
                            "step %d loss %.4f acc %.3f %.1f ex/s",
                            rec["step"], rec["loss"], rec["accuracy"],
                            rec["examples_per_sec"],
                        )
                        t_last, examples = now, 0
                    # -- elastic resize (docs/resilience.md) -----------------
                    # Polled at the boundary AFTER save/log so the transition
                    # always starts from a fully-accounted step. A proposal
                    # arriving with a preemption signal absorbs it: the gang
                    # reshapes instead of dying, and the loop keeps training —
                    # the whole point of shrink-to-fit over gang restart.
                    if elastic is not None and not is_last:
                        proposal = elastic.propose(step, preempted)
                        if (
                            proposal is not None
                            and proposal.dp != _mesh_dp(trainer)
                        ):
                            from_dp = _mesh_dp(trainer)
                            at_step = step
                            with timings.span("resize") as resize_span:
                                new_mesh = elastic.mesh_factory(proposal.dp)
                                new_trainer = trainer.resize(new_mesh)
                                restored_step = None
                                if proposal.source == "checkpoint":
                                    # Part of the old mesh is already gone (a
                                    # host died with its shards): the live
                                    # state is not recoverable — restore the
                                    # newest verified checkpoint INTO the new
                                    # topology. Checkpoints hold global
                                    # arrays, so the restore is shape-
                                    # polymorphic on dp by construction.
                                    if checkpointer is None:
                                        raise RuntimeError(
                                            "resize with source='checkpoint' "
                                            "needs a checkpointer (the live "
                                            "state went down with the dead "
                                            "host)"
                                        )
                                    restored = checkpointer.restore_latest(
                                        new_trainer.abstract_state()
                                    )
                                    if restored is None:
                                        raise RuntimeError(
                                            f"resize at step {step}: no valid "
                                            "checkpoint to restore into the "
                                            "new topology"
                                        )
                                    state = restored.state
                                    restored_step = step = int(restored.step)
                                    data_state = restored.data_state
                                else:
                                    # Happy path: re-shard the LIVE state
                                    # across device sets — no checkpoint
                                    # round-trip, no recomputed steps.
                                    state = new_trainer.reshard_state(state)
                                    data_state = _data_state(data)
                                trainer = new_trainer
                                data = elastic.data_factory(new_mesh, data)
                                # Transplant the resumable-data state: content
                                # is a pure function of (seed, salt,
                                # position), never the mesh, so the (step ->
                                # position) identity mapping holds across the
                                # resize — zero repeated or skipped batches.
                                _load_data_state(data, data_state)
                                it = iter(data)
                                step_fn = trainer.make_train_step()
                                noted = False
                            event = ResizeEvent(
                                step=at_step,
                                from_dp=from_dp,
                                to_dp=proposal.dp,
                                source=proposal.source,
                                seconds=resize_span.duration_ns / 1e9,
                                absorbed_signum=(
                                    preempt["signum"] if preempted else None
                                ),
                                restored_step=restored_step,
                            )
                            resizes.append(event)
                            log.warning(
                                "elastic resize at step %d: dp %d -> %d "
                                "(source=%s, absorbed_signum=%s, %.2fs)",
                                event.step, event.from_dp, event.to_dp,
                                event.source, event.absorbed_signum,
                                event.seconds,
                            )
                            if elastic.on_resize is not None:
                                elastic.on_resize(event)
                            if preempted:
                                # Absorbed: the preemption cost a resize, not
                                # the gang.
                                preempt["signum"] = None
                                preempted = False
                    if preempted:
                        if checkpointer is not None and not saved:
                            # Emergency save at the boundary: the preemption
                            # costs zero steps.
                            with timings.span("save"):
                                checkpointer.save(
                                    step, state, force=True,
                                    data_state=_data_state(data),
                                )
                        log.warning(
                            "preemption signal %s honored at step %d: %s, "
                            "exiting cleanly",
                            preempt["signum"], step,
                            "emergency save done" if checkpointer is not None
                            else "NO checkpointer — progress not saved",
                        )
                        result = Preempted(
                            state=state,
                            history=history,
                            steps_done=step - start_step,
                            resumed_from=resumed_from,
                            rollbacks=rollbacks,
                            resizes=resizes,
                            timings=timings.totals,
                            signum=preempt["signum"],
                        )
                        break
        finally:
            # Even on the exception path: restore signal disposition, make
            # enqueued saves durable (the last good checkpoint is the
            # recovery point) and close a live trace (a diverging run should
            # still leave a readable profile).
            if installed:
                _restore_handlers()
            if profiler is not None:
                profiler.close()
            if checkpointer is not None:
                if sys.exc_info()[0] is None:
                    # Clean exit (completion or Preempted): a durability
                    # failure here means the "saved" work is NOT safe —
                    # surface it instead of returning a result that claims
                    # zero lost steps.
                    with timings.span("save"):
                        checkpointer.wait()
                else:
                    # An exception is already unwinding (TrainingDiverged,
                    # a KeyboardInterrupt escalation): that is the story —
                    # still try to make enqueued saves durable, but demote
                    # a wait() failure to a log line so it cannot replace
                    # the in-flight exception and break callers' typed
                    # handling.
                    try:
                        with timings.span("save"):
                            checkpointer.wait()
                    except Exception:
                        log.exception(
                            "checkpoint wait failed while another "
                            "exception was unwinding"
                        )

        if result is not None:
            return result
        return FitResult(
            state=state,
            history=history,
            steps_done=total_steps - start_step,
            resumed_from=resumed_from,
            rollbacks=rollbacks,
            resizes=resizes,
            timings=timings.totals,
        )
