"""What a process traces, lowers and compiles arrives in the platform's one
ring as `compile.*` spans (ISSUE 50): the compile observer of
`utils/compile_cache.py` on `Tracer.record`, `fit()`'s account of it in
`FitResult.timings` and in every record, and JAX's own names pinned."""

import logging
import threading
import time

import jax
import jax.monitoring
import jax.numpy as jnp
import pytest

from kubeflow_tpu.parallel import MeshSpec, build_mesh
from kubeflow_tpu.testing.tinymodels import TinyMLP
from kubeflow_tpu.train import SyntheticImages, TrainConfig, Trainer, fit
from kubeflow_tpu.utils import compile_cache, tracing

PHASES = ("compile.trace", "compile.lower", "compile.backend")
CFG = TrainConfig(
    batch_size=8, learning_rate=0.05, warmup_steps=2, total_steps=24,
    fsdp_params=False, weight_decay=0.0,
)


@pytest.fixture(autouse=True)
def observer():
    compile_cache.observe_compiles()
    tracing.tracer.export()  # whatever other tests left


def _program(fun_name: str) -> str:
    """`train_step` of a trace, `jit(train_step)` of the other phases."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


def _compiled(of: str | None = None) -> list[dict]:
    """The ring's `compile.*` spans (left in it), those of one program."""
    return [
        s for s in tracing.tracer.snapshot()
        if s["name"] in PHASES
        and (of is None or _program(s["attributes"]["fun_name"]) == of)
    ]


def _names(spans) -> list[str]:
    return [s["name"] for s in spans]


def _seconds(spans, name) -> float:
    return sum(
        (s["endNs"] - s["startNs"]) / 1e9 for s in spans if s["name"] == name
    )


def test_registering_twice_doubles_nothing():
    from jax._src import monitoring

    for _ in range(3):
        compile_cache.observe_compiles()
    assert monitoring.get_event_duration_listeners().count(
        compile_cache._on_exit) == 1
    assert monitoring.get_scalar_listeners().count(
        compile_cache._on_entry) == 1
    assert monitoring.get_event_listeners().count(
        compile_cache._on_event) == 1


def test_one_shape_is_one_span_a_phase_and_a_new_shape_three_more():
    @jax.jit
    def doubled_once(x):
        return x * 2.0

    x = jnp.ones((4, 4))
    doubled_once(x)
    doubled_once(x)
    assert _names(_compiled("doubled_once")) == list(PHASES)
    doubled_once(jnp.ones((8, 4)))
    spans = _compiled("doubled_once")
    assert _names(spans) == list(PHASES) * 2
    assert [s["attributes"]["fun_name"] for s in spans[:3]] == [
        "doubled_once", "jit(doubled_once)", "jit(doubled_once)",
    ]
    assert all(s["endNs"] >= s["startNs"] for s in spans)
    # no span was open: each is the root of a trace of its own
    assert all(s["parentId"] is None for s in spans)
    assert len({s["traceId"] for s in spans}) == 6
    assert spans[2]["attributes"]["cache"] in ("off", "miss", "hit")


def test_nested_jits_leave_one_trace_no_shorter_than_its_parts():
    """Inner `jit`s are traced inside the outer trace: one span, and the
    ring's trace seconds never pass the wall time the traces took."""
    inner_seconds = []

    def hear(event, duration, **kwargs):
        if (
            event == compile_cache.TRACE_EVENT
            and kwargs["fun_name"].startswith("inner_")
        ):
            inner_seconds.append(duration)

    @jax.jit
    def inner_a(x):
        return jnp.tanh(x) @ x

    @jax.jit
    def inner_b(x):
        return jnp.linalg.norm(inner_a(x))

    @jax.jit
    def outer_of_two(x):
        return inner_a(x).sum() + inner_b(x)

    x = jnp.ones((16, 16))
    tracing.tracer.export()  # the eager program that made x
    jax.monitoring.register_event_duration_secs_listener(hear)
    try:
        t0 = time.perf_counter()
        outer_of_two.trace(x)
        wall = time.perf_counter() - t0
    finally:
        jax.monitoring.unregister_event_duration_listener(hear)
    assert len(inner_seconds) >= 2  # inner_a (once: cached), inner_b
    traces = [s for s in _compiled() if s["name"] == "compile.trace"]
    assert [s["attributes"]["fun_name"] for s in traces] == ["outer_of_two"]
    assert _seconds(traces, "compile.trace") >= max(inner_seconds)
    # the raw events would add up to more than the outer trace alone
    assert _seconds(traces, "compile.trace") <= wall
    # nothing of the inner programs in the other phases either
    outer_of_two(x)
    assert {
        _program(s["attributes"]["fun_name"]) for s in _compiled()
    } == {"outer_of_two"}


def test_a_trace_inside_a_lowering_is_part_of_the_lowering():
    """A lowering rule that traces (a kernel's body, `custom_jvp`'s) adds
    no span: the seconds of trace and lowering are never counted twice."""
    if compile_cache._thread.depth:
        pytest.skip("a phase is open on this thread")
    compile_cache._on_entry(compile_cache.LOWER_EVENT, 0.0, fun_name="jit(f)")
    compile_cache._on_entry(compile_cache.TRACE_EVENT, 0.0, fun_name="body")
    compile_cache._on_exit(compile_cache.TRACE_EVENT, 0.25, fun_name="body")
    assert _compiled() == []
    compile_cache._on_exit(compile_cache.LOWER_EVENT, 0.5, fun_name="jit(f)")
    (span,) = _compiled()
    assert span["name"] == "compile.lower"
    assert span["endNs"] - span["startNs"] == 500_000_000
    # an exit heard without its entry (registered meanwhile) goes no lower
    compile_cache._on_exit(compile_cache.TRACE_EVENT, 0.1, fun_name="late")
    assert compile_cache._thread.depth == 0


@pytest.fixture
def cache_dir(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache

    names = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
        "jax_enable_compilation_cache",
    )
    before = {name: getattr(jax.config, name) for name in names}
    for name, value in zip(names, (str(tmp_path / "cache"), 0.0, -1, True)):
        jax.config.update(name, value)
    compilation_cache.reset_cache()
    yield tmp_path / "cache"
    for name, value in before.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()


def test_the_cache_says_miss_then_hit_with_the_retrieval(cache_dir):
    @jax.jit
    def kept_in_the_cache(x):
        return jnp.cumsum(x * 3.0, axis=0)

    x = jnp.ones((32, 8))
    kept_in_the_cache(x)
    (first,) = [
        s for s in _compiled("kept_in_the_cache")
        if s["name"] == "compile.backend"
    ]
    assert first["attributes"]["cache"] == "miss"
    assert "retrieval_s" not in first["attributes"]
    assert any(cache_dir.iterdir())

    jax.clear_caches()
    kept_in_the_cache(x)
    _, again = [
        s for s in _compiled("kept_in_the_cache")
        if s["name"] == "compile.backend"
    ]
    assert again["attributes"]["cache"] == "hit"
    assert again["attributes"]["retrieval_s"] > 0
    assert "saved_s" in again["attributes"]


def test_without_a_cache_a_compile_reads_off():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        jax.jit(lambda x: x - 7.0)(jnp.ones((3, 5)))
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    (backend,) = [
        s for s in _compiled("<lambda>") if s["name"] == "compile.backend"
    ]
    assert backend["attributes"] == {"fun_name": "jit(<lambda>)", "cache": "off"}


def test_a_span_is_a_child_of_the_span_that_paid_on_its_own_thread():
    @jax.jit
    def paid_for(x):
        return x + 1.0

    @jax.jit
    def elsewhere(x):
        return x + 2.0

    x = jnp.ones((2, 3))
    with tracing.tracer.span("caller") as caller:
        paid_for(x)
        other = threading.Thread(target=elsewhere, args=(x,))
        other.start()
        other.join()
    mine = _compiled("paid_for")
    assert _names(mine) == list(PHASES)
    assert all(s["parentId"] == caller.span_id for s in mine)
    assert all(s["traceId"] == caller.trace_id for s in mine)
    theirs = _compiled("elsewhere")
    assert _names(theirs) == list(PHASES)
    assert all(s["parentId"] is None for s in theirs)


def test_compiled_here_collects_this_threads_spans_only():
    @jax.jit
    def collected(x):
        return x * x

    x = jnp.ones((5,))
    with compile_cache.compiled_here() as spans:
        other = threading.Thread(target=jax.jit(lambda x: x / 3.0), args=(x,))
        other.start()
        other.join()
        assert spans == []
        collected(x)
        assert [s.name for s in spans] == list(PHASES)
    collected(jnp.ones((6,)))  # after the block: the ring alone
    assert len(spans) == 3
    assert len(_compiled("collected")) == 6


def _job(devices):
    mesh = build_mesh(MeshSpec(dp=1), devices[:1])
    trainer = Trainer(TinyMLP(), CFG, mesh, example_input_shape=(2, 8, 8, 3))

    def images(batch):
        return SyntheticImages(
            mesh, batch, image_size=8, num_classes=10, seed=3,
            vary_per_step=True,
        )

    return trainer, images


def _ring_sums(spans) -> dict:
    sums: dict = {}
    for s in spans:
        total = sums.setdefault(s["name"], {"count": 0, "seconds": 0.0})
        total["count"] += 1
        total["seconds"] += (s["endNs"] - s["startNs"]) / 1e9
    return sums


def test_fit_accounts_for_what_it_compiled(devices, caplog):
    trainer, images = _job(devices)
    data = images(8)
    tracing.tracer.export()  # the trainer's and the stream's own programs
    records = []
    with caplog.at_level(logging.WARNING, logger="kubeflow_tpu.train.loop"):
        result = fit(
            trainer, data, total_steps=6, log_every=2,
            on_metrics=lambda step, rec: records.append(rec),
        )
    assert not [r for r in caplog.records if "compiled again" in r.message]
    ring = tracing.tracer.snapshot()
    by_id = {s["spanId"]: s for s in ring}

    # the whole call is one span, and every span of the call is in its trace
    (call,) = [s for s in ring if s["name"] == "train.fit"]
    assert call["parentId"] is None
    assert call["attributes"] == {
        "total_steps": 6, "start_step": 0, "resumed_from": None,
    }
    inside = [s for s in ring if s is not call]
    assert all(s["traceId"] == call["traceId"] for s in inside)
    assert all(
        call["startNs"] <= s["startNs"] <= s["endNs"] <= call["endNs"]
        for s in inside
    )

    # the step's three spans are children of step 1's dispatch
    step = [
        s for s in ring if s["name"] in PHASES
        and _program(s["attributes"]["fun_name"]) == "train_step"
    ]
    assert _names(step) == list(PHASES)
    (parent,) = {s["parentId"] for s in step}
    assert by_id[parent]["name"] == "train.dispatch"
    assert by_id[by_id[parent]["parentId"]]["attributes"]["step_num"] == 1
    # ... and every other under a `train.*` span of the call
    compiled = [s for s in ring if s["name"] in PHASES]
    assert all(
        by_id[s["parentId"]]["name"].startswith("train.") for s in compiled
    )

    # the timings hold the three totals, equal to the ring's sums
    sums = _ring_sums(compiled)
    for name in PHASES:
        assert result.timings[name]["count"] == sums[name]["count"] >= 1
        assert result.timings[name]["seconds"] == pytest.approx(
            sums[name]["seconds"], rel=1e-9
        )
    # ... and never more than the spans that paid
    paid = sum(
        (s["endNs"] - s["startNs"]) / 1e9 for s in ring
        if s["name"].startswith("train.") and s["parentId"] == call["spanId"]
    )
    assert sum(result.timings[name]["seconds"] for name in PHASES) <= paid

    # the records: the first paid for the programs, the later ones for none
    assert [r["step"] for r in records] == [2, 4, 6]
    assert records[0]["compiles"] >= 1 and records[0]["compile_s"] > 0
    assert [r["compiles"] for r in records[1:]] == [0, 0]
    assert [r["compile_s"] for r in records[1:]] == [0.0, 0.0]
    assert sum(r["compiles"] for r in records) == sums[
        "compile.backend"]["count"]
    assert sum(r["compile_s"] for r in records) == pytest.approx(
        sum(sums[name]["seconds"] for name in PHASES), rel=1e-9
    )


def test_a_batch_of_another_shape_is_the_record_that_recompiled(
    devices, caplog
):
    trainer, images = _job(devices)
    small, large = iter(images(8)), iter(images(16))

    def batches():
        for i in range(8):
            yield next(large if i == 4 else small)  # step 5 is another shape

    records = []
    with caplog.at_level(logging.WARNING, logger="kubeflow_tpu.train.loop"):
        result = fit(
            trainer, batches(), total_steps=8, log_every=1,
            on_metrics=lambda step, rec: records.append(rec),
        )
    again = [r["step"] for r in records[1:] if r["compiles"] > 0]
    assert again == [5]
    assert records[4]["compile_s"] > 0
    assert records[4]["compile_s"] <= (
        records[4]["data_s"] + records[4]["dispatch_s"]
    )
    (warning,) = [r for r in caplog.records if "compiled again" in r.message]
    assert warning.levelno == logging.WARNING
    assert "step 5 compiled again" in warning.getMessage()
    assert "train_step" in warning.getMessage()
    # step 5's compile hangs under step 5's dispatch
    ring = tracing.tracer.snapshot()
    by_id = {s["spanId"]: s for s in ring}
    step = [
        s for s in ring if s["name"] == "compile.backend"
        and s["attributes"]["fun_name"] == "jit(train_step)"
    ]
    assert [
        by_id[by_id[s["parentId"]]["parentId"]]["attributes"]["step_num"]
        for s in step
    ] == [1, 5]
    assert result.timings["compile.backend"]["count"] >= 2


def test_record_links_to_the_current_span_and_enters_the_ring():
    t = tracing.Tracer()
    now = time.perf_counter_ns()
    with t.span("outer") as outer:
        inside = t.record("heard", now - 2_000_000, now, fun_name="f")
    alone = t.record("heard", now - 1_000, now)
    assert inside.parent_id == outer.span_id
    assert inside.trace_id == outer.trace_id
    assert alone.parent_id is None and alone.trace_id != outer.trace_id
    rec = inside.to_dict()
    assert rec["durationMs"] == pytest.approx(2.0)
    assert rec["attributes"] == {"fun_name": "f"}
    assert rec["end"] - rec["start"] == pytest.approx(0.002, abs=1e-5)
    assert abs(rec["end"] - time.time()) < 5.0  # wall clock, about now
    # a finished span is kept before the span it ended in
    assert _names(t.snapshot()) == ["heard", "outer", "heard"]


def test_snapshot_leaves_export_whole_and_record_counts_as_dropped():
    t = tracing.Tracer(capacity=2)
    with t.span("a"):
        pass
    t.record("b", 1, 2)
    assert _names(t.snapshot()) == ["a", "b"]
    assert _names(t.snapshot()) == ["a", "b"]  # read twice, nothing drained
    assert t.pending() == 2 and t.dropped == 0
    t.record("c", 3, 4)
    assert t.dropped == 1
    assert _names(t.snapshot()) == ["b", "c"]
    assert _names(t.export()) == ["b", "c"]
    assert t.snapshot() == [] and t.export() == []


def test_jax_still_reports_under_the_names_the_observer_hears():
    """An upgrade that renames an event or its keyword must fail here, not
    read 0 in every metric."""
    entries, exits = [], []

    def on_entry(event, value, **kwargs):
        entries.append((event, sorted(kwargs)))

    def on_exit(event, duration, **kwargs):
        exits.append((event, sorted(kwargs)))

    jax.monitoring.register_scalar_listener(on_entry)
    jax.monitoring.register_event_duration_secs_listener(on_exit)
    try:
        jax.jit(lambda x: x * 5.0 - 1.0)(jnp.ones((7, 3)))
    finally:
        jax.monitoring.unregister_scalar_listener(on_entry)
        jax.monitoring.unregister_event_duration_listener(on_exit)
    assert compile_cache.SPAN_OF == {
        "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
        "/jax/core/compile/backend_compile_duration": "compile.backend",
    }
    for event in compile_cache.SPAN_OF:
        assert (event, ["fun_name"]) in entries, event
        assert (event, ["fun_name"]) in exits, event
