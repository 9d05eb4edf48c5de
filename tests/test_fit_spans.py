"""fit() reports to the platform's one span primitive (ISSUE 26): a
`train.step` parent span a step with the step's number, children where the
work happens, `FitResult.timings` added up from the same spans, and the
same spans on the host plane of a profile taken meanwhile. Since ISSUE 50
the whole call is one `train.fit` span, the root of everything it reports,
and what it compiles is in the ring too (`tests/test_compile_spans.py`)."""

import glob

import jax
import pytest

from kubeflow_tpu.parallel import MeshSpec, build_mesh
from kubeflow_tpu.testing.tinymodels import TinyMLP
from kubeflow_tpu.train import (
    Checkpointer,
    ElasticResize,
    MetricsLogger,
    Profiler,
    ProfileSchedule,
    ResizeProposal,
    SyntheticImages,
    TrainConfig,
    Trainer,
    fit,
)
from kubeflow_tpu.utils import tracing

CFG = TrainConfig(
    batch_size=8, learning_rate=0.05, warmup_steps=2, total_steps=24,
    fsdp_params=False, weight_decay=0.0,
)


def _job(devices, dp=1):
    mesh = build_mesh(MeshSpec(dp=dp), devices[:dp])
    trainer = Trainer(TinyMLP(), CFG, mesh, example_input_shape=(2, 8, 8, 3))
    data = SyntheticImages(
        mesh, 8, image_size=8, num_classes=10, seed=3, vary_per_step=True
    )
    return trainer, data


def _fit_spans(*args, **kwargs):
    """fit(), and the `train.*` and `compile.*` spans it left in the ring,
    oldest first: the call's own `train.fit` ends last."""
    tracing.tracer.export()
    result = fit(*args, **kwargs)
    spans = [
        s for s in tracing.tracer.export()
        if s["name"].startswith(("train.", "compile."))
    ]
    return result, spans


def _train(spans):
    return [s for s in spans if s["name"].startswith("train.")]


def _by_step(spans):
    """{step number: [child names in start order]} from parent links."""
    parents = {
        s["spanId"]: s["attributes"]["step_num"]
        for s in spans if s["name"] == "train.step"
    }
    out = {n: [] for n in parents.values()}
    for s in sorted(spans, key=lambda s: s["startNs"]):
        if s["parentId"] in parents:
            out[parents[s["parentId"]]].append(s["name"])
    return out


def test_one_step_span_a_step_with_children_in_order(devices):
    trainer, data = _job(devices)
    result, spans = _fit_spans(trainer, data, total_steps=5, log_every=2)
    steps = _by_step(spans)
    assert sorted(steps) == [1, 2, 3, 4, 5]
    quiet = ["train.data", "train.dispatch"]
    assert steps == {
        1: quiet, 2: quiet + ["train.readback"], 3: quiet,
        4: quiet + ["train.readback"], 5: quiet + ["train.readback"],
    }
    assert [r["step"] for r in result.history] == [2, 4, 5]
    # children lie inside their parent, on one monotonic clock
    by_id = {s["spanId"]: s for s in spans}
    for s in spans:
        if s["parentId"] in by_id:
            parent = by_id[s["parentId"]]
            assert parent["startNs"] <= s["startNs"] <= s["endNs"] <= parent["endNs"]
            assert s["traceId"] == parent["traceId"]
    # the call is the root: one trace, and everything else inside it
    root = spans[-1]
    assert root["name"] == "train.fit" and root["parentId"] is None
    assert root["attributes"] == {
        "total_steps": 5, "start_step": 0, "resumed_from": None,
    }
    assert {s["traceId"] for s in spans} == {root["traceId"]}
    # set-up before the first step: the state, then the step function,
    # children of the call as the steps are
    first = [s for s in _train(spans) if s["parentId"] == root["spanId"]][:3]
    assert [s["name"] for s in first] == [
        "train.init", "train.init", "train.step",
    ]


@pytest.mark.parametrize("log_every,save_every,readback_at", [
    (100, None, {6: 1}),                  # only the last step logs
    (3, None, {3: 1, 6: 1}),
    # a save reads the loss first; an empty directory saves its first step
    (100, 2, {1: 1, 2: 1, 4: 1, 6: 2}),
    (4, 4, {1: 1, 4: 2, 6: 2}),
])
def test_readback_only_at_log_and_save_boundaries(
    devices, tmp_path, log_every, save_every, readback_at
):
    trainer, data = _job(devices)
    ckpt = save_every and Checkpointer(
        tmp_path / "ck", save_interval_steps=save_every
    )
    result, spans = _fit_spans(
        trainer, data, total_steps=6, log_every=log_every,
        checkpointer=ckpt or None,
    )
    if ckpt:
        ckpt.close()
    got = {
        n: names.count("train.readback")
        for n, names in _by_step(spans).items()
        if "train.readback" in names
    }
    assert got == readback_at
    assert result.timings["readback"]["count"] == sum(readback_at.values())


def test_timings_equal_the_rings_sums(devices, tmp_path):
    trainer, data = _job(devices)
    ckpt = Checkpointer(tmp_path / "ck", save_interval_steps=2)
    result, spans = _fit_spans(
        trainer, data, total_steps=4, log_every=1, checkpointer=ckpt
    )
    *inside, root = spans
    assert root["name"] == "train.fit" and root["parentId"] is None
    sums: dict = {}
    for s in inside:
        total = sums.setdefault(
            s["name"].removeprefix("train."), {"count": 0, "seconds": 0.0}
        )
        total["count"] += 1
        total["seconds"] += (s["endNs"] - s["startNs"]) / 1e9
    assert set(result.timings) == {
        "restore", "init", "step", "data", "dispatch", "readback", "save",
        "compile.trace", "compile.lower", "compile.backend",
    }
    for name, total in result.timings.items():
        assert total["count"] == sums[name]["count"], name
        assert total["seconds"] == pytest.approx(sums[name]["seconds"], rel=1e-9)
    assert result.timings["step"]["count"] == 4
    assert result.timings["restore"]["count"] == 1
    # saves at steps 1 (an empty directory's first), 2 and 4, and the final
    # wait that makes them durable
    assert result.timings["save"]["count"] == 4
    # ... the last thing the call does, under its root
    last = _train(inside)[-1]
    assert last["name"] == "train.save" and last["parentId"] == root["spanId"]

    # a resumed call finds the checkpoint: a restore, no new state, no step
    again, spans = _fit_spans(
        trainer, data, total_steps=4,
        checkpointer=Checkpointer(tmp_path / "ck", save_interval_steps=2),
    )
    assert again.resumed_from == 4 and again.steps_done == 0
    assert [s["name"] for s in _train(spans)] == ["train.restore", "train.fit"]
    assert spans[-1]["attributes"] == {
        "total_steps": 4, "start_step": 4, "resumed_from": 4,
    }
    assert {n for n in again.timings if not n.startswith("compile.")} == {
        "restore"
    }
    ckpt.close()


def test_records_carry_the_seconds_since_the_last_record(devices, tmp_path):
    trainer, data = _job(devices)
    logger = MetricsLogger(tmp_path / "logs")
    result, _ = _fit_spans(
        trainer, data, total_steps=6, log_every=2, on_metrics=logger,
    )
    rows = logger.read()
    assert [r["step"] for r in rows] == [2, 4, 6]
    for name in ("data", "dispatch", "readback"):
        assert all(r[f"{name}_s"] > 0 for r in rows)
        assert sum(r[f"{name}_s"] for r in rows) == pytest.approx(
            result.timings[name]["seconds"], rel=1e-9
        )
    assert all(r["save_s"] == 0.0 for r in rows)  # no checkpointer


def test_a_profile_taken_during_fit_holds_the_steps_and_their_children(
    devices, tmp_path
):
    """No flag: the spans are TraceAnnotations because jax is imported, and
    `train.step` is a StepTraceAnnotation with its number."""
    from jax.profiler import ProfileData

    trainer, data = _job(devices)
    profiler = Profiler(
        tmp_path / "logs", ProfileSchedule(start_step=1, num_steps=4)
    )
    fit(trainer, data, total_steps=6, log_every=2, profiler=profiler)
    (path,) = glob.glob(f"{tmp_path}/logs/plugins/profile/*/*.xplane.pb")
    events: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("train."):
                        events.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns,
                             dict(ev.stats))
                        )
    # the trace starts inside step 2's span, so whole steps are 3 and 4
    steps = {int(st["step_num"]): (a, b) for a, b, st in events["train.step"]}
    assert {3, 4} <= set(steps)
    for name in ("train.data", "train.dispatch"):
        for a, b in (steps[3], steps[4]):
            assert any(a <= s and e <= b for s, e, _ in events[name]), name
    a, b = steps[4]  # log_every=2: step 4 reads its loss back
    assert any(a <= s and e <= b for s, e, _ in events["train.readback"])


def test_resize_event_seconds_is_read_from_the_span(devices):
    trainer, data = _job(devices, dp=2)
    elastic = ElasticResize(
        mesh_factory=lambda dp: build_mesh(MeshSpec(dp=dp), devices[:dp]),
        data_factory=lambda mesh, data: data.rebind(mesh),
        propose=lambda step, preempted: {2: ResizeProposal(dp=1)}.get(step),
    )
    result, spans = _fit_spans(
        trainer, data, total_steps=4, log_every=100, elastic=elastic
    )
    (event,) = result.resizes
    (span,) = [s for s in spans if s["name"] == "train.resize"]
    assert event.seconds == (span["endNs"] - span["startNs"]) / 1e9 > 0
    assert result.timings["resize"] == {"count": 1, "seconds": event.seconds}
    assert _by_step(spans)[2][-1] == "train.resize"
