"""Profiler + metrics-logging (SURVEY.md §5 tracing row: the reference
served profiles via Tensorboard but never captured them; here capture is
part of the training loop)."""

import jax
import jax.numpy as jnp
import pytest

from kubeflow_tpu.models.resnet import tiny_resnet
from kubeflow_tpu.parallel import MeshSpec, build_mesh
from kubeflow_tpu.train import (
    MetricsLogger,
    PhaseRoofline,
    Profiler,
    ProfileSchedule,
    SyntheticImages,
    TrainConfig,
    Trainer,
    fit,
    time_phase,
)
from kubeflow_tpu.utils import tracing


def test_schedule_validation():
    with pytest.raises(ValueError):
        ProfileSchedule(start_step=-1).validate()
    with pytest.raises(ValueError):
        ProfileSchedule(num_steps=0).validate()


def test_windowed_capture_writes_tb_profile_layout(tmp_path, devices):
    """The trace must land where TensorBoard's profile plugin looks:
    <logdir>/plugins/profile/<run>/ — that dir is what a Tensorboard CR's
    logspath serves."""
    mesh = build_mesh(MeshSpec(dp=2), devices[:2])
    config = TrainConfig(batch_size=4, total_steps=6, warmup_steps=1)
    trainer = Trainer(
        tiny_resnet(), config, mesh, example_input_shape=(2, 32, 32, 3)
    )
    data = SyntheticImages(
        mesh, batch_size=4, image_size=32, num_classes=10, dtype=jnp.float32
    )
    profiler = Profiler(
        tmp_path / "logs", ProfileSchedule(start_step=2, num_steps=2)
    )
    result = fit(
        trainer, data, total_steps=6, profiler=profiler, log_every=100
    )
    assert result.steps_done == 6
    assert profiler.trace_written
    profile_dir = tmp_path / "logs" / "plugins" / "profile"
    runs = list(profile_dir.iterdir())
    assert runs, "no profile run directory written"
    traces = list(runs[0].glob("*"))
    assert traces, "profile run dir is empty"


def test_close_is_crash_safe(tmp_path):
    profiler = Profiler(tmp_path, ProfileSchedule(start_step=0, num_steps=100))
    profiler.before_step(0)  # trace live
    with tracing.tracer.span("region"):
        jnp.ones((4, 4)).sum().block_until_ready()
    profiler.close()  # must stop cleanly even though window isn't done
    assert profiler.trace_written
    # And close again is a no-op.
    profiler.close()
    # A finished profiler never restarts.
    profiler.before_step(50)
    assert not profiler._active


def test_resume_shifts_profile_window(tmp_path):
    """A resumed run (first step 480) must still skip its warmup/compile
    steps before tracing — the schedule is relative to the process's
    first step, not absolute."""
    profiler = Profiler(tmp_path, ProfileSchedule(start_step=2, num_steps=1))
    profiler.before_step(480)
    assert not profiler._active  # 480 is this process's compile step
    profiler.after_step(480)
    profiler.before_step(481)
    assert not profiler._active
    profiler.after_step(481)
    profiler.before_step(482)  # 480 + start_step(2)
    assert profiler._active
    profiler.after_step(482)
    assert profiler.trace_written


def test_metrics_logger_roundtrip(tmp_path):
    logger = MetricsLogger(tmp_path / "logs")
    logger(10, {"loss": 1.5})
    logger(20, {"loss": 1.1})
    rows = logger.read()
    assert [r["step"] for r in rows] == [10, 20]
    assert all("ts" in r for r in rows)


# -- per-phase roofline (ISSUE 7) -------------------------------------------


def test_phase_roofline_math_and_bounds():
    """The mechanical roofline's arithmetic and the bound classifier
    (same convention as the hand-built docs/architecture.md table):
    achieved TF/s = TFLOP/s-of-wall-clock, achieved GB/s likewise, and
    the binding resource follows the dominant utilization."""
    roof = PhaseRoofline(peak_tflops=200.0, peak_gbps=800.0)
    # 10 TFLOP in 100 ms = 100 TF/s (50%); 8 GB in 100 ms = 80 GB/s
    # (10%): compute dominates by 0.4 -> MXU-side.
    mxu = roof.add("fwd", ms=100.0, tflop=10.0, gb=8.0)
    assert mxu["achieved_tflops"] == 100.0 and mxu["achieved_gbps"] == 80.0
    assert mxu["bound_by"] == "MXU-side"
    # ~0 TFLOP, 72 GB in 100 ms = 720 GB/s (90%) vs 0% compute -> HBM.
    hbm = roof.add("optimizer", ms=100.0, tflop=0.0, gb=72.0)
    assert hbm["bound_by"] == "HBM"
    # 64% compute vs 69% bandwidth -> mixed, HBM
    # dominant.
    mixed = roof.add("bwd", ms=100.0, tflop=12.8, gb=55.2)
    assert mixed["bound_by"] == "mixed → HBM"
    # The step's saturated resource is the longest phase's bound.
    roof.phases[-1] = roof.phases[-1].__class__("bwd", 300.0, 12.8, 55.2)
    assert roof.saturated().startswith("bwd:")
    # Table renders the Round-5 columns.
    table = roof.table()
    assert table.splitlines()[0] == (
        "| phase | ms | TFLOP | GB moved | achieved | bound by |"
    )
    assert "MXU-side" in table and "HBM" in table


def test_time_phase_fenced_timer():
    """time_phase returns positive wall-clock ms for a jitted fn and
    fences through a scalar device_get (it must not explode on pytree
    outputs either)."""
    f = jax.jit(lambda x: (x * 2.0, {"aux": x.sum()}))
    x = jnp.ones((32, 32))
    ms = time_phase(f, x, warmup=1, steps=2)
    assert ms > 0.0
