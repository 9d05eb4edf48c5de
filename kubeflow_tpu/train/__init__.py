"""Training runtime: train-step factories, data, metrics, checkpointing.

This is the tier the reference delegated to container images entirely
(`tf_cnn_benchmarks` inside pinned TF images — SURVEY.md §2 item 21, §6):
here it is a first-class library so the platform's operators, tuning
studies, and benchmarks all drive one code path.
"""

from kubeflow_tpu.train.trainer import Trainer, TrainConfig, TrainState
from kubeflow_tpu.train.data import SyntheticImages, SyntheticTokens
from kubeflow_tpu.train.checkpoint import Checkpointer, Restored
from kubeflow_tpu.train.guard import AnomalyGuard, GuardConfig
from kubeflow_tpu.train.loop import (
    ElasticResize,
    FitResult,
    Preempted,
    ResizeEvent,
    ResizeProposal,
    TrainingDiverged,
    fit,
)
from kubeflow_tpu.train.profiling import (
    MetricsLogger,
    PhaseRoofline,
    PhaseStat,
    Profiler,
    ProfileSchedule,
    time_phase,
)
