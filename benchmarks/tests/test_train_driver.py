"""The train driver end to end on the CPU, at the tiny size of
`data/workloads/tiny-lm.train.json`; the control that the comparison has
to fail; a broken timed path that it has to catch; and `run.py` itself,
which refuses to run without a TPU."""

import json
import pathlib
import subprocess
import sys
import time
import types

import pytest

from benchmarks import run as runmod
from benchmarks.lib import compare, loader

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).parent / "data"


def _cell(name):
    bench = json.loads((DATA / "BENCHMARK.json").read_text())
    return loader.load_cell(name, bench, base=DATA, root=DATA)


@pytest.fixture(scope="module")
def cell():
    return _cell("tiny-lm.train")


@pytest.fixture(scope="module")
def counter():
    return runmod.LoweringCounter()


def _args(counter, seed, tmp_path):
    return types.SimpleNamespace(
        seed=seed, seconds=0.5, trace=0, trace_dir=str(tmp_path),
        compile_counter=counter,
    )


def _run(cell, counter, seed, tmp_path):
    lines = []
    out = cell["driver"].run(
        cell, _args(counter, seed, tmp_path), time.perf_counter(),
        lambda phase, **kw: lines.append((phase, kw)),
    )
    return out, lines


def test_a_sound_run_is_correct_and_reports_its_metrics(cell, counter, tmp_path):
    out, lines = _run(cell, counter, 2**31 + 11, tmp_path)  # beyond 32 signed bits
    assert out["checks"].correct, out["checks"].lines()
    assert len(out["checks"].rows) == 4
    assert set(out["end_to_end"]) == {"tokens_per_s_per_chip", "setup_s"}
    assert out["end_to_end"]["tokens_per_s_per_chip"] > 0
    assert out["attempted"] >= 3 and out["failed"] == 0
    window = dict(lines)["window"]
    assert window["compilations_in_window"] == 0
    assert window["tokens"] == window["steps"] * 2 * 32


def test_a_sound_run_on_a_dp2_tp2_mesh_is_correct(counter, tmp_path):
    """Four devices: the program sharded over dp x tp, the reference
    spread over the same four; the same limits hold."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    cell = _cell("tiny-lm.train-dp2tp2")
    out, lines = _run(cell, counter, 41, tmp_path)
    assert out["checks"].correct, out["checks"].lines()
    assert dict(lines)["window"]["tokens"] % (4 * 32) == 0


def test_the_same_seed_gives_the_same_first_steps(cell, counter, tmp_path):
    a, _ = _run(cell, counter, 7, tmp_path)
    b, _ = _run(cell, counter, 7, tmp_path)
    c, _ = _run(cell, counter, 8, tmp_path)
    first = lambda out: out["checks"].rows[0]["note"]
    assert first(a) == first(b) != first(c)


def test_the_int8_control_is_not_correct(cell):
    """The reference in the program's place, every matmul in int8: the
    nearest precision below the bfloat16 the configuration states."""
    import jax

    from benchmarks.reference import lm as reference

    driver = cell["driver"]
    for seed in (21, 22, 23):
        trainer, feed, key, numbers = driver.build(cell, seed, jax.devices())
        ref = driver.run_reference(cell, key, numbers, feed, jax.devices())
        control = driver.run_reference(
            cell, key, numbers, feed, jax.devices(), quant=reference.int8_quant
        )
        checks = compare.Checks()
        driver.compare(control, ref, cell["workload"]["limits"], checks)
        assert not checks.correct, checks.lines()
        failed = [r["check"].split(",")[0] for r in checks.rows if not r["ok"]]
        assert "first_grad_norm" in failed


def test_a_step_that_leaves_its_state_unchanged_is_not_correct(
    cell, counter, tmp_path, monkeypatch
):
    """The rest of a run, with the timed path broken underneath."""
    from kubeflow_tpu.train.trainer import TrainState

    monkeypatch.setattr(
        TrainState, "apply_gradients",
        lambda self, *, grads, **updates: self.replace(step=self.step + 1),
    )
    out, _ = _run(cell, counter, 31, tmp_path)
    assert not out["checks"].correct
    failed = {r["check"].split(",")[0] for r in out["checks"].rows if not r["ok"]}
    assert {"first_grad_norm", "change_norm"} <= failed


def test_worst_leaf_gap_measures_against_the_median_leaf():
    ref = {"a": 1.0, "b": 2.0, "tiny": 1e-9}
    gap, leaf = compare.worst_leaf_gap({"a": 1.1, "b": 2.0, "tiny": 2e-9}, ref)
    assert leaf == "a" and gap == pytest.approx(0.1)
    with pytest.raises(ValueError):
        compare.worst_leaf_gap({"a": 1.0}, ref)
    checks = compare.Checks()
    assert not checks.correct  # nothing compared is not correct
    checks.at_most("x", float("nan"), 1.0)
    assert not checks.correct


def test_run_py_refuses_to_run_without_a_tpu():
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "olmo-1b-cut.train-2k", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(ROOT / ".bench_trace")},
    )
    assert done.returncode == 2, done.stderr[-2000:]
    assert "no CPU mode" in done.stderr
    assert '"metrics"' not in done.stdout and '"correct"' not in done.stdout
