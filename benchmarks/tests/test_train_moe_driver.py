"""The train_moe driver end to end on the CPU, at the tiny size of
`data_moe/workloads/tiny-zaya.train.json` (half of four experts held);
the int8 control that the comparison has to fail; a step that leaves its
state unchanged, which it has to catch; and the FLOP count that follows
the tokens routed here."""

import json
import pathlib
import time
import types

import pytest

from benchmarks import run as runmod
from benchmarks.lib import compare, flops_moe, loader

DATA = pathlib.Path(__file__).parent / "data_moe"


@pytest.fixture(scope="module")
def cell():
    bench = json.loads((DATA / "BENCHMARK.json").read_text())
    return loader.load_cell("tiny-zaya.train", bench, base=DATA, root=DATA)


@pytest.fixture(scope="module")
def counter():
    return runmod.LoweringCounter()


def _run(cell, counter, seed, tmp_path):
    lines = []
    args = types.SimpleNamespace(
        seed=seed, seconds=0.5, trace=0, trace_dir=str(tmp_path),
        compile_counter=counter,
    )
    out = cell["driver"].run(
        cell, args, time.perf_counter(),
        lambda phase, **kw: lines.append((phase, kw)),
    )
    return out, dict(lines)


def test_a_sound_run_is_correct_and_reports_its_routing(cell, counter, tmp_path):
    out, lines = _run(cell, counter, 2**31 + 11, tmp_path)  # beyond 32 signed bits
    assert out["checks"].correct, out["checks"].lines()
    assert len(out["checks"].rows) == 4
    assert set(out["end_to_end"]) == {"tokens_per_s_per_chip", "setup_s"}
    assert lines["window"]["compilations_in_window"] == 0
    moe, numbers = out["facts"]["moe"], out["facts"]["numbers"]
    # Two of four experts are held: some tokens are routed elsewhere.
    assert 0 < moe["held_share"] < 1 <= moe["load_max_over_mean"]
    assert moe["tokens_held_a_layer"] == moe["held_share"] * 4 * 64
    assert out["facts"]["flops_per_token"] == flops_moe.moe_flops_per_token(
        numbers, 64, moe["held_share"]
    ) < flops_moe.moe_flops_per_token(numbers, 64, 1.0)
    assert set(numbers) >= {"num_attention_heads", "head_dim"}


def test_a_forced_selection_gives_every_seed_the_same_rows(cell, counter, tmp_path):
    """`router_force_balance` in the workload: program and reference draw
    the selection by position and layer, so the rows held do not follow
    the seed, and the run is still correct."""
    forced = {**cell, "workload": {**cell["workload"], "router_force_balance": True}}
    runs = [_run(forced, counter, seed, tmp_path)[0] for seed in (41, 42)]
    for out in runs:
        assert out["checks"].correct, out["checks"].lines()
        assert out["facts"]["numbers"]["router_force_balance"] is True
    assert runs[0]["facts"]["moe"] == runs[1]["facts"]["moe"]
    free = [_run(cell, counter, seed, tmp_path)[0] for seed in (41, 42)]
    assert free[0]["facts"]["moe"] != free[1]["facts"]["moe"]


def test_the_int8_control_is_not_correct(cell):
    """The reference in the program's place, every matmul in int8 (the
    router's too): the nearest precision below the bfloat16 the
    configuration states."""
    import jax

    from benchmarks.reference import lm as reference

    driver = cell["driver"]
    for seed in (21, 22):
        _, feed, key, numbers = driver.build(cell, seed, jax.devices())
        ref = driver.run_reference(cell, key, numbers, feed, jax.devices())
        control = driver.run_reference(
            cell, key, numbers, feed, jax.devices(), quant=reference.int8_quant
        )
        checks = compare.Checks()
        driver.compare(control, ref, cell["workload"]["limits"], checks)
        assert not checks.correct, checks.lines()


def test_a_step_that_leaves_its_state_unchanged_is_not_correct(
    cell, counter, tmp_path, monkeypatch
):
    from kubeflow_tpu.train.trainer import TrainState

    monkeypatch.setattr(
        TrainState, "apply_gradients",
        lambda self, *, grads, **updates: self.replace(step=self.step + 1),
    )
    out, _ = _run(cell, counter, 31, tmp_path)
    assert not out["checks"].correct
    failed = {r["check"].split(",")[0] for r in out["checks"].rows if not r["ok"]}
    assert {"first_grad_norm", "change_norm"} <= failed


def test_the_grouped_matmuls_cost_follows_the_rows_routed():
    shape = dict(contract=2048, cols=2048, experts=8)
    flops, nbytes = flops_moe.gmm_call_cost("moe_gmm_fwd", rows=8192, **shape)
    assert flops == 2 * 8192 * 2048 * 2048
    assert nbytes == 2 * 8192 * 4096 + 2 * 8 * 2048 * 2048
    half, _ = flops_moe.gmm_call_cost("moe_gmm_dlhs", rows=4096, **shape)
    assert half == flops / 2
    _, dw = flops_moe.gmm_call_cost("moe_gmm_dw", rows=8192, **shape)
    assert dw - nbytes == 2 * 8 * 2048 * 2048  # the gradient leaves in float32
    assert flops_moe.gmm_kernel_kind("moe_gmm_dw.3") == "moe_gmm_dw"
    assert flops_moe.gmm_kernel_kind("flash_fwd_compact") is None
