"""The train_hybrid driver end to end on the CPU, at the tiny size of
`data_hybrid/workloads/tiny-nemotron.train.json` (a quarter of sixteen
experts held, three a token); the int8 control that the comparison has to
fail; a step that leaves its state unchanged, which it has to catch; the
FLOP count that follows the rows routed here; the scan kernels' needed
work; the loader refusing a key the new files may not hold."""

import json
import pathlib
import shutil
import time
import types

import pytest

from benchmarks import run as runmod
from benchmarks.lib import compare, flops_hybrid, loader

DATA = pathlib.Path(__file__).parent / "data_hybrid"
ROOT = pathlib.Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def cell():
    bench = json.loads((DATA / "BENCHMARK.json").read_text())
    return loader.load_cell("tiny-nemotron.train", bench, base=DATA, root=DATA)


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
    # 4 of 16 experts held, 3 a token: some rows are routed elsewhere.
    assert 0 < moe["held_share"] < 3 and moe["load_max_over_mean"] >= 1
    assert moe["tokens_held_a_layer"] == moe["held_share"] * 4 * 64
    assert out["facts"]["flops_per_token"] == flops_hybrid.hybrid_flops_per_token(
        numbers, 64, moe["held_share"]
    ) < flops_hybrid.hybrid_flops_per_token(numbers, 64, 3.0)
    # what the accepted readers take from `numbers`
    assert set(numbers) >= {"num_attention_heads", "head_dim"}


def test_a_forced_selection_gives_every_seed_the_same_rows(cell, counter, tmp_path):
    forced = {**cell, "workload": {**cell["workload"], "router_force_balance": True}}
    runs = [_run(forced, counter, seed, tmp_path)[0] for seed in (41, 42)]
    for out in runs:
        assert out["checks"].correct, out["checks"].lines()
        assert out["facts"]["numbers"]["router_force_balance"] is True
    assert runs[0]["facts"]["moe"] == runs[1]["facts"]["moe"]


def test_the_int8_control_is_not_correct(cell):
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


@pytest.mark.parametrize("where", ["workloads/tiny-nemotron.train.json",
                                   "configs/tiny-nemotron.json"])
def test_an_unknown_key_is_refused(tmp_path, where):
    shutil.copytree(DATA, tmp_path / "data")
    path = tmp_path / "data" / where
    data = json.loads(path.read_text())
    data["chunk_szie"] = 128
    path.write_text(json.dumps(data))
    bench = json.loads((DATA / "BENCHMARK.json").read_text())
    with pytest.raises(loader.BenchmarkFileError, match="unknown key.*chunk_szie"):
        loader.load_cell(
            "tiny-nemotron.train", bench, base=tmp_path / "data",
            root=tmp_path / "data",
        )


def test_the_cell_resolves_with_its_nine_readers():
    cell = loader.load_cell(
        "nemotron-3-super-tp2ep64.train-8k", loader.load_benchmark(ROOT)
    )
    names = {m["name"] for m in cell["per_layer"]}
    assert names >= {
        "ssd_time_pct.train", "ssd_roofline.train",
        "moe_latent_gmm_roofline.train", "flash_time_pct.train",
        "flash_roofline.train", "loop_stall_pct.train", "moe_time_pct.train",
        "moe_load_max_over_mean.train",
    }
    assert "moe_gmm_roofline.train" not in names
    numbers = cell["driver"].model_numbers(cell["config"])
    # ISSUE 32's sum: 919 M parameters held, ~3.85 GFLOP a token of matmuls.
    held = 8 * 22 / 512
    params = flops_hybrid.matmul_params_per_token(numbers, held)
    assert 630e6 < params < 650e6
    assert 3.8e9 < flops_hybrid.hybrid_flops_per_token(numbers, 8192, held) < 4.1e9


def test_the_scan_kernels_needed_work_is_from_the_shapes():
    cfg = dict(mamba_num_heads=64, mamba_head_dim=64, n_groups=4,
               ssm_state_size=128, chunk_size=128)
    a_token = 4 * 128 * 128 + 64 * 128 * 64 + 4 * 64 * 128 * 64
    assert flops_hybrid.scan_flops_per_token(cfg) == a_token
    flops, nbytes = flops_hybrid.ssd_call_cost("ssd_fwd", cfg, batch=1, seq_len=8192)
    assert flops == 8192 * a_token
    assert nbytes == 8192 * (2 * 2 * 4096 + 2 * 2 * 512 + 4 * 64)
    back, more = flops_hybrid.ssd_call_cost("ssd_bwd", cfg, batch=1, seq_len=8192)
    assert back == 2 * flops and more > nbytes
    assert flops_hybrid.ssd_kernel_kind("ssd_bwd.7") == "ssd_bwd"
    assert flops_hybrid.ssd_kernel_kind("moe_gmm_fwd") is None


def test_the_new_readers_read_a_trace_and_return_nothing_without_one():
    """On a trace with the kernels' events each reader gives a share under
    100 %; on one without them, or for a program that reports no such
    numbers (the parent), None and no error."""
    from benchmarks.lib import trace as tracelib

    ops = [
        ["ssd_fwd.3", 0, 400_000], ["ssd_bwd.4", 400_000, 1_200_000],
        ["moe_gmm_fwd.5", 1_600_000, 300_000], ["fusion.1", 1_900_000, 100_000],
    ]
    reduced = tracelib.reduce(
        {"devices": {0: {"ops": ops, "modules": []}}, "spans": []}
    )
    bare = tracelib.reduce(
        {"devices": {0: {"ops": [["fusion.1", 0, 100]], "modules": []}}, "spans": []}
    )
    cell = loader.load_cell(
        "nemotron-3-super-tp2ep64.train-8k", loader.load_benchmark(ROOT)
    )
    numbers = cell["driver"].model_numbers(cell["config"])
    context = {**cell, "facts": {
        "numbers": numbers, "device_kind": "TPU v5 lite",
        "moe": {"tokens_held_a_layer": 2816.0},
    }}
    read = lambda name, trace, ctx: loader.load_metric(name).read(trace, [], ctx)
    assert read("ssd_time_pct.train", reduced, context) == pytest.approx(80.0)
    assert 0 < read("ssd_roofline.train", reduced, context) < 100
    latent = read("moe_latent_gmm_roofline.train", reduced, context)
    wide = read("moe_gmm_roofline.train", reduced, {
        **context, "facts": {**context["facts"], "numbers": {**numbers, "num_experts": 8}},
    })
    assert 0 < latent < 100 and wide == pytest.approx(latent * 4, rel=0.2)
    for name in ("ssd_time_pct.train", "ssd_roofline.train",
                 "moe_latent_gmm_roofline.train"):
        assert read(name, bare, context) is None
        assert read(name, reduced, {**context, "facts": {
            "numbers": {"hidden_size": 2048}, "device_kind": "TPU v5 lite",
        }}) is None or name == "ssd_time_pct.train"
