"""The train_window driver end to end on the CPU, at the tiny size of
`data_window/workloads/tiny-laguna.train.json` (half of eight experts
held, a window of 8 at S = 64); the int8 control that the comparison has
to fail; a step that leaves its state unchanged, which it has to catch;
the two readers of the window layers' kernels on a hand-made trace; and
the needed work of a window call against a count by hand."""

import json
import pathlib
import time
import types

import pytest

from benchmarks import run as runmod
from benchmarks.lib import compare, flops_window, loader

DATA = pathlib.Path(__file__).parent / "data_window"
ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "laguna-s-2.1-ep32.train-8k"


@pytest.fixture(scope="module")
def cell():
    bench = json.loads((DATA / "BENCHMARK.json").read_text())
    return loader.load_cell("tiny-laguna.train", bench, base=DATA, root=DATA)


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


def test_a_sound_run_is_correct_and_reports_its_routing_and_its_band(
    cell, counter, tmp_path
):
    out, lines = _run(cell, counter, 2**31 + 11, tmp_path)  # beyond 32 signed bits
    assert out["checks"].correct, out["checks"].lines()
    assert len(out["checks"].rows) == 4
    assert set(out["end_to_end"]) == {"tokens_per_s_per_chip", "setup_s"}
    assert lines["window"]["compilations_in_window"] == 0
    facts = out["facts"]
    moe, numbers = facts["moe"], facts["numbers"]
    # Four of eight experts held, three a token: rows, over the SPARSE layers.
    assert 0 < moe["held_share"] < 3 and moe["load_max_over_mean"] >= 1
    assert moe["tokens_held_a_layer"] == moe["held_share"] * 4 * 64
    assert facts["flops_per_token"] == flops_window.window_flops_per_token(
        numbers, 64, moe["held_share"]
    ) == sum(facts["flops_by_part"].values())
    assert facts["flops_by_part"]["window_attention"] < (
        3 * 6 * 64 * 6 * 16  # the three window layers by the triangle
    )
    assert facts["window"]["window"] == 8 and facts["window"]["band_steps"] == 1


def test_fit_records_the_gates_mean_beside_the_routing(cell):
    import jax

    from kubeflow_tpu.train import fit

    driver = cell["driver"]
    trainer, feed, key, numbers = driver.build(cell, 7, jax.devices())
    program = driver.first_steps(trainer, feed, key, numbers, fit)
    gates = [c["attn_gate_mean"] for c in program["counters"]]
    assert len(gates) == 3 and all(0.3 < g < 0.7 for g in gates)
    assert all(c["moe_tokens_held"] > 0 for c in program["counters"])


def test_the_int8_control_is_not_correct(cell):
    """The reference in the program's place, every matmul in int8 (the
    router's and the gate's too): the nearest precision below the
    bfloat16 the configuration states."""
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


def test_the_stand_in_leaves_the_references_as_they_were(cell):
    import jax

    import benchmarks.reference as package
    from benchmarks.reference import laguna, zaya

    cell["driver"].build(cell, 5, jax.devices())
    assert package.zaya is zaya and laguna._zaya is zaya


def test_the_cell_resolves_with_its_readers_and_its_811_million():
    cell = loader.load_cell(CELL, loader.load_benchmark(ROOT))
    names = {m["name"] for m in cell["per_layer"]}
    assert names == {
        "flash_window_time_pct.train", "flash_window_roofline.train",
        "flash_time_pct.train", "moe_time_pct.train", "moe_gmm_roofline.train",
        "moe_load_max_over_mean.train", "mfu_pct.train",
        "device_idle_pct.train", "input_wait_ms.train", "loop_stall_pct.train",
    }
    numbers = cell["driver"].model_numbers(cell["config"])
    from benchmarks.reference import laguna

    held = sum(
        shape[0] * (shape[1] if len(shape) > 1 else 1)
        * (shape[2] if len(shape) > 2 else 1)
        for shape, _ in laguna.param_specs(numbers).values()
    )
    assert round(held / 1e6, 1) == 811.0
    cfg = cell["driver"].transformer_config(numbers)
    assert [k.n_heads for k in cfg.attention_kinds] == [48, 72]
    assert cfg.attention_pattern == (0, 1, 1, 1, 0)
    assert cfg.attention_kinds[1].window == 512 and cfg.dense_layers == 1


def test_a_window_calls_needed_work_is_the_bands():
    s, w, hd = 8192, 512, 128
    pairs = sum(min(i + 1, w) for i in range(s))
    shape = dict(batch=1, heads=72, kv_heads=8, seq_len=s, window=w, head_dim=hd)
    flops, nbytes = flops_window.window_call_cost("fwd", **shape)
    assert flops == 2 * 2 * pairs * hd * 72
    assert nbytes == s * hd * 2 * (2 * 72 + 2 * 8)
    back, more = flops_window.window_call_cost("bwd_fused", **shape)
    assert back == 2 * flops and more == s * hd * 2 * (4 * 72 + 4 * 8)
    assert flops_window.window_call_cost("dq", **shape)[0] == flops
    assert flops_window.window_call_cost("dkv", **shape)[1] == (
        s * hd * 2 * (2 * 72 + 4 * 8)
    )
    kind = flops_window.window_kernel_kind
    assert kind("flash_fwd_window.3") == "fwd"
    assert kind("flash_bwd_window_fused.9") == "bwd_fused"
    assert kind("flash_dq_window_rect") == "dq" and kind("flash_dkv_window") == "dkv"
    for name in ("flash_fwd_compact.1", "flash_bwd_fused.2", "flash_delta.4",
                 "fusion.7", "moe_gmm_fwd"):
        assert kind(name) is None, name


def test_the_new_readers_count_the_window_calls_only():
    """A window call and a global call in one trace: only the first is
    counted; without a window call, or for a configuration with no window
    layer (the parent's cells), None and no error."""
    from benchmarks.lib import trace as tracelib

    ops = [
        ["flash_fwd_window.3", 0, 2_000_000],
        ["flash_bwd_window_fused.4", 2_000_000, 6_000_000],
        ["flash_fwd_compact.5", 8_000_000, 7_000_000],
        ["flash_delta.6", 15_000_000, 1_000_000], ["fusion.1", 16_000_000, 4_000_000],
    ]
    reduced = tracelib.reduce(
        {"devices": {0: {"ops": ops, "modules": []}}, "spans": []}
    )
    bare = tracelib.reduce({"devices": {0: {
        "ops": [["flash_fwd_compact.5", 0, 100], ["fusion.1", 100, 100]],
        "modules": [],
    }}, "spans": []})
    cell = loader.load_cell(CELL, loader.load_benchmark(ROOT))
    numbers = cell["driver"].model_numbers(cell["config"])
    context = {**cell, "facts": {"numbers": numbers, "device_kind": "TPU v5 lite"}}
    read = lambda name, trace, ctx: loader.load_metric(name).read(trace, [], ctx)
    assert read("flash_window_time_pct.train", reduced, context) == pytest.approx(40.0)
    assert read("flash_time_pct.train", reduced, context) == pytest.approx(80.0)
    share = read("flash_window_roofline.train", reduced, context)
    fwd = flops_window.window_call_cost(
        "fwd", batch=1, heads=72, kv_heads=8, seq_len=8192, window=512, head_dim=128
    )[0]
    assert share == pytest.approx(100 * (3 * fwd / 197e12) / 8e-3)
    assert 0 < share < 100
    for name in ("flash_window_time_pct.train", "flash_window_roofline.train"):
        assert read(name, bare, context) is None
        assert read(name, reduced, {**context, "facts": {
            "numbers": {"hidden_size": 2048}, "device_kind": "TPU v5 lite",
        }}) is None or name == "flash_window_time_pct.train"
