"""The train_kda driver end to end on the CPU, at the tiny size of
`data_kda/workloads/tiny-kimi.train.json` (half of eight experts held, two
heads of 16, layers KDA, KDA, MLA, S = 64); the int8 control and an
unchanged state that the comparison has to fail; the refusal a program
without the mixer gets; the new readers on a hand-made trace; and
`lib/flops_kda.py`'s counts against the numbers ISSUE 41 gives."""

import json
import math
import pathlib
import time
import types

import pytest

from benchmarks import run as runmod
from benchmarks.lib import compare, flops_kda, loader

DATA = pathlib.Path(__file__).parent / "data_kda"
ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "kimi-linear-48b-a3b-ep32.train-8k"


@pytest.fixture(scope="module")
def cell():
    bench = json.loads((DATA / "BENCHMARK.json").read_text())
    return loader.load_cell("tiny-kimi.train", bench, base=DATA, root=DATA)


@pytest.fixture(scope="module")
def real():
    cell = loader.load_cell(CELL, loader.load_benchmark(ROOT))
    return cell, cell["driver"].model_numbers(cell["config"])


@pytest.fixture(scope="module")
def followed(cell):
    """The reference's three steps, and the int8 control's."""
    import jax

    from benchmarks.reference import lm as reference

    driver = cell["driver"]
    _, feed, key, numbers = driver.build(cell, 21, jax.devices())
    ref = driver.run_reference(cell, key, numbers, feed, jax.devices())
    control = driver.run_reference(
        cell, key, numbers, feed, jax.devices(), quant=reference.int8_quant
    )
    return ref, control


def test_a_sound_run_is_correct_and_reports_its_routing_and_its_schedules(
    cell, tmp_path
):
    lines = []
    args = types.SimpleNamespace(
        seed=2**31 + 11, seconds=0.5, trace=0, trace_dir=str(tmp_path),
        compile_counter=runmod.LoweringCounter(),
    )  # a seed beyond 32 signed bits
    out = cell["driver"].run(
        cell, args, time.perf_counter(),
        lambda phase, **kw: lines.append((phase, kw)),
    )
    lines = dict(lines)
    assert out["checks"].correct, out["checks"].lines()
    assert len(out["checks"].rows) == 4
    assert set(out["end_to_end"]) == {"tokens_per_s_per_chip", "setup_s"}
    assert lines["window"]["compilations_in_window"] == 0
    facts = out["facts"]
    moe, numbers = facts["moe"], facts["numbers"]
    # Four of eight experts held, two a token: rows, over the SPARSE layers.
    assert 0 < moe["held_share"] < 2 and moe["load_max_over_mean"] >= 1
    assert moe["tokens_held_a_layer"] == moe["held_share"] * 4 * 64
    # the mixer's counters ride the `[routed]` lines
    assert 0.5 < moe["kda_decay_mean"] < 1 and 0.2 < moe["kda_beta_mean"] < 0.8
    assert facts["flops_per_token"] == flops_kda.kda_flops_per_token(
        numbers, 64, moe["held_share"], 64
    ) == sum(facts["flops_by_part"].values())
    assert facts["kda"]["chunk"] == 64 and facts["kda"]["chunks"] == 1
    assert facts["mla"]["qk_dim"] == 16 and facts["mla"]["rope_dim"] == 8


def test_the_int8_control_is_not_correct(cell, followed):
    """The reference in the program's place, every matmul with a weight in
    int8: the nearest precision below the bfloat16 the configuration
    states."""
    ref, control = followed
    checks = compare.Checks()
    cell["driver"].compare(control, ref, cell["workload"]["limits"], checks)
    assert not checks.correct, checks.lines()


def test_a_step_that_returns_its_state_unchanged_is_not_correct(cell, followed):
    ref, _ = followed
    still = dict(ref, change_norm={k: 0.0 for k in ref["change_norm"]})
    checks = compare.Checks()
    cell["driver"].compare(still, ref, cell["workload"]["limits"], checks)
    assert not checks.correct, checks.lines()


def test_a_program_without_the_mixer_is_refused_at_once(cell, monkeypatch):
    """What the parent commit gets on this cell: `preload()` raises before
    anything reaches the chip."""
    import kubeflow_tpu.models.transformer as model

    cell["driver"].preload()  # this program has it
    monkeypatch.delattr(model, "DeltaMixer")
    with pytest.raises(ImportError, match="no delta-rule mixer"):
        cell["driver"].preload()


def test_the_stand_in_leaves_the_references_as_they_were(cell):
    import jax

    import benchmarks.reference as package
    from benchmarks.reference import kimi_linear, zaya

    cell["driver"].build(cell, 5, jax.devices())
    assert package.zaya is zaya and kimi_linear._zaya is zaya


def test_the_cell_resolves_with_its_readers_and_its_602_million(real):
    cell, numbers = real
    names = {m["name"] for m in cell["per_layer"]}
    assert {
        "kda_time_pct.train", "kda_roofline.train", "kda_layer_time_pct.train",
        "flash_mla_roofline.train", "flash_time_pct.train",
        "moe_gmm_roofline.train", "mfu_pct.train", "scope_named_pct.train",
        "recompute_time_pct.train",
    } <= names
    assert "flash_roofline.train" not in names  # its reader costs one `d`
    from benchmarks.reference import kimi_linear

    specs = kimi_linear.param_specs(numbers)
    size = lambda pre: sum(
        math.prod(shape) for name, (shape, _) in specs.items()
        if name.startswith(pre)
    )
    assert size("layer.1.kda_") == 39_514_272  # ISSUE 41's 39.51 M
    assert size("layer.3.w") - 3 * 8 * 2304 * 1024 == 29_114_368  # MLA, 29.11 M
    held = size("")
    assert held == 602_434_432
    cfg = cell["driver"].transformer_config(numbers)
    assert [k.mixer for k in cfg.attention_kinds] == ["delta", "attention"]
    assert cfg.attention_pattern == (0, 0, 0, 1, 0)
    assert (cfg.q_latent, cfg.kv_latent) == (0, 512)
    assert (cfg.head_dim, cfg.rope_head_dim, cfg.v_head_dim) == (128, 64, 128)
    assert cfg.attention_kinds[1].rope_fraction == 0 and cfg.softmax_scale is None
    assert cfg.dense_layers == 1 and cfg.experts_held == (0, 8)
    assert cfg.experts_per_token == 8 and cfg.routed_scaling == 2.446
    assert cfg.ssm_conv == 4 and cfg.ssm_chunk == 64


def test_every_number_of_the_catalogs_entry_is_in_the_file_or_reduced(real):
    cell, _ = real
    catalog = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.is_file():
        pytest.skip("no catalog here")
    entry = next(
        e for e in map(json.loads, catalog.read_text().splitlines())
        if e["name"] == "Kimi-Linear-48B-A3B-Instruct"
    )
    bench = loader.load_benchmark(ROOT)
    listed = next(c for c in bench["configs"] if c["name"] == cell["workload"]["config"])
    assert listed["source"] == entry["source_url"] == cell["config"]["source"]
    for key, value in entry["config"].items():
        if key in listed["reduced"]:
            assert cell["config"][key] != value, key
        else:
            assert cell["config"][key] == value, key
    assert set(cell["config"]["reduced"]) == set(listed["reduced"])
    linear = cell["config"]["linear_attn_config"]
    for key in ("head_dim", "num_heads", "short_conv_kernel_size"):
        assert linear[key] == entry["config"]["linear_attn_config"][key]


def test_the_flop_count_is_the_issues(real):
    """ISSUE 41's count at S = 8192 and 0.25 rows held a token (8 of 256
    experts, eight a token): ~2.3 GFLOP a token, the KDA mixers ~43 %."""
    _, numbers = real
    parts = flops_kda.flops_by_part(numbers, 8192, 0.25, 64)
    total = sum(parts.values())
    share = {k: round(100 * v / total) for k, v in parts.items()}
    assert total == pytest.approx(2.3e9, rel=0.03)
    assert share == {
        "kda_projections": 41, "kda_scan": 2, "mla_projections": 8,
        "mla_attention": 11, "dense_mlp": 17, "shared_and_router": 8,
        "routed_experts": 2, "head": 12,
    }
    assert parts["kda_scan"] == 4 * 3 * 32 * (6 * 128 * 128 + 4 * 64 * 128)


def test_a_delta_rule_calls_needed_work_is_by_the_cells_shapes(real):
    _, numbers = real
    shape = dict(batch=1, seq_len=8192, chunk=64)
    flops, nbytes = flops_kda.kda_call_cost("fwd", numbers, **shape)
    assert flops == 8192 * 32 * (6 * 128 * 128 + 4 * 64 * 128)
    lanes, states = 8192 * 4096, 128 * 128 * 4096 * 2
    assert nbytes == lanes * 12 + states
    back, more = flops_kda.kda_call_cost("bwd", numbers, **shape)
    assert back == 2 * flops and more == lanes * 22 + states
    kind = flops_kda.kda_kernel_kind
    assert kind("kda_fwd.3") == "fwd" and kind("kda_bwd.9") == "bwd"
    for name in ("ssd_fwd", "flash_fwd_mla.1", "fusion.7", "moe_gmm_fwd"):
        assert kind(name) is None, name


def test_the_new_readers_count_their_calls_only_and_never_raise(real):
    from benchmarks.lib import trace as tracelib

    cell, numbers = real
    ops = [
        ["kda_fwd.3", 0, 2_000_000], ["kda_bwd.4", 2_000_000, 6_000_000],
        ["flash_fwd_mla.5", 8_000_000, 7_000_000],
        ["fusion.1", 15_000_000, 5_000_000],
    ]
    reduced = tracelib.reduce(
        {"devices": {0: {"ops": ops, "modules": []}}, "spans": []}
    )
    facts = {"numbers": numbers, "device_kind": "TPU v5 lite", "kda": {"chunk": 64}}
    context = {**cell, "facts": facts}
    read = lambda name, trace, ctx: loader.load_metric(name).read(trace, [], ctx)
    assert read("kda_time_pct.train", reduced, context) == pytest.approx(40.0)
    share = read("kda_roofline.train", reduced, context)
    lanes, states = 8192 * 4096, 128 * 128 * 4096 * 2
    least = (lanes * 34 + 2 * states) / 819e9  # memory-bound both ways
    assert share == pytest.approx(100 * least / 8e-3) and 0 < share < 100
    plain = {**context, "facts": {
        "numbers": {"hidden_size": 2048}, "device_kind": "TPU v5 lite",
    }}
    none = tracelib.reduce({"devices": {0: {"ops": ops[2:], "modules": []}}, "spans": []})
    assert read("kda_roofline.train", reduced, plain) is None
    assert read("kda_roofline.train", none, context) is None
    assert read("kda_time_pct.train", none, context) is None
    # no registered step program, no profile: no table, so None
    assert read("kda_layer_time_pct.train", reduced, context) is None
    mixer = loader.load_metric("kda_layer_time_pct.train").in_mixer
    assert mixer("layer_1/kda/kda.proj/wq") and mixer("kda.scan") and mixer("kda")
    assert not mixer("attn/attn.latent_kv") and not mixer("mlp/wo")
