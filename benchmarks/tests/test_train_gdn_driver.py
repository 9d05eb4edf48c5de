"""The train_gdn driver end to end on the CPU, at the tiny size of
`data_gdn/workloads/tiny-qwen3-next.train.json` (half of eight experts held,
four value heads over two key heads of 16, layers delta, delta, delta,
attention, S = 64); the int8 control and an unchanged state that the
comparison has to fail; the refusal a program without the form gets; the new
readers on a hand-made trace; and `lib/flops_gdn.py`'s counts against the
numbers ISSUE 45 gives."""

import json
import math
import pathlib
import time
import types

import pytest

from benchmarks import run as runmod
from benchmarks.lib import compare, flops_gdn, loader

DATA = pathlib.Path(__file__).parent / "data_gdn"
ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "qwen3-next-80b-a3b-ep16.train-8k"


@pytest.fixture(scope="module")
def cell():
    bench = json.loads((DATA / "BENCHMARK.json").read_text())
    return loader.load_cell("tiny-qwen3-next.train", bench, base=DATA, root=DATA)


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
    # Four of eight experts held, two a token: rows, over all four layers.
    assert 0 < moe["held_share"] < 2 and moe["load_max_over_mean"] >= 1
    assert moe["tokens_held_a_layer"] == moe["held_share"] * 4 * 64
    # the mixers' and the gates' counters ride the `[routed]` lines
    assert 0.5 < moe["kda_decay_mean"] < 1 and 0.2 < moe["kda_beta_mean"] < 0.8
    assert 0.3 < moe["attn_gate_mean"] < 0.7 and 0.3 < moe["shared_gate_mean"] < 0.7
    assert facts["flops_per_token"] == flops_gdn.gdn_flops_per_token(
        numbers, 64, moe["held_share"], 128
    ) == sum(facts["flops_by_part"].values())
    assert facts["gdn"]["chunk"] == 128 and facts["gdn"]["chunks"] == 1
    assert facts["gdn"]["form"] == "head"
    assert (facts["gdn"]["heads_a_step"], facts["gdn"]["key_heads_a_step"]) == (4, 2)
    assert facts["flash"]["qk_dim"] == facts["flash"]["v_dim"] == 32
    # no key of another family's dresses this one for its readers
    assert "linear_attn_config" not in numbers and "kda" not in facts


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


def test_a_program_without_the_form_is_refused_at_once(cell, monkeypatch):
    """What the parent commit gets on this cell: `preload()` raises before
    anything reaches the chip."""
    import kubeflow_tpu.ops.kda as kda

    cell["driver"].preload()  # this program has it
    monkeypatch.delattr(kda, "gdn_chunked")
    with pytest.raises(ImportError, match="no delta rule with a decay a head"):
        cell["driver"].preload()


def test_the_stand_in_leaves_the_references_as_they_were(cell):
    import jax

    import benchmarks.reference as package
    from benchmarks.reference import qwen3_next, zaya

    cell["driver"].build(cell, 5, jax.devices())
    assert package.zaya is zaya and qwen3_next._zaya is zaya


def test_the_cell_resolves_with_its_readers_and_its_626_million(real):
    cell, numbers = real
    names = {m["name"] for m in cell["per_layer"]}
    assert {
        "gdn_time_pct.train", "gdn_roofline.train", "kda_layer_time_pct.train",
        "flash_time_pct.train", "flash_roofline.train", "moe_gmm_roofline.train",
        "shortconv_time_pct.train", "gatenorm_time_pct.train", "mfu_pct.train",
        "scope_named_pct.train", "recompute_time_pct.train",
    } <= names
    # readers that count another family's shapes or kernel names
    assert not {
        "kda_time_pct.train", "kda_roofline.train", "shortconv_roofline.train",
        "gatenorm_roofline.train", "flash_mla_roofline.train",
    } & names
    from benchmarks.reference import qwen3_next

    specs = qwen3_next.param_specs(numbers)
    size = lambda pre: sum(
        math.prod(shape) for name, (shape, _) in specs.items()
        if name.startswith(pre)
    )
    experts = 3 * 32 * 2048 * 512
    assert size("layer.0.kda_") == 33_718_464          # ISSUE 45's 33.72 M
    assert size("layer.3.w") + 2 * 256 - experts == 27_263_488  # 27.26 M
    assert size("layer.0.router") + size("layer.0.shared") == 4_196_352  # 4.20 M
    assert size("layer.0.w_") == experts == 100_663_296
    assert size("embedding") + size("lm_head") == 77_791_232
    assert size("") == 625_667_136                     # 625.7 M
    cfg = cell["driver"].transformer_config(numbers)
    delta, attention = cfg.attention_kinds
    assert (delta.mixer, delta.decay, delta.gate_act) == ("delta", "head", "silu")
    assert (delta.n_heads, delta.key_heads, delta.head_dim) == (32, 16, 128)
    assert (attention.n_heads, attention.rope_fraction) == (16, 0.25)
    assert attention.rope_theta == 1e7 and cfg.attention_pattern == (0, 0, 0, 1)
    assert (cfg.n_kv_heads, cfg.head_dim) == (2, 256)
    assert cfg.qk_norm and cfg.norm_unit_offset and cfg.attention_gate == "channel"
    assert cfg.router == "softmax" and cfg.routed_scaling == 1.0
    assert cfg.experts_held == (0, 32) and cfg.num_experts == 512
    assert cfg.experts_per_token == 10 and cfg.d_ff == 512
    assert cfg.moe_shared_ff == 512 and cfg.moe_shared_gate
    assert cfg.ssm_conv == 4 and cfg.ssm_chunk == 128 and not cfg.tie_embeddings


def test_every_number_of_the_catalogs_entry_is_in_the_file_or_reduced(real):
    cell, _ = real
    catalog = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.is_file():
        pytest.skip("no catalog here")
    entry = next(
        e for e in map(json.loads, catalog.read_text().splitlines())
        if e["name"] == "Qwen3-Next-80B-A3B-Instruct"
    )
    bench = loader.load_benchmark(ROOT)
    listed = next(c for c in bench["configs"] if c["name"] == cell["workload"]["config"])
    assert listed["source"] == entry["source_url"] == cell["config"]["source"]
    assert sorted(listed["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    for key, value in entry["config"].items():
        if key in listed["reduced"]:
            assert cell["config"][key] != value, key
        else:
            assert cell["config"][key] == value, key
    assert set(cell["config"]["reduced"]) == set(listed["reduced"])
    assert cell["config"]["experts_routed"] == entry["config"]["num_experts"] == 512
    assert cell["config"]["vocab_size"] * 8 == entry["config"]["vocab_size"]


def test_a_configuration_the_program_does_not_build_is_refused(real):
    cell, _ = real
    driver, config = cell["driver"], cell["config"]
    for change, message in [
        (dict(linear_value_head_dim=64), "one head width"),
        (dict(linear_num_key_heads=12), "key heads divide"),
        (dict(norm_topk_prob=False), "normalised"),
        (dict(mlp_only_layers=[0]), "experts in every layer"),
        (dict(rope_scaling={"type": "yarn"}), "plain rope"),
        (dict(tie_word_embeddings=True), "untied head"),
        (dict(experts_first=500), "not a range"),
    ]:
        with pytest.raises(ValueError, match=message):
            driver.model_numbers(dict(config, **change))


def test_the_flop_count_is_the_issues(real):
    """ISSUE 45's count at S = 8192 and 0.625 rows held a token (32 of 512
    experts, ten a token): ~11 TFLOP a 8,192 tokens (6 x 192 M active
    parameters and the attention layer's kernels)."""
    _, numbers = real
    parts = flops_gdn.flops_by_part(numbers, 8192, 0.625, 128)
    total = sum(parts.values())
    share = {k: round(100 * v / total) for k, v in parts.items()}
    assert total * 8192 == pytest.approx(11.4e12, rel=0.02)
    assert share == {
        "gdn_projections": 43, "gdn_scan": 3, "attn_projections": 12,
        "attention": 14, "shared_and_router": 7, "routed_experts": 3,
        "head": 17,
    }
    a_token = 32 * (6 * 128 * 128 + 2 * 128 * 128) + 16 * 2 * 128 * 128
    assert parts["gdn_scan"] == 3 * 3 * a_token
    assert flops_gdn.mixer_layers(numbers) == (3, 1)


def test_a_delta_rule_calls_needed_work_is_by_the_cells_shapes(real):
    _, numbers = real
    shape = dict(batch=2, seq_len=8192, chunk=128)
    tokens = 2 * 8192
    flops, nbytes = flops_gdn.gdn_call_cost("fwd", numbers, **shape)
    assert flops == tokens * (
        32 * (6 * 128 * 128 + 2 * 128 * 128) + 16 * 2 * 128 * 128
    )
    keys, values, scalars = tokens * 2048 * 2, tokens * 4096 * 2, tokens * 32 * 4
    states = 2 * 64 * 128 * 4096 * 2  # 64 chunks a sequence
    assert nbytes == 2 * keys + 2 * values + 2 * scalars + states
    back, more = flops_gdn.gdn_call_cost("bwd", numbers, **shape)
    assert back == 2 * flops
    assert more == 4 * keys + 3 * values + 4 * scalars + states
    # g widened to a head's lanes would be 128 times the scalars' bytes
    assert tokens * 4096 * 4 == 128 * scalars > 0.3 * nbytes
    kind = flops_gdn.gdn_kernel_kind
    assert kind("gdn_fwd.3") == "fwd" and kind("gdn_bwd.9") == "bwd"
    for name in ("kda_fwd", "ssd_fwd", "flash_fwd_compact.1", "fusion.7"):
        assert kind(name) is None, name


def test_the_new_readers_count_their_calls_only_and_never_raise(real):
    from benchmarks.lib import trace as tracelib

    cell, numbers = real
    ops = [
        ["gdn_fwd.3", 0, 2_000_000], ["gdn_bwd.4", 2_000_000, 6_000_000],
        ["kda_fwd.5", 8_000_000, 7_000_000],
        ["fusion.1", 15_000_000, 5_000_000],
    ]
    reduced = tracelib.reduce(
        {"devices": {0: {"ops": ops, "modules": []}}, "spans": []}
    )
    facts = {"numbers": numbers, "device_kind": "TPU v5 lite", "gdn": {"chunk": 128}}
    context = {**cell, "facts": facts}
    read = lambda name, trace, ctx: loader.load_metric(name).read(trace, [], ctx)
    assert read("gdn_time_pct.train", reduced, context) == pytest.approx(40.0)
    share = read("gdn_roofline.train", reduced, context)
    shape = dict(batch=2, seq_len=8192, chunk=128)
    nbytes = sum(
        flops_gdn.gdn_call_cost(kind, numbers, **shape)[1] for kind in ("fwd", "bwd")
    )
    least = nbytes / 819e9  # memory-bound both ways
    assert share == pytest.approx(100 * least / 8e-3) and 0 < share < 100
    # a program of another family, or without the kernels: nothing to read
    kimi = {**context, "facts": {
        "numbers": {"hidden_size": 2304, "linear_attn_config": {}},
        "device_kind": "TPU v5 lite", "kda": {"chunk": 64},
    }}
    none = tracelib.reduce({"devices": {0: {"ops": ops[2:], "modules": []}}, "spans": []})
    assert read("gdn_roofline.train", reduced, kimi) is None
    assert read("gdn_roofline.train", none, context) is None
    assert read("gdn_time_pct.train", none, context) is None
    # the accepted channel-form readers see none of this cell's calls
    only_gdn = tracelib.reduce({"devices": {0: {"ops": ops[:2] + ops[3:], "modules": []}}, "spans": []})
    assert read("kda_time_pct.train", only_gdn, context) is None
