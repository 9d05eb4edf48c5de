"""The train_mla driver end to end on the CPU, at the tiny size of
`data_mla/workloads/tiny-xing.train.json` (half of eight experts held, two
heads of 16 + 8 over v of 16, four streams, S = 64); the int8 control that
the comparison has to fail; the new readers on a hand-made trace; and
`lib/flops_mla.py`'s counts against the numbers ISSUE 39 gives."""

import json
import pathlib
import time
import types

import pytest

from benchmarks import run as runmod
from benchmarks.lib import compare, flops_mla, loader

DATA = pathlib.Path(__file__).parent / "data_mla"
ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "xing4.0-29b-a4b-ep8.train-8k"


@pytest.fixture(scope="module")
def cell():
    bench = json.loads((DATA / "BENCHMARK.json").read_text())
    return loader.load_cell("tiny-xing.train", bench, base=DATA, root=DATA)


@pytest.fixture(scope="module")
def real():
    cell = loader.load_cell(CELL, loader.load_benchmark(ROOT))
    return cell, cell["driver"].model_numbers(cell["config"])


def test_a_sound_run_is_correct_and_reports_its_routing_and_its_widths(
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
    # the streams' counters ride the `[routed]` lines
    assert 0 <= moe["hc_sinkhorn_err"] < 2e-2 and 0.3 < moe["hc_res_diag_mean"] < 0.8
    assert facts["flops_per_token"] == flops_mla.mla_flops_per_token(
        numbers, 64, moe["held_share"]
    ) == sum(facts["flops_by_part"].values())
    assert facts["mla"]["qk_dim"] == 16 and facts["mla"]["rope_dim"] == 8
    assert facts["mla"]["v_dim"] == 16
    assert facts["mla"]["rope_layout"] == "head_major"


def test_fit_records_the_streams_counters_beside_the_routing(cell):
    import jax

    from kubeflow_tpu.train import fit

    driver = cell["driver"]
    trainer, feed, key, numbers = driver.build(cell, 7, jax.devices())
    program = driver.first_steps(trainer, feed, key, numbers, fit)
    assert len(program["counters"]) == 3
    for counted in program["counters"]:
        assert 0 <= counted["hc_sinkhorn_err"] < 2e-2
        assert 0.3 < counted["hc_res_diag_mean"] < 0.8
        assert counted["moe_tokens_held"] > 0


def test_the_int8_control_is_not_correct(cell):
    """The reference in the program's place, every matmul in int8 (the
    router's and the maps' product too): the nearest precision below the
    bfloat16 the configuration states."""
    import jax

    from benchmarks.reference import lm as reference

    driver = cell["driver"]
    _, feed, key, numbers = driver.build(cell, 21, jax.devices())
    ref = driver.run_reference(cell, key, numbers, feed, jax.devices())
    control = driver.run_reference(
        cell, key, numbers, feed, jax.devices(), quant=reference.int8_quant
    )
    checks = compare.Checks()
    driver.compare(control, ref, cell["workload"]["limits"], checks)
    assert not checks.correct, checks.lines()


def test_the_stand_in_leaves_the_references_as_they_were(cell):
    import jax

    import benchmarks.reference as package
    from benchmarks.reference import xing, zaya

    cell["driver"].build(cell, 5, jax.devices())
    assert package.zaya is zaya and xing._zaya is zaya


def test_the_cell_resolves_with_its_readers_and_its_759_million(real):
    cell, numbers = real
    names = {m["name"] for m in cell["per_layer"]}
    assert {
        "flash_mla_roofline.train", "hc_time_pct.train", "hc_roofline.train",
        "flash_time_pct.train", "moe_gmm_roofline.train", "mfu_pct.train",
        "scope_named_pct.train", "recompute_time_pct.train",
    } <= names
    assert "flash_roofline.train" not in names  # its reader costs one `d`
    from benchmarks.reference import xing

    import math

    held = sum(math.prod(shape) for shape, _ in xing.param_specs(numbers).values())
    assert held == 759_346_446
    assert f"{held:,}" in cell["config"]["deployment"]
    cfg = cell["driver"].transformer_config(numbers)
    assert (cfg.q_latent, cfg.kv_latent) == (768, 512)
    assert (cfg.head_dim, cfg.rope_head_dim, cfg.v_head_dim) == (128, 64, 128)
    assert cfg.residual_streams == 4 and cfg.hc_iters == 20
    assert cfg.dense_layers == 1 and cfg.experts_held == (0, 8)
    # 192^-1/2 x mscale(64, 1)^2, and cos and sin unscaled
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * 2.0047, rel=1e-4)
    assert cfg.attention_kinds[0].rope_yarn == (64.0, 4096, 32.0, 1.0, 1.0)


def test_the_flop_count_is_the_issues(real):
    """ISSUE 39's count at S = 8192 and 0.5 rows held a token (8 of 64
    experts, four a token), GFLOP a token by part."""
    _, numbers = real
    parts = flops_mla.flops_by_part(numbers, 8192, 0.5)
    giga = {k: round(v / 1e9, 2) for k, v in parts.items()}
    assert giga == {
        "latent_projections": 0.85, "attention": 1.26, "streams": 0.03,
        "dense_mlp": 0.59, "shared_and_router": 0.27, "routed_experts": 0.13,
        "head": 0.35,
    }
    assert parts["attention"] == 5 * 3 * 32 * 4096.5 * (2 * 192 + 2 * 128)
    assert flops_mla.mla_flops_per_token(numbers, 8192, 0.5) == pytest.approx(
        3.485e9, rel=1e-3
    )


def test_a_two_part_calls_needed_work_is_at_the_true_widths():
    s = 8192
    pairs = s * (s + 1) // 2
    shape = dict(batch=1, heads=32, seq_len=s, nope=128, rope=64, v_dim=128)
    flops, nbytes = flops_mla.mla_call_cost("fwd", **shape)
    assert flops == 2 * pairs * 32 * (192 + 128)
    # q's two parts, k_n, v and o once a head; the rope key once
    assert nbytes == s * 2 * (32 * (192 + 128 + 2 * 128) + 64)
    back, more = flops_mla.mla_call_cost("bwd_fused", **shape)
    assert back == 2 * flops
    # q, dq | k_n, dk_n | v, o, dO, dv once a head; k_r and dk_r once
    assert more == s * 2 * (32 * (2 * 192 + 2 * 128 + 4 * 128) + 2 * 64)
    assert flops_mla.mla_call_cost("dq", **shape)[0] == flops
    assert flops_mla.mla_call_cost("dkv", **shape)[0] == 2 * pairs * 32 * (192 + 256)
    kind = flops_mla.mla_kernel_kind
    assert kind("flash_fwd_mla.3") == "fwd"
    assert kind("flash_bwd_mla_fused.9") == "bwd_fused"
    assert kind("flash_dq_mla_rect") == "dq" and kind("flash_dkv_mla") == "dkv"
    for name in ("flash_fwd_compact.1", "flash_bwd_fused.2", "flash_delta.4",
                 "flash_fwd_window", "fusion.7", "moe_gmm_fwd"):
        assert kind(name) is None, name
    # 28.7 KB a token a sublayer forward, as ISSUE 39 counts a pass
    assert 4 * 3584 * 2 == 28_672
    assert flops_mla.hc_bytes(
        {"hc_mult": 4, "hidden_size": 3584, "num_hidden_layers": 5}, 8192
    ) == 10 * 8192 * 24 * 3584 * 2


def test_the_new_readers_count_their_calls_only_and_never_raise(real):
    """A two-part call and a plain call in one trace: the roofline counts
    the first alone; for a configuration with no rope part or no streams
    (the parent's cells), None and no error."""
    from benchmarks.lib import trace as tracelib

    cell, numbers = real
    ops = [
        ["flash_fwd_mla.3", 0, 8_000_000],
        ["flash_bwd_mla_fused.4", 8_000_000, 16_000_000],
        ["flash_fwd_compact.5", 24_000_000, 7_000_000],
        ["flash_delta.6", 31_000_000, 1_000_000], ["fusion.1", 32_000_000, 8_000_000],
    ]
    reduced = tracelib.reduce(
        {"devices": {0: {"ops": ops, "modules": []}}, "spans": []}
    )
    context = {**cell, "facts": {"numbers": numbers, "device_kind": "TPU v5 lite"}}
    read = lambda name, trace, ctx: loader.load_metric(name).read(trace, [], ctx)
    assert read("flash_time_pct.train", reduced, context) == pytest.approx(80.0)
    share = read("flash_mla_roofline.train", reduced, context)
    fwd = flops_mla.mla_call_cost(
        "fwd", batch=1, heads=32, seq_len=8192, nope=128, rope=64, v_dim=128
    )[0]
    assert share == pytest.approx(100 * (3 * fwd / 197e12) / 24e-3)
    assert 0 < share < 100
    plain = {**context, "facts": {
        "numbers": {"hidden_size": 2048}, "device_kind": "TPU v5 lite",
    }}
    for name in ("flash_mla_roofline.train", "hc_roofline.train"):
        assert read(name, reduced, plain) is None
    # no registered step program, no profile: no table, so None
    assert read("hc_time_pct.train", reduced, context) is None
    assert read("hc_roofline.train", reduced, context) is None
    streams = loader.load_metric("hc_time_pct.train").in_streams
    assert streams("hc_attn/hc.maps") and streams("hc.post") and streams("hc.entry")
    assert not streams("attn/attn.latent_q") and not streams("mlp/wo")
