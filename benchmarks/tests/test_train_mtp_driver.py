"""The train_mtp driver end to end on the CPU, at the tiny size of
`data_mtp/workloads/tiny-glm.train.json` (half of eight experts held, two
heads of 24 + 8 over v of 32, the module's block and both losses, S = 64);
the int8 control that the comparison has to fail; a module that predicts
the wrong token, which it has to fail too; the new reader on a hand-made
table; and `lib/flops_mtp.py`'s counts against the numbers ISSUE 48
gives."""

import json
import math
import pathlib
import time
import types

import pytest

from benchmarks import run as runmod
from benchmarks.lib import compare, flops_mtp, loader

DATA = pathlib.Path(__file__).parent / "data_mtp"
ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "glm-4.7-flash-ep8.train-8k"


@pytest.fixture(scope="module")
def cell():
    bench = json.loads((DATA / "BENCHMARK.json").read_text())
    return loader.load_cell("tiny-glm.train", bench, base=DATA, root=DATA)


@pytest.fixture(scope="module")
def real():
    cell = loader.load_cell(CELL, loader.load_benchmark(ROOT))
    return cell, cell["driver"].model_numbers(cell["config"])


def test_a_sound_run_is_correct_and_reports_both_losses_and_its_routing(
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
    # L, main_loss, mtp_loss, the two norms, and the window's two losses
    names = [row["check"].split(",")[0] for row in out["checks"].rows]
    assert names == [
        "loss", "main_loss", "mtp_loss", "first_grad_norm", "change_norm",
        "main_loss", "mtp_loss",
    ]
    assert set(out["end_to_end"]) == {"tokens_per_s_per_chip", "setup_s"}
    assert lines["window"]["compilations_in_window"] == 0
    facts = out["facts"]
    moe, numbers = facts["moe"], facts["numbers"]
    # Four of eight experts held, two a token: rows, over the two SPARSE
    # layers and the module's block.
    assert 0 < moe["held_share"] < 2 and moe["load_max_over_mean"] >= 1
    assert moe["tokens_held_a_layer"] == moe["held_share"] * 4 * 64
    ln_vocab = math.log(64)
    for name in ("main_loss_last", "mtp_loss_last"):
        assert abs(moe[name] - ln_vocab) < 0.2 * ln_vocab
    # the window's `loss` is L = main + 0.3 mtp, and is not held to ln(vocab)
    assert lines["window"]["loss_last"] == pytest.approx(
        moe["main_loss_last"] + 0.3 * moe["mtp_loss_last"], rel=1e-5
    )
    assert facts["flops_per_token"] == flops_mtp.mtp_flops_per_token(
        numbers, 64, moe["held_share"]
    ) == sum(facts["flops_by_part"].values())
    assert facts["attention"]["form"] == "joined"
    assert facts["attention"]["qk_dim"] == facts["attention"]["v_dim"] == 32
    assert facts["attention"]["rope_dim"] == 0


@pytest.fixture(scope="module")
def followed(cell):
    import jax

    driver = cell["driver"]
    _, feed, key, numbers = driver.build(cell, 21, jax.devices())
    ref = driver.run_reference(cell, key, numbers, feed, jax.devices())
    return driver, feed, key, numbers, ref


def test_the_int8_control_is_not_correct(cell, followed):
    """The reference in the program's place, every matmul in int8 (the
    router's too): the nearest precision below the bfloat16 the
    configuration states."""
    import jax

    from benchmarks.reference import lm as reference

    driver, feed, key, numbers, ref = followed
    control = driver.run_reference(
        cell, key, numbers, feed, jax.devices(), quant=reference.int8_quant
    )
    checks = compare.Checks()
    driver.compare(control, ref, cell["workload"]["limits"], checks)
    assert not checks.correct, checks.lines()
    assert len(checks.rows) == 5


@pytest.mark.parametrize("fault", ["the_next_token", "a_weight_of_zero"])
def test_a_module_with_the_wrong_target_or_no_weight_is_not_correct(
    cell, followed, monkeypatch, fault
):
    """What the gaps on `main_loss` and `mtp_loss` APART are for: a module
    that predicts t_(i+1) like the main head moves `mtp_loss` alone at the
    seed; a weight of 0 moves `loss` and leaves the two."""
    import jax

    from benchmarks.reference import glm_moe_lite

    driver, feed, key, numbers, ref = followed
    if fault == "a_weight_of_zero":
        numbers = dict(numbers, mtp_weight=0.0)
    else:
        sound = glm_moe_lite._summed_ce

        def shifted_back(z, labels):  # the module's call hands S - 1 rows
            if z.shape[1] == cell["workload"]["seq_len"] - 1:
                labels = jax.numpy.roll(labels, 1, axis=1)
            return sound(z, labels)

        monkeypatch.setattr(glm_moe_lite, "_summed_ce", shifted_back)
    wrong = driver.run_reference(cell, key, numbers, feed, jax.devices())
    checks = compare.Checks()
    driver.compare(wrong, ref, cell["workload"]["limits"], checks)
    failed = {r["check"].split(",")[0] for r in checks.rows if not r["ok"]}
    assert failed and failed <= (
        {"loss"} if fault == "a_weight_of_zero" else {"loss", "mtp_loss"}
    ) | {"first_grad_norm", "change_norm"}, checks.lines()
    assert ("mtp_loss" in failed) == (fault == "the_next_token")


def test_the_stand_in_leaves_the_references_as_they_were(cell):
    import jax

    import benchmarks.reference as package
    from benchmarks.reference import zaya

    trainer, *_ = cell["driver"].build(cell, 5, jax.devices())
    assert package.zaya is zaya
    assert trainer.config.loss_in_model  # the model's own scalar is the loss


def test_the_cell_resolves_with_its_readers_and_its_706_million(real):
    cell, numbers = real
    names = {m["name"] for m in cell["per_layer"]}
    assert {
        "mtp_time_pct.train", "flash_roofline.train", "flash_time_pct.train",
        "moe_gmm_roofline.train", "mfu_pct.train", "scope_named_pct.train",
        "recompute_time_pct.train", "head_loss_time_pct.train",
        "router_time_pct.train", "moe_load_max_over_mean.train",
    } <= names
    assert "flash_mla_roofline.train" not in names  # no two-part call runs
    assert {m["name"] for m in cell["end_to_end"]} == {
        "tokens_per_s_per_chip", "setup_s"}
    from benchmarks.reference import glm_moe_lite

    held = sum(
        math.prod(shape) for shape, _ in glm_moe_lite.param_specs(numbers).values()
    )
    assert held == 706_518_848
    assert f"{held:,}" in cell["config"]["deployment"]
    cfg = cell["driver"].transformer_config(numbers)
    assert (cfg.q_latent, cfg.kv_latent) == (768, 512)
    assert (cfg.head_dim, cfg.rope_head_dim, cfg.v_head_dim) == (192, 64, 256)
    assert cfg.mtp_layers == 1 and cfg.mtp_weight == 0.3
    assert cfg.dense_layers == 1 and cfg.experts_held == (0, 8)
    assert cfg.softmax_scale is None  # (192 + 64)^-1/2, no yarn
    assert cfg.attention_kinds[0].rope_yarn is None
    assert cfg.attention_kinds[0].rope_theta == 1e6
    # what `flash_roofline.train`'s reader costs a call by
    assert (numbers["head_dim"], numbers["num_attention_heads"]) == (256, 20)
    # every published key of the catalog's entry but the three cut
    assert cell["config"]["num_nextn_predict_layers"] == 1
    assert set(cell["config"]["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}


def test_the_flop_count_is_the_issues(real):
    """ISSUE 48's count at S = 8192 and 0.5 rows held a token (8 of 64
    experts, four a token): TFLOP a step forward by part, 8,192 tokens."""
    _, numbers = real
    parts = flops_mtp.flops_by_part(numbers, 8192, 0.5)
    forward = {k: round(v * 8192 / 3 / 1e12, 2) for k, v in parts.items()}
    assert forward == {
        # five blocks of 0.36 latent projections and 0.69 causal attention
        "latent_projections": 1.78, "attention": 3.44, "dense_mlp": 1.03,
        # four sparse layers of 0.16 shared + router and 0.08 held experts
        "shared_and_router": 0.63, "routed_experts": 0.31, "head": 0.65,
        "mtp_block": 1.28, "mtp_proj": 0.14, "mtp_head": 0.65,
    }
    assert parts["attention"] == 5 * 3 * 20 * 4096.5 * (2 * 256 + 2 * 256)
    assert flops_mtp.mtp_flops_per_token(numbers, 8192, 0.5) == pytest.approx(
        sum(forward.values()) * 3e12 / 8192, rel=5e-3
    )
    # the module with its head: a fifth of the step's model FLOPs
    module = parts["mtp_block"] + parts["mtp_proj"] + parts["mtp_head"]
    assert 0.19 < module / sum(parts.values()) < 0.23


def test_the_new_reader_takes_the_modules_paths_in_every_phase_and_never_raises(
    real
):
    from benchmarks.lib import scopes
    from benchmarks.lib import trace as tracelib

    cell, numbers = real
    reader = loader.load_metric("mtp_time_pct.train")
    inside = reader.in_module
    for comp in ("mtp/block/attn/wo", "mtp/mtp.proj/eh_proj", "mtp/mtp.head",
                 "mtp/mtp.loss", "mtp/block/moe/moe.route"):
        assert inside(comp), comp
    for comp in ("attn/wo", "head/bsd,vd->bsv", "loss", "embed", "optimizer",
                 "moe/moe.experts/moe_gmm_fwd", scopes.UNNAMED):
        assert not inside(comp), comp
    # the accepted readers over the module's paths: the by-kind shares hold
    # them, the head's and the loss's reads the MAIN ones only
    assert scopes.in_router("mtp/block/moe/moe.route")
    assert not scopes.in_head_or_loss("mtp/mtp.head", "forward")
    assert not scopes.in_head_or_loss("mtp/mtp.loss", "backward")
    assert scopes.in_head_or_loss("loss", "forward")
    scoped = scopes.ScopedTime(100, {
        ("mtp/block/attn/wo", "forward", "matmul"): 7,
        ("mtp/block/attn/wo", "recompute", "matmul"): 6,
        ("mtp/mtp.loss", "backward", "elementwise"): 5,
        ("attn/wo", "forward", "matmul"): 30, ("loss", "forward", "elementwise"): 9,
    }, 100, 100, 0, {}, {}, {})
    assert scoped.share(lambda comp, phase, kind: inside(comp)) == 18.0
    # no registered step program, no profile: no table, so None and no error
    reduced = tracelib.reduce(
        {"devices": {0: {"ops": [["fusion.1", 0, 8_000_000]], "modules": []}},
         "spans": []}
    )
    context = {**cell, "facts": {"numbers": numbers, "device_kind": "TPU v5 lite"}}
    assert reader.read(reduced, [], context) is None
