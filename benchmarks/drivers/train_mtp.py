"""Driver of the `train_mtp` kind: a GLM-4.7-Flash-style decoder (latent
attention whose values are as wide as a head's two parts together, one rope
key for all heads, a leading dense layer, then gated top-k experts beside a
shared one; an untied head) WITH its multi-token module (one further expert
layer over `[embedding | hidden]`, the shared head a second time, a loss
over two targets) through `Trainer` + `fit()`.

The run is `drivers/train_moe.py`'s, as it stands: a private copy of that
module is loaded and what depends on the family is rebound in it (as
`drivers/train_mla.py` does), so its `run()` — and `limits.py`, which calls
this module's `build`, `first_steps`, `run_reference` and `gaps` — reach
this family's glue: the configuration's keys, the `TransformerConfig` they
become, where the program keeps each of the reference's leaves
(`reference/glm_moe_lite.py`, the module's five among them), the routing
counters in rows over the SPARSE layers and the module's block, the two
losses the model counts apart, the comparison over `L`, `main_loss` and
`mtp_loss`, FLOPs by `lib/flops_mtp.py` and the kernels' widths among the
`facts`.
"""

from __future__ import annotations

import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "bench_driver_train_mtp_base",
    pathlib.Path(__file__).with_name("train_moe.py"),
)
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)

WORKLOAD_REQUIRED, WORKLOAD_KEYS = _base.WORKLOAD_REQUIRED, _base.WORKLOAD_KEYS
CONFIG_REQUIRED = {
    "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "q_lora_rank",
    "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "vocab_size", "rms_norm_eps", "rope_theta", "rope_scaling",
    "partial_rotary_factor", "first_k_dense_replace", "n_routed_experts",
    "n_shared_experts", "num_experts_per_tok", "moe_intermediate_size",
    "routed_scaling_factor", "norm_topk_prob", "tie_word_embeddings",
    "num_nextn_predict_layers", "mtp_weight", "experts_routed",
    "experts_first",
}
CONFIG_KEYS = CONFIG_REQUIRED | {
    "model_type", "attention_bias", "hidden_act", "max_position_embeddings",
    "n_group", "topk_group", "topk_method",
}
LOSSES = ("loss", "main_loss", "mtp_loss")
# `fit()` writes every counter the model sows into its records; the model
# counts its two losses apart there, which `first_steps` then reports too.
COUNTERS = _base.COUNTERS = (*_base.COUNTERS, *LOSSES[1:])
# Both walk `_program_path`, which is rebound below.
to_program_tree, from_program_tree = _base.to_program_tree, _base.from_program_tree


def preload() -> None:
    """The program's imports, made while the chip is still being reached;
    a program whose decoder has no multi-token module fails here, at
    once."""
    _base.preload()
    import dataclasses

    import kubeflow_tpu.models.transformer as model

    fields = {f.name for f in dataclasses.fields(model.TransformerConfig)}
    if not {"kv_latent", "mtp_layers", "mtp_weight"} <= fields:
        raise ImportError(
            "the program's decoder has no latent attention or no "
            "multi-token module (`TransformerConfig.mtp_layers`)"
        )


def model_numbers(config: dict) -> dict:
    """The configuration's keys, checked for what the program's decoder
    can express."""
    if config["tie_word_embeddings"] or config.get("attention_bias"):
        raise ValueError("an untied head and no biases are built")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("latent attention has as many K/V heads as query heads")
    if config["v_head_dim"] not in (
        config["qk_nope_head_dim"],
        config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
    ):
        raise ValueError(
            "the kernels want v as wide as a head's own part or as its two "
            "parts together"
        )
    if not config["norm_topk_prob"] or config.get("topk_method") != "noaux_tc":
        raise ValueError("sigmoid scores and normalised weights are built")
    if config.get("n_group", 1) != 1 or config.get("topk_group", 1) != 1:
        raise ValueError("one group of experts is built")
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError("silu experts are built")
    if config["num_nextn_predict_layers"] not in (0, 1):
        raise ValueError("a multi-token module of depth 0 or 1 is built")
    if config["rope_scaling"] is not None or config["partial_rotary_factor"] != 1:
        raise ValueError("plain rope over the whole rope part is built here")
    if not 0 < config["first_k_dense_replace"] <= config["num_hidden_layers"]:
        raise ValueError("leading dense layers, then sparse ones, are built")
    if config["experts_first"] + config["n_routed_experts"] > config["experts_routed"]:
        raise ValueError("the experts held are not a range of those routed")
    out = {k: config[k] for k in CONFIG_REQUIRED}
    # What `train_moe.run`'s own FLOP count and the accepted readers ask
    # for under their names (`run` below replaces the count with
    # `lib/flops_mtp`'s): the experts held, no router MLP and no CCA, and
    # a head as the flash calls see it, `flash_roofline.train`'s `d`: q's
    # and k's two parts side by side where v is that wide (the one-part
    # calls), else the own part.
    out.update(
        num_experts=config["n_routed_experts"], head_dim=config["v_head_dim"],
        router_hidden_size=0, cca_time1=0,
    )
    return out


_ATTN = ("wq_a", "wkv_a", "wo")
_DENSE = {"mlp_gate": "wi_gate", "mlp_up": "wi_up", "mlp_down": "wo"}
_SHARED = {"shared_gate": "wi_gate", "shared_up": "wi_up", "shared_down": "wo"}
_MODULE = {
    "enorm": ("enorm", "scale"), "hnorm": ("hnorm", "scale"),
    "eh_proj": ("eh_proj", "kernel"), "head_norm": ("head_norm", "scale"),
}


def _program_path(name: str) -> tuple[str, ...]:
    """Where the program's `TransformerLM` keeps the reference's leaf."""
    if name in ("embedding", "lm_head"):
        return (name,)
    if name == "ln_final":
        return ("ln_final", "scale")
    where, i, *leaf = name.split(".", 2)
    if not leaf:  # the module's own four
        return ("mtp", *_MODULE[i])
    leaf = leaf[0]
    if leaf in ("ln_attn", "ln_mlp"):
        sub = (leaf, "scale")
    elif leaf in ("q_norm", "kv_norm"):
        sub = ("attn", leaf, "scale")
    elif leaf in _ATTN:
        sub = ("attn", leaf, "kernel")
    elif leaf in ("wq_b", "wkv_b"):
        sub = ("attn", leaf)
    elif leaf in _DENSE:
        sub = ("mlp", _DENSE[leaf], "kernel")
    elif leaf in _SHARED:
        sub = ("moe", "shared", _SHARED[leaf], "kernel")
    else:  # the router's leaves and the experts'
        sub = ("moe", leaf)
    return (("mtp", "block") if where == "mtp" else (f"layer_{i}",)) + sub


def transformer_config(numbers: dict, **how):
    """The program's `TransformerConfig` for the configuration's numbers."""
    from kubeflow_tpu.models.transformer import AttentionKind, TransformerConfig

    kind = AttentionKind(
        n_heads=numbers["num_attention_heads"], window=None,
        rope_theta=float(numbers["rope_theta"]), rope_fraction=1.0,
    )
    ff = numbers["moe_intermediate_size"]
    return TransformerConfig(
        vocab_size=numbers["vocab_size"], d_model=numbers["hidden_size"],
        n_layers=numbers["num_hidden_layers"], tie_embeddings=False,
        norm_eps=numbers["rms_norm_eps"],
        n_heads=numbers["num_attention_heads"],
        head_dim=numbers["qk_nope_head_dim"],
        q_latent=numbers["q_lora_rank"], kv_latent=numbers["kv_lora_rank"],
        rope_head_dim=numbers["qk_rope_head_dim"],
        v_head_dim=numbers["v_head_dim"],
        attention_kinds=(kind,),
        attention_pattern=(0,) * numbers["num_hidden_layers"],
        dense_layers=numbers["first_k_dense_replace"],
        dense_d_ff=numbers["intermediate_size"], d_ff=ff, mlp_act="swiglu",
        num_experts=numbers["experts_routed"],
        experts_held=(numbers["experts_first"], numbers["n_routed_experts"]),
        experts_per_token=numbers["num_experts_per_tok"], router="sigmoid",
        routed_scaling=float(numbers["routed_scaling_factor"]),
        moe_shared_ff=numbers["n_shared_experts"] * ff,
        mtp_layers=numbers["num_nextn_predict_layers"],
        mtp_weight=float(numbers["mtp_weight"]),
        router_force_balance=numbers.get("router_force_balance", False), **how,
    )


def _as_this_family(function):
    """`function` of `train_moe.py` as it stands, over this family's
    reference: those functions import `benchmarks.reference.zaya` by name
    when they are called and ask of it `init_params`, `param_specs`,
    `init_leaf` and `follow`, which `reference/glm_moe_lite.py` answers
    under the same names, so for the length of the call that module stands
    in for it. (`glm_moe_lite` imports `zaya` itself, so it is imported
    first.)"""
    import functools

    @functools.wraps(function)
    def call(*args, **kwargs):
        import benchmarks.reference as package
        from benchmarks.reference import glm_moe_lite, zaya

        package.zaya = glm_moe_lite
        try:
            return function(*args, **kwargs)
        finally:
            package.zaya = zaya

    return call


run_reference = _as_this_family(_base.run_reference)
_build, _first_steps = (
    _as_this_family(_base.build), _as_this_family(_base.first_steps)
)
_gaps = _base.gaps  # `drivers/train.py`'s, before this family's is bound


def build(cell: dict, seed: int, devices):
    """`train_moe.build`'s trainer, whose step takes the model's own scalar
    as the loss (`TrainConfig.loss_in_model`: the module reads the labels,
    and the objective has two terms). The step is made when `fit()` first
    asks for it, from the configuration the trainer holds then."""
    import dataclasses

    trainer, feed, key, numbers = _build(cell, seed, devices)
    trainer.config = dataclasses.replace(trainer.config, loss_in_model=True)
    return trainer, feed, key, numbers


def first_steps(trainer, feed, key, numbers, fit, mark=lambda what: None) -> dict:
    """`train_moe.first_steps`, and the two losses the model counted apart
    in each of the three records."""
    program = _first_steps(trainer, feed, key, numbers, fit, mark)
    for name in LOSSES[1:]:
        program[name] = [counted[name] for counted in program["counters"]]
    return program


def gaps(program: dict, ref: dict) -> dict:
    """`drivers/train.gaps`'s numbers (`loss` is L), and the widest
    relative gap of the steps' `main_loss` and `mtp_loss` apart."""
    out = _gaps(program, ref)
    for name in LOSSES[1:]:
        out[name] = max(
            abs(p - r) / abs(r) for p, r in zip(program[name], ref[name])
        )
    return out


def compare(program: dict, ref: dict, limits: dict, checks) -> None:
    """`drivers/train.compare` with the limit of `loss` held on each of
    `LOSSES`: a module that predicts the wrong token, or a weight of 0,
    moves `mtp_loss` or `loss` where `main_loss` stands."""
    if set(limits) != _base.LIMIT_KEYS:
        raise ValueError(f"limits must be exactly {sorted(_base.LIMIT_KEYS)}")
    read = gaps(program, ref)
    for name in LOSSES:
        checks.at_most(
            f"{name}, steps 1-3, widest |program - reference| / reference",
            read[name], limits["loss"],
            f"program {program[name]} reference {ref[name]}",
        )
    for what in ("first_grad_norm", "change_norm"):
        checks.at_most(
            f"{what}, worst leaf, |program - reference| / "
            "max(reference leaf, median leaf)",
            read[what], limits[what], f"at {read[what + '_leaf']}",
        )


def routed(records: list[dict], numbers: dict, tokens_a_step: int) -> dict:
    """What the counters of some steps' records say: the rows (token-expert
    pairs) a sparse block — a SPARSE layer or the module's — routed to the
    experts held here a step (`tokens_held_a_layer`, the name the accepted
    readers take), those rows a token (`held_share`), the fullest held
    expert's load over the mean one's, and the two losses of the last
    record."""
    blocks = (
        numbers["num_hidden_layers"] - numbers["first_k_dense_replace"]
        + numbers["num_nextn_predict_layers"]
    )
    mean = lambda name: sum(r[name] for r in records) / len(records)
    held = mean("moe_tokens_held") / blocks
    return {
        "tokens_held_a_layer": held,
        "held_share": held / tokens_a_step,
        "load_max_over_mean": mean("moe_load_max") / mean("moe_load_mean"),
        **{name + "_last": records[-1][name] for name in LOSSES[1:]},
        "records": len(records),
    }


def kernel_widths(work: dict, numbers: dict) -> dict:
    """The widths and the schedule of the attention calls, by
    `flash_schedule`: the one-part calls at a head's two parts together
    where v is that wide, else the two-part calls."""
    from kubeflow_tpu.ops.attention import latent_form
    from kubeflow_tpu.ops.flash import flash_schedule

    own, rope = numbers["qk_nope_head_dim"], numbers["qk_rope_head_dim"]
    form = latent_form(own, rope, numbers["v_head_dim"])
    parts = dict(head_dim=own + rope) if form == "joined" else dict(
        head_dim=own, rope_dim=rope
    )
    sched = flash_schedule(work["seq_len"], work["seq_len"], **parts)
    keys = (
        "qk_dim", "rope_dim", "v_dim", "layout", "block_q", "grid_steps",
        "computed_pairs_over_needed", "bwd_fused", "bwd_fused_vmem_bytes",
    )
    return {"form": form, **{k: sched[k] for k in keys}}


for _name in (
    "model_numbers", "_program_path", "transformer_config", "build",
    "first_steps", "run_reference", "routed", "gaps", "compare",
):
    setattr(_base, _name, globals()[_name])

WINDOW_CHECK = "loss, last step of the window"


def run(cell: dict, args, clock_start: float, say) -> dict:
    """One run of a train_mtp cell: `train_moe.run` over this family's
    glue, then the facts that are this family's own. `train_moe.run` holds
    the window's last `loss` to ln(vocab); here that is L, of two terms, so
    its row is replaced by one for each term, each under the same limit."""
    import math

    from benchmarks.lib import flops_mtp

    out = _base.run(cell, args, clock_start, say)
    facts, work = out["facts"], cell["workload"]
    rows = out["checks"].rows
    if not rows[-1]["check"].startswith(WINDOW_CHECK):
        raise RuntimeError(f"expected the window's row last: {rows[-1]}")
    rows.pop()
    ln_vocab = math.log(facts["numbers"]["vocab_size"])
    for name in LOSSES[1:]:
        out["checks"].at_most(
            f"{name}, last step of the window, |value - ln(vocab)| / ln(vocab)",
            abs(facts["moe"][name + "_last"] - ln_vocab) / ln_vocab,
            work["limits"]["window_loss"],
            "random tokens: each loss stays near ln(vocab) while training "
            "is sound",
        )
    parts = flops_mtp.flops_by_part(
        facts["numbers"], work["seq_len"], facts["moe"]["held_share"]
    )
    facts["flops_per_token"] = float(sum(parts.values()))
    facts["flops_by_part"] = parts
    facts["attention"] = kernel_widths(work, facts["numbers"])
    say("flops", per_token=facts["flops_per_token"], **parts)
    say("attention", **facts["attention"])
    return out
