"""Driver of the `train_kda` kind: a Kimi-Linear-style decoder (a gated
delta-rule mixer, KDA, in most layers and un-rotated latent attention with a
directly projected q in the others, one stack of blocks; a leading dense
layer, then gated top-k experts beside a shared one; an untied head) through
`Trainer` + `fit()`.

The run is `drivers/train_moe.py`'s, as it stands: a private copy of that
module is loaded and what depends on the family is rebound in it (as
`drivers/train_mla.py` and `drivers/train_window.py` do), so its `run()` —
and `limits.py`, which calls this module's `build`, `first_steps`,
`run_reference` and `gaps` — reach this family's glue: the configuration's
keys, the `TransformerConfig` they become, where the program keeps each of
the reference's leaves (`reference/kimi_linear.py`), the routing counters in
rows over the SPARSE layers, the mixer's counters, FLOPs by
`lib/flops_kda.py` and the kernels' schedules among the `facts`.
"""

from __future__ import annotations

import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "bench_driver_train_kda_base",
    pathlib.Path(__file__).with_name("train_moe.py"),
)
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)

WORKLOAD_REQUIRED, WORKLOAD_KEYS = _base.WORKLOAD_REQUIRED, _base.WORKLOAD_KEYS
CONFIG_REQUIRED = {
    "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "q_lora_rank",
    "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "vocab_size", "rms_norm_eps", "mla_use_nope", "linear_attn_config",
    "first_k_dense_replace", "num_experts", "num_shared_experts",
    "num_experts_per_token", "moe_intermediate_size", "routed_scaling_factor",
    "moe_renormalize", "moe_router_activation_func", "tie_word_embeddings",
    "num_nextn_predict_layers", "experts_routed", "experts_first",
}
CONFIG_KEYS = CONFIG_REQUIRED | {
    "model_type", "head_dim", "hidden_act", "model_max_length",
    "moe_layer_freq", "num_expert_group", "topk_group", "use_grouped_topk",
    "rope_scaling", "rope_theta",
}
# Positions a chunk of the delta rule: the program's choice, no published
# key (`ops/kda.py`; 128 chunks a sequence of 8,192).
KDA_CHUNK = 64
# `fit()` writes every counter the model sows into its records; the mixer
# sows two, which `first_steps` then reports too.
COUNTERS = _base.COUNTERS = (
    *_base.COUNTERS, "kda_decay_mean", "kda_beta_mean",
)
gaps, compare = _base.gaps, _base.compare
# Both walk `_program_path`, which is rebound below.
to_program_tree, from_program_tree = _base.to_program_tree, _base.from_program_tree


def preload() -> None:
    """The program's imports, made while the chip is still being reached;
    a program without the delta-rule mixer fails here, at once."""
    _base.preload()
    import dataclasses

    import kubeflow_tpu.models.transformer as model

    fields = {f.name for f in dataclasses.fields(model.AttentionKind)}
    if "mixer" not in fields or not hasattr(model, "DeltaMixer"):
        raise ImportError(
            "the program's decoder has no delta-rule mixer (no "
            "`AttentionKind.mixer`, no `DeltaMixer`)"
        )
    import kubeflow_tpu.ops.kda  # noqa: F401


def model_numbers(config: dict) -> dict:
    """The configuration's keys, checked for what the program's decoder
    can express."""
    linear = config["linear_attn_config"]
    layers = list(range(1, config["num_hidden_layers"] + 1))
    if sorted(linear["kda_layers"] + linear["full_attn_layers"]) != layers:
        raise ValueError("every layer is in exactly one of the two lists")
    if config["tie_word_embeddings"] or config["num_nextn_predict_layers"]:
        raise ValueError("an untied head and no multi-token module are built")
    if config["q_lora_rank"] is not None or not config["mla_use_nope"]:
        raise ValueError("a directly projected q and no rotation are built")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("latent attention has as many K/V heads as query heads")
    if config["v_head_dim"] != config["qk_nope_head_dim"]:
        raise ValueError("the kernels want v as wide as q's and k's own part")
    if linear["head_dim"] != config["qk_nope_head_dim"]:
        raise ValueError("one head width for both mixers is built")
    if (
        config["moe_router_activation_func"] != "sigmoid"
        or not config["moe_renormalize"]
    ):
        raise ValueError("sigmoid scores and normalised weights are built")
    if config.get("num_expert_group", 1) != 1 or config.get("topk_group", 1) != 1:
        raise ValueError("one group of experts is built")
    if (
        config.get("moe_layer_freq", 1) != 1
        or config.get("hidden_act", "silu") != "silu"
    ):
        raise ValueError("experts in every layer after the dense ones, silu")
    if not 0 < config["first_k_dense_replace"] <= config["num_hidden_layers"]:
        raise ValueError("leading dense layers, then sparse ones, are built")
    if config["experts_first"] + config["num_experts"] > config["experts_routed"]:
        raise ValueError("the experts held are not a range of those routed")
    out = {k: config[k] for k in CONFIG_REQUIRED}
    # What `train_moe.run`'s own FLOP count asks for under its names (`run`
    # below replaces the count with `lib/flops_kda`'s): a head's own part,
    # and no router MLP and no CCA. `num_experts` is the experts held, the
    # name the accepted readers of the grouped matmuls take.
    out.update(head_dim=config["qk_nope_head_dim"], router_hidden_size=0, cca_time1=0)
    return out


_KDA_KERNELS = ("wq", "wk", "wv", "wf_a", "wf_b", "wg_a", "wg_b", "wo")
_DENSE = {"mlp_gate": "wi_gate", "mlp_up": "wi_up", "mlp_down": "wo"}
_SHARED = {"shared_gate": "wi_gate", "shared_up": "wi_up", "shared_down": "wo"}


def _program_path(name: str) -> tuple[str, ...]:
    """Where the program's `TransformerLM` keeps the reference's leaf."""
    if name in ("embedding", "lm_head"):
        return (name,)
    if name == "ln_final":
        return ("ln_final", "scale")
    _, i, leaf = name.split(".")
    if leaf in ("ln_attn", "ln_mlp"):
        sub = (leaf, "scale")
    elif leaf.startswith("kda_"):
        leaf = leaf[len("kda_"):]
        if leaf in _KDA_KERNELS:
            sub = ("kda", leaf, "kernel")
        else:
            sub = ("kda", "norm_scale" if leaf == "norm" else leaf)
    elif leaf == "kv_norm":
        sub = ("attn", leaf, "scale")
    elif leaf in ("wkv_a", "wo"):
        sub = ("attn", leaf, "kernel")
    elif leaf in ("wq", "wkv_b"):
        sub = ("attn", leaf)
    elif leaf in _DENSE:
        sub = ("mlp", _DENSE[leaf], "kernel")
    elif leaf in _SHARED:
        sub = ("moe", "shared", _SHARED[leaf], "kernel")
    else:  # the router's leaves and the experts'
        sub = ("moe", leaf)
    return (f"layer_{i}", *sub)


def transformer_config(numbers: dict, **how):
    """The program's `TransformerConfig` for the configuration's numbers."""
    from kubeflow_tpu.models.transformer import AttentionKind, TransformerConfig

    linear = numbers["linear_attn_config"]
    kinds = (
        AttentionKind(n_heads=linear["num_heads"], mixer="delta"),
        AttentionKind(n_heads=numbers["num_attention_heads"], rope_fraction=0.0),
    )
    layers = numbers["num_hidden_layers"]
    ff = numbers["moe_intermediate_size"]
    return TransformerConfig(
        vocab_size=numbers["vocab_size"], d_model=numbers["hidden_size"],
        n_layers=layers, tie_embeddings=False,
        norm_eps=numbers["rms_norm_eps"],
        n_heads=numbers["num_attention_heads"],
        head_dim=numbers["qk_nope_head_dim"],
        q_latent=0, kv_latent=numbers["kv_lora_rank"],
        rope_head_dim=numbers["qk_rope_head_dim"],
        v_head_dim=numbers["v_head_dim"],
        attention_kinds=kinds,
        attention_pattern=tuple(
            0 if i in linear["kda_layers"] else 1 for i in range(1, layers + 1)
        ),
        ssm_conv=linear["short_conv_kernel_size"], ssm_chunk=KDA_CHUNK,
        dense_layers=numbers["first_k_dense_replace"],
        dense_d_ff=numbers["intermediate_size"], d_ff=ff, mlp_act="swiglu",
        num_experts=numbers["experts_routed"],
        experts_held=(numbers["experts_first"], numbers["num_experts"]),
        experts_per_token=numbers["num_experts_per_token"], router="sigmoid",
        routed_scaling=float(numbers["routed_scaling_factor"]),
        moe_shared_ff=numbers["num_shared_experts"] * ff,
        router_force_balance=numbers.get("router_force_balance", False), **how,
    )


def _as_this_family(function):
    """`function` of `train_moe.py` as it stands, over this family's
    reference: those functions import `benchmarks.reference.zaya` by name
    when they are called and ask of it `init_params`, `param_specs`,
    `init_leaf` and `follow`, which `reference/kimi_linear.py` answers
    under the same names, so for the length of the call that module stands
    in for it. (`kimi_linear` imports `zaya` itself, so it is imported
    first.)"""
    import functools

    @functools.wraps(function)
    def call(*args, **kwargs):
        import benchmarks.reference as package
        from benchmarks.reference import kimi_linear, zaya

        package.zaya = kimi_linear
        try:
            return function(*args, **kwargs)
        finally:
            package.zaya = zaya

    return call


build = _as_this_family(_base.build)
first_steps = _as_this_family(_base.first_steps)
run_reference = _as_this_family(_base.run_reference)


def routed(records: list[dict], numbers: dict, tokens_a_step: int) -> dict:
    """What the counters of some steps' records say: the rows (token-expert
    pairs) a SPARSE layer routed to the experts held here a step
    (`tokens_held_a_layer`, the name the accepted readers take), those
    rows a token (`held_share`), the fullest held expert's load over the
    mean one's, and the mixer's two counters, each its mean over the
    records."""
    layers = numbers["num_hidden_layers"] - numbers["first_k_dense_replace"]
    mean = lambda name: sum(r[name] for r in records) / len(records)
    held = mean("moe_tokens_held") / layers
    return {
        "tokens_held_a_layer": held,
        "held_share": held / tokens_a_step,
        "load_max_over_mean": mean("moe_load_max") / mean("moe_load_mean"),
        "kda_decay_mean": mean("kda_decay_mean"),
        "kda_beta_mean": mean("kda_beta_mean"),
        "records": len(records),
    }


def kernel_schedules(work: dict, numbers: dict) -> dict:
    """(the delta rule's schedule, the two-part flash calls' widths)."""
    from kubeflow_tpu.ops.flash import flash_schedule
    from kubeflow_tpu.ops.kda import kda_schedule

    linear = numbers["linear_attn_config"]
    kda = kda_schedule(
        work["seq_len"], heads=linear["num_heads"], head_dim=linear["head_dim"],
        chunk=KDA_CHUNK, batch=work["batch"],
    )
    sched = flash_schedule(
        work["seq_len"], work["seq_len"], head_dim=numbers["qk_nope_head_dim"],
        rope_dim=numbers["qk_rope_head_dim"],
    )
    keys = (
        "qk_dim", "rope_dim", "v_dim", "layout", "rope_layout", "block_q",
        "grid_steps", "computed_pairs_over_needed", "bwd_fused",
    )
    return {"chunk": KDA_CHUNK, **kda}, {k: sched[k] for k in keys}


for _name in (
    "model_numbers", "_program_path", "transformer_config", "build",
    "first_steps", "run_reference", "routed",
):
    setattr(_base, _name, globals()[_name])


def run(cell: dict, args, clock_start: float, say) -> dict:
    """One run of a train_kda cell: `train_moe.run` over this family's
    glue, then the facts that are this family's own."""
    from benchmarks.lib import flops_kda

    out = _base.run(cell, args, clock_start, say)
    facts, work = out["facts"], cell["workload"]
    parts = flops_kda.flops_by_part(
        facts["numbers"], work["seq_len"], facts["moe"]["held_share"], KDA_CHUNK
    )
    facts["flops_per_token"] = float(sum(parts.values()))
    facts["flops_by_part"] = parts
    facts["kda"], facts["mla"] = kernel_schedules(work, facts["numbers"])
    say("flops", per_token=facts["flops_per_token"], **parts)
    say("kda", **facts["kda"])
    say("mla", **facts["mla"])
    return out
