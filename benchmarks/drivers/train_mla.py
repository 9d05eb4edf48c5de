"""Driver of the `train_mla` kind: a Xing4.0-style decoder (latent attention
with two-part scores and one rope key for all heads, several residual
streams mixed by Sinkhorn-normalised maps round every sublayer, leading
dense layers, then gated top-k experts beside a shared one; an untied head)
through `Trainer` + `fit()`.

The run is `drivers/train_moe.py`'s, as it stands: a private copy of that
module is loaded and what depends on the family is rebound in it (as
`drivers/train_window.py` does), so its `run()` — and `limits.py`, which
calls this module's `build`, `first_steps`, `run_reference` and `gaps` —
reach this family's glue: the configuration's keys, the `TransformerConfig`
they become, where the program keeps each of the reference's leaves
(`reference/xing.py`), the routing counters in rows over the SPARSE layers,
the streams' counters, FLOPs by `lib/flops_mla.py` and the kernels' widths
among the `facts`.
"""

from __future__ import annotations

import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "bench_driver_train_mla_base",
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
    "first_k_dense_replace", "n_routed_experts", "n_shared_experts",
    "num_experts_per_tok", "moe_intermediate_size", "routed_scaling_factor",
    "norm_topk_prob", "scoring_func", "tie_word_embeddings", "hc_mult",
    "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
    "mhc_h_res_clamp_max", "num_nextn_predict_layers", "experts_routed",
    "experts_first",
}
CONFIG_KEYS = CONFIG_REQUIRED | {
    "model_type", "attention_bias", "ep_size", "hidden_act",
    "max_position_embeddings", "moe_layer_freq", "n_group", "topk_group",
    "topk_method",
}
# `fit()` writes every counter the model sows into its records; the
# streams' maps sow two more, which `first_steps` then reports too.
COUNTERS = _base.COUNTERS = (
    *_base.COUNTERS, "hc_sinkhorn_err", "hc_res_diag_mean",
)
gaps, compare = _base.gaps, _base.compare
# Both walk `_program_path`, which is rebound below.
to_program_tree, from_program_tree = _base.to_program_tree, _base.from_program_tree


def preload() -> None:
    """The program's imports, made while the chip is still being reached;
    a program with no latent attention or no residual streams fails here,
    at once."""
    _base.preload()
    import dataclasses

    import kubeflow_tpu.models.transformer as model

    fields = {f.name for f in dataclasses.fields(model.TransformerConfig)}
    if not {"kv_latent", "residual_streams"} <= fields:
        raise ImportError(
            "the program's decoder has no latent attention or no residual "
            "streams"
        )


def model_numbers(config: dict) -> dict:
    """The configuration's keys, checked for what the program's decoder
    can express."""
    if config["tie_word_embeddings"] or config.get("attention_bias"):
        raise ValueError("an untied head and no biases are built")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("latent attention has as many K/V heads as query heads")
    if config["v_head_dim"] != config["qk_nope_head_dim"]:
        raise ValueError("the kernels want v as wide as q's and k's own part")
    if config["scoring_func"] != "sigmoid" or not config["norm_topk_prob"]:
        raise ValueError("sigmoid scores and normalised weights are built")
    if config.get("n_group", 1) != 1 or config.get("topk_group", 1) != 1:
        raise ValueError("one group of experts is built")
    if config.get("moe_layer_freq", 1) != 1 or config.get("hidden_act", "silu") != "silu":
        raise ValueError("experts in every layer after the dense ones, silu")
    if config["num_nextn_predict_layers"]:
        raise ValueError("the multi-token module is not built")
    if config["rope_scaling"]["type"] != "yarn":
        raise ValueError("yarn is built")
    if config["mhc_h_res_clamp_min"] != -config["mhc_h_res_clamp_max"]:
        raise ValueError("a clamp of +-c is built")
    if not 0 < config["first_k_dense_replace"] <= config["num_hidden_layers"]:
        raise ValueError("leading dense layers, then sparse ones, are built")
    if config["experts_first"] + config["n_routed_experts"] > config["experts_routed"]:
        raise ValueError("the experts held are not a range of those routed")
    out = {k: config[k] for k in CONFIG_REQUIRED}
    # What `train_moe.run`'s own FLOP count and the accepted readers of the
    # grouped matmuls ask for under their names (`run` below replaces the
    # count with `lib/flops_mla`'s): the experts held, a head's own part,
    # and no router MLP and no CCA.
    out.update(
        num_experts=config["n_routed_experts"],
        head_dim=config["qk_nope_head_dim"], router_hidden_size=0, cca_time1=0,
    )
    return out


_ATTN = ("wq_a", "wkv_a", "wo")
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
    elif leaf in ("q_norm", "kv_norm"):
        sub = ("attn", leaf, "scale")
    elif leaf in _ATTN:
        sub = ("attn", leaf, "kernel")
    elif leaf in ("wq_b", "wkv_b"):
        sub = ("attn", leaf)
    elif leaf.startswith("hc_"):  # hc_<sublayer>_<phi | b | a>
        module, _, which = leaf.rpartition("_")
        sub = (module, which)
    elif leaf in _DENSE:
        sub = ("mlp", _DENSE[leaf], "kernel")
    elif leaf in _SHARED:
        sub = ("moe", "shared", _SHARED[leaf], "kernel")
    else:  # the router's leaves and the experts'
        sub = ("moe", leaf)
    return (f"layer_{i}", *sub)


def mscale(factor: float, m: float) -> float:
    import math

    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def transformer_config(numbers: dict, **how):
    """The program's `TransformerConfig` for the configuration's numbers."""
    from kubeflow_tpu.models.transformer import AttentionKind, TransformerConfig

    yarn = numbers["rope_scaling"]
    all_dims = mscale(yarn["factor"], yarn["mscale_all_dim"])
    kind = AttentionKind(
        n_heads=numbers["num_attention_heads"], window=None,
        rope_theta=float(numbers["rope_theta"]), rope_fraction=1.0,
        rope_yarn=(
            float(yarn["factor"]), int(yarn["original_max_position_embeddings"]),
            float(yarn["beta_fast"]), float(yarn["beta_slow"]),
            mscale(yarn["factor"], yarn["mscale"]) / all_dims,
        ),
    )
    width = numbers["qk_nope_head_dim"] + numbers["qk_rope_head_dim"]
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
        softmax_scale=width ** -0.5 * all_dims ** 2,
        attention_kinds=(kind,),
        attention_pattern=(0,) * numbers["num_hidden_layers"],
        residual_streams=numbers["hc_mult"],
        hc_iters=numbers["hc_sinkhorn_iters"], hc_eps=numbers["hc_eps"],
        hc_clamp=float(numbers["mhc_h_res_clamp_max"]),
        dense_layers=numbers["first_k_dense_replace"],
        dense_d_ff=numbers["intermediate_size"], d_ff=ff, mlp_act="swiglu",
        num_experts=numbers["experts_routed"],
        experts_held=(numbers["experts_first"], numbers["n_routed_experts"]),
        experts_per_token=numbers["num_experts_per_tok"], router="sigmoid",
        routed_scaling=float(numbers["routed_scaling_factor"]),
        moe_shared_ff=numbers["n_shared_experts"] * ff,
        router_force_balance=numbers.get("router_force_balance", False), **how,
    )


def _as_this_family(function):
    """`function` of `train_moe.py` as it stands, over this family's
    reference: those functions import `benchmarks.reference.zaya` by name
    when they are called and ask of it `init_params`, `param_specs`,
    `init_leaf` and `follow`, which `reference/xing.py` answers under the
    same names, so for the length of the call that module stands in for
    it. (`xing` imports `zaya` itself, so it is imported first.)"""
    import functools

    @functools.wraps(function)
    def call(*args, **kwargs):
        import benchmarks.reference as package
        from benchmarks.reference import xing, zaya

        package.zaya = xing
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
    mean one's, and the streams' two counters, each its mean over the
    records."""
    layers = numbers["num_hidden_layers"] - numbers["first_k_dense_replace"]
    mean = lambda name: sum(r[name] for r in records) / len(records)
    held = mean("moe_tokens_held") / layers
    return {
        "tokens_held_a_layer": held,
        "held_share": held / tokens_a_step,
        "load_max_over_mean": mean("moe_load_max") / mean("moe_load_mean"),
        "hc_sinkhorn_err": mean("hc_sinkhorn_err"),
        "hc_res_diag_mean": mean("hc_res_diag_mean"),
        "records": len(records),
    }


def kernel_widths(work: dict, numbers: dict) -> dict:
    """The widths and the schedule of the two-part calls, by
    `flash_schedule`."""
    from kubeflow_tpu.ops.flash import flash_schedule

    sched = flash_schedule(
        work["seq_len"], work["seq_len"], head_dim=numbers["qk_nope_head_dim"],
        rope_dim=numbers["qk_rope_head_dim"],
    )
    keys = (
        "qk_dim", "rope_dim", "v_dim", "layout", "rope_layout", "block_q",
        "grid_steps", "computed_pairs_over_needed", "bwd_fused",
        "bwd_fused_vmem_bytes",
    )
    return {k: sched[k] for k in keys}


for _name in (
    "model_numbers", "_program_path", "transformer_config", "build",
    "first_steps", "run_reference", "routed",
):
    setattr(_base, _name, globals()[_name])


def run(cell: dict, args, clock_start: float, say) -> dict:
    """One run of a train_mla cell: `train_moe.run` over this family's
    glue, then the facts that are this family's own."""
    from benchmarks.lib import flops_mla

    out = _base.run(cell, args, clock_start, say)
    facts, work = out["facts"], cell["workload"]
    parts = flops_mla.flops_by_part(
        facts["numbers"], work["seq_len"], facts["moe"]["held_share"]
    )
    facts["flops_per_token"] = float(sum(parts.values()))
    facts["flops_by_part"] = parts
    facts["mla"] = kernel_widths(work, facts["numbers"])
    say("flops", per_token=facts["flops_per_token"], **parts)
    say("mla", **facts["mla"])
    return out
