"""Driver of the `train_gdn` kind: a Qwen3-Next-style decoder (a Gated
DeltaNet mixer, the delta rule with a decay a HEAD over grouped key heads,
in most layers and gated softmax attention with a head's q/k norm, a partial
rope and a gate an output channel in the others, one stack of blocks; every
layer over softmax-routed top-k experts beside a gated shared one; (1 + w)
norms; an untied head) through `Trainer` + `fit()`.

The run is `drivers/train_moe.py`'s, as it stands: a private copy of that
module is loaded and what depends on the family is rebound in it (as
`drivers/train_kda.py` does), so its `run()` — and `limits.py`, which calls
this module's `build`, `first_steps`, `run_reference` and `gaps` — reach this
family's glue: the configuration's keys, the `TransformerConfig` they become,
where the program keeps each of the reference's leaves
(`reference/qwen3_next.py`), the routing counters in rows a layer, the
mixers' and the gates' counters, FLOPs by `lib/flops_gdn.py` and the kernels'
schedules among the `facts`.
"""

from __future__ import annotations

import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "bench_driver_train_gdn_base",
    pathlib.Path(__file__).with_name("train_moe.py"),
)
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)

WORKLOAD_REQUIRED, WORKLOAD_KEYS = _base.WORKLOAD_REQUIRED, _base.WORKLOAD_KEYS
CONFIG_REQUIRED = {
    "decoder_sparse_step", "full_attention_interval", "head_dim", "hidden_act",
    "hidden_size", "linear_conv_kernel_dim", "linear_key_head_dim",
    "linear_num_key_heads", "linear_num_value_heads", "linear_value_head_dim",
    "mlp_only_layers", "moe_intermediate_size", "norm_topk_prob",
    "num_attention_heads", "num_experts", "num_experts_per_tok",
    "num_hidden_layers", "num_key_value_heads", "partial_rotary_factor",
    "rms_norm_eps", "rope_scaling", "rope_theta",
    "shared_expert_intermediate_size", "tie_word_embeddings",
    "use_sliding_window", "vocab_size", "experts_routed", "experts_first",
}
CONFIG_KEYS = CONFIG_REQUIRED | {
    "model_type", "intermediate_size", "max_position_embeddings",
}
# Positions a chunk of the delta rule: the program's choice, no published
# key (`ops/kda.py`; 64 chunks a sequence of 8,192). A head's decay needs no
# sub-blocks, so a chunk twice the channel form's costs no clipped exponent;
# the kernels alone at the cell's shape took 2.99 ms forward and 7.45 both
# ways a layer at 64 and 2.25 and 5.99 at 128 (PERF.md §6, PR 45).
GDN_CHUNK = 128
# `fit()` writes every counter the model sows into its records; the mixers
# and the two gates sow four, which `first_steps` then reports too.
MIXER_COUNTERS = (
    "kda_decay_mean", "kda_beta_mean", "attn_gate_mean", "shared_gate_mean",
)
COUNTERS = _base.COUNTERS = (*_base.COUNTERS, *MIXER_COUNTERS)
gaps, compare = _base.gaps, _base.compare
# Both walk `_program_path`, which is rebound below.
to_program_tree, from_program_tree = _base.to_program_tree, _base.from_program_tree


def preload() -> None:
    """The program's imports, made while the chip is still being reached;
    a program without the delta rule's decay-a-head form fails here, at
    once."""
    _base.preload()
    import dataclasses

    import kubeflow_tpu.models.transformer as model
    import kubeflow_tpu.ops.kda as kda

    rows = {f.name for f in dataclasses.fields(model.AttentionKind)}
    stack = {f.name for f in dataclasses.fields(model.TransformerConfig)}
    missing = sorted(
        ({"mixer", "key_heads", "head_dim", "decay", "gate_act"} - rows)
        | ({"qk_norm", "norm_unit_offset", "moe_shared_gate"} - stack)
    )
    if missing or not hasattr(kda, "gdn_chunked"):
        raise ImportError(
            "the program's decoder has no delta rule with a decay a head over "
            f"grouped key heads (no `ops.kda.gdn_chunked`; fields {missing} "
            "of `AttentionKind` / `TransformerConfig` are missing)"
        )


def model_numbers(config: dict) -> dict:
    """The configuration's keys, checked for what the program's decoder
    can express."""
    if config["tie_word_embeddings"] or config["hidden_act"] != "silu":
        raise ValueError("an untied head and silu are built")
    if config["decoder_sparse_step"] != 1 or config["mlp_only_layers"]:
        raise ValueError("experts in every layer are built")
    if config["use_sliding_window"] or config["rope_scaling"] is not None:
        raise ValueError("full attention under a plain rope is built")
    if config["linear_key_head_dim"] != config["linear_value_head_dim"]:
        raise ValueError("the delta rule's state is square: one head width")
    if config["linear_num_value_heads"] % config["linear_num_key_heads"]:
        raise ValueError("the key heads divide the value heads")
    if config["num_attention_heads"] % config["num_key_value_heads"]:
        raise ValueError("the K/V heads divide the query heads")
    if not config["norm_topk_prob"]:
        raise ValueError("the chosen experts' weights are normalised")
    if not 0 < config["full_attention_interval"] <= config["num_hidden_layers"]:
        raise ValueError("at least one whole period of the layers is built")
    if config["experts_first"] + config["num_experts"] > config["experts_routed"]:
        raise ValueError("the experts held are not a range of those routed")
    out = {k: config[k] for k in CONFIG_REQUIRED}
    # What `train_moe.run`'s own FLOP count asks for under its names (`run`
    # below replaces the count with `lib/flops_gdn`'s): no router MLP and no
    # CCA. `num_experts` is the experts held, the name the accepted readers
    # of the grouped matmuls take; `head_dim` and `num_attention_heads` are
    # the attention layer's, as published, which the flash readers take.
    out.update(router_hidden_size=0, cca_time1=0)
    return out


_KDA_KERNELS = ("wq", "wk", "wv", "wg", "wo")
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
    elif leaf in ("wk", "wv", "wo"):
        sub = ("attn", leaf, "kernel")
    elif leaf in ("wq", "q_norm", "k_norm"):  # q and its gate: one matrix
        sub = ("attn", leaf)
    elif leaf in _SHARED:
        sub = ("moe", "shared", _SHARED[leaf], "kernel")
    elif leaf == "shared_expert_gate":
        sub = ("moe", "shared_gate")
    else:  # the router's leaf and the experts'
        sub = ("moe", leaf)
    return (f"layer_{i}", *sub)


def transformer_config(numbers: dict, **how):
    """The program's `TransformerConfig` for the configuration's numbers."""
    from kubeflow_tpu.models.transformer import AttentionKind, TransformerConfig

    kinds = (
        AttentionKind(
            n_heads=numbers["linear_num_value_heads"], mixer="delta",
            key_heads=numbers["linear_num_key_heads"],
            head_dim=numbers["linear_key_head_dim"], decay="head",
            gate_act="silu",
        ),
        AttentionKind(
            n_heads=numbers["num_attention_heads"],
            rope_theta=float(numbers["rope_theta"]),
            rope_fraction=numbers["partial_rotary_factor"],
        ),
    )
    layers, every = numbers["num_hidden_layers"], numbers["full_attention_interval"]
    return TransformerConfig(
        vocab_size=numbers["vocab_size"], d_model=numbers["hidden_size"],
        n_layers=layers, tie_embeddings=False,
        norm_eps=numbers["rms_norm_eps"], norm_unit_offset=True,
        n_heads=numbers["num_attention_heads"],
        n_kv_heads=numbers["num_key_value_heads"], head_dim=numbers["head_dim"],
        qk_norm=True, attention_gate="channel",
        attention_kinds=kinds,
        attention_pattern=tuple(
            1 if (i + 1) % every == 0 else 0 for i in range(layers)
        ),
        ssm_conv=numbers["linear_conv_kernel_dim"], ssm_chunk=GDN_CHUNK,
        d_ff=numbers["moe_intermediate_size"], mlp_act="swiglu",
        num_experts=numbers["experts_routed"],
        experts_held=(numbers["experts_first"], numbers["num_experts"]),
        experts_per_token=numbers["num_experts_per_tok"], router="softmax",
        moe_shared_ff=numbers["shared_expert_intermediate_size"],
        moe_shared_gate=True,
        router_force_balance=numbers.get("router_force_balance", False), **how,
    )


def _as_this_family(function):
    """`function` of `train_moe.py` as it stands, over this family's
    reference: those functions import `benchmarks.reference.zaya` by name
    when they are called and ask of it `init_params`, `param_specs`,
    `init_leaf` and `follow`, which `reference/qwen3_next.py` answers under
    the same names, so for the length of the call that module stands in for
    it. (`qwen3_next` imports `zaya` itself, so it is imported first.)"""
    import functools

    @functools.wraps(function)
    def call(*args, **kwargs):
        import benchmarks.reference as package
        from benchmarks.reference import qwen3_next, zaya

        package.zaya = qwen3_next
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
    pairs) a layer routed to the experts held here a step
    (`tokens_held_a_layer`, the name the accepted readers take), those
    rows a token (`held_share`), the fullest held expert's load over the
    mean one's, and the mixers' and the gates' counters, each its mean
    over the records."""
    mean = lambda name: sum(r[name] for r in records) / len(records)
    held = mean("moe_tokens_held") / numbers["num_hidden_layers"]
    return {
        "tokens_held_a_layer": held,
        "held_share": held / tokens_a_step,
        "load_max_over_mean": mean("moe_load_max") / mean("moe_load_mean"),
        **{name: mean(name) for name in MIXER_COUNTERS},
        "records": len(records),
    }


def kernel_schedules(work: dict, numbers: dict) -> dict:
    """(the delta rule's schedule, the d = 256 flash calls')."""
    from kubeflow_tpu.ops.flash import flash_schedule
    from kubeflow_tpu.ops.kda import kda_schedule

    gdn = kda_schedule(
        work["seq_len"], heads=numbers["linear_num_value_heads"],
        key_heads=numbers["linear_num_key_heads"],
        head_dim=numbers["linear_key_head_dim"], chunk=GDN_CHUNK,
        batch=work["batch"],
    )
    sched = flash_schedule(
        work["seq_len"], work["seq_len"], head_dim=numbers["head_dim"]
    )
    keys = (
        "qk_dim", "v_dim", "layout", "block_q", "block_k", "grid_steps",
        "computed_pairs_over_needed", "bwd_fused", "bwd_fused_vmem_bytes",
    )
    return {"chunk": GDN_CHUNK, **gdn}, {k: sched[k] for k in keys}


for _name in (
    "model_numbers", "_program_path", "transformer_config", "build",
    "first_steps", "run_reference", "routed",
):
    setattr(_base, _name, globals()[_name])


def run(cell: dict, args, clock_start: float, say) -> dict:
    """One run of a train_gdn cell: `train_moe.run` over this family's
    glue, then the facts that are this family's own."""
    from benchmarks.lib import flops_gdn

    out = _base.run(cell, args, clock_start, say)
    facts, work = out["facts"], cell["workload"]
    parts = flops_gdn.flops_by_part(
        facts["numbers"], work["seq_len"], facts["moe"]["held_share"], GDN_CHUNK
    )
    facts["flops_per_token"] = float(sum(parts.values()))
    facts["flops_by_part"] = parts
    facts["gdn"], facts["flash"] = kernel_schedules(work, facts["numbers"])
    say("flops", per_token=facts["flops_per_token"], **parts)
    say("gdn", **facts["gdn"])
    say("flash", **facts["flash"])
    return out
