"""Driver of the `train_window` kind: a Laguna-style decoder (global and
window attention layers in one stack, a sigmoid gate a query head, yarn on
the global layers, a leading dense layer, then gated top-k experts beside a
shared one; an untied head) through `Trainer` + `fit()`.

The run is `drivers/train_moe.py`'s, as it stands: a private copy of that
module is loaded and what depends on the family is rebound in it, so its
`run()` — and `limits.py`, which calls this module's `build`,
`first_steps`, `run_reference` and `gaps` — reach this family's glue: the
configuration's keys, the `TransformerConfig` they become, where the
program keeps each of the reference's leaves (`reference/laguna.py`), the
routing counters in rows over the SPARSE layers, FLOPs that count a window
layer's attention by its band (`lib/flops_window.py`) and the band's
schedule among the `facts`.
"""

from __future__ import annotations

import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "bench_driver_train_window_base",
    pathlib.Path(__file__).with_name("train_moe.py"),
)
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)

WORKLOAD_REQUIRED, WORKLOAD_KEYS = _base.WORKLOAD_REQUIRED, _base.WORKLOAD_KEYS
CONFIG_REQUIRED = {
    "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim", "vocab_size",
    "rms_norm_eps", "num_experts", "num_experts_per_tok",
    "moe_intermediate_size", "shared_expert_intermediate_size",
    "norm_topk_prob", "moe_routed_scaling_factor", "mlp_only_layers",
    "tie_word_embeddings", "gating", "sliding_window", "rope_parameters",
    "layer_types", "mlp_layer_types", "num_attention_heads_per_layer",
    "experts_routed", "experts_first",
}
CONFIG_KEYS = CONFIG_REQUIRED | {
    "model_type", "max_position_embeddings", "attention_bias",
    "decoder_sparse_step", "moe_apply_router_weight_on_input", "gating_types",
    "moe_router_logit_softcapping",
}
# `fit()` writes every counter the model sows into its records; this
# family's gate sows one more, which `first_steps` then reports too.
COUNTERS = _base.COUNTERS = (*_base.COUNTERS, "attn_gate_mean")
gaps, compare = _base.gaps, _base.compare
# Both walk `_program_path`, which is rebound below.
to_program_tree, from_program_tree = _base.to_program_tree, _base.from_program_tree


def preload() -> None:
    """The program's imports, made while the chip is still being reached;
    a program whose stack cannot mix attention kinds fails here, at once."""
    _base.preload()
    import kubeflow_tpu.models.transformer as model

    if not hasattr(model, "AttentionKind"):
        raise ImportError("the program's stack has no attention kinds by layer")


def model_numbers(config: dict) -> dict:
    """The configuration's keys, checked for what the program's decoder
    can express."""
    n = config["num_hidden_layers"]
    for key in ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer"):
        if len(config[key]) != n:
            raise ValueError(f"{key} names {len(config[key])} layers of {n}")
    if set(config["layer_types"]) - {"full_attention", "sliding_attention"}:
        raise ValueError("full_attention and sliding_attention layers are built")
    dense = [i for i, k in enumerate(config["mlp_layer_types"]) if k == "dense"]
    if dense != list(range(len(dense))) or dense != list(config["mlp_only_layers"]):
        raise ValueError(
            f"dense layers {dense} (mlp_only_layers {config['mlp_only_layers']}):"
            " only leading dense layers are built"
        )
    if set(config["mlp_layer_types"]) - {"dense", "sparse"}:
        raise ValueError("dense and sparse feed-forward halves are built")
    if config["tie_word_embeddings"] or config.get("attention_bias"):
        raise ValueError("an untied head and no biases are built")
    if config["gating"] != "per-head" or set(
        config.get("gating_types", ["per_head"])
    ) != {"per_head"}:
        raise ValueError("a gate a query head is built, in every layer")
    if not config["norm_topk_prob"] or config.get("moe_router_logit_softcapping"):
        raise ValueError("normalised weights and no soft cap are built")
    if config.get("moe_apply_router_weight_on_input"):
        raise ValueError("the weights multiply the experts' outputs")
    if config["experts_first"] + config["num_experts"] > config["experts_routed"]:
        raise ValueError("the experts held are not a range of those routed")
    for kind, rope in config["rope_parameters"].items():
        if rope["rope_type"] not in ("default", "yarn"):
            raise ValueError(f"{kind}: rope_type {rope['rope_type']!r}")
    out = {k: config[k] for k in CONFIG_REQUIRED}
    # This family has neither a router MLP nor CCA's convolutions: the two
    # widths `train_moe.run`'s own FLOP count reads (`run` below replaces
    # that count with `lib/flops_window`'s).
    out.update(router_hidden_size=0, cca_time1=0)
    return out


_ATTN = ("wq", "wk", "wv", "wo")
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
    elif leaf in _ATTN:
        sub = ("attn", leaf, "kernel")
    elif leaf == "wg":
        sub = ("attn", "wg")
    elif leaf in _DENSE:
        sub = ("mlp", _DENSE[leaf], "kernel")
    elif leaf in _SHARED:
        sub = ("moe", "shared", _SHARED[leaf], "kernel")
    else:  # the router's leaves and the experts'
        sub = ("moe", leaf)
    return (f"layer_{i}", *sub)


def attention_kinds(numbers: dict):
    """(the table of attention kinds, which kind each layer is) for the
    program's `TransformerConfig`, from the configuration's lists."""
    from kubeflow_tpu.models.transformer import AttentionKind

    table, pattern = [], []
    for kind, heads in zip(
        numbers["layer_types"], numbers["num_attention_heads_per_layer"]
    ):
        rope = numbers["rope_parameters"][kind]
        yarn = None
        if rope["rope_type"] == "yarn":
            yarn = (
                float(rope["factor"]),
                int(rope["original_max_position_embeddings"]),
                float(rope["beta_fast"]), float(rope["beta_slow"]),
                float(rope["attention_factor"]),
            )
        entry = AttentionKind(
            n_heads=heads,
            window=numbers["sliding_window"] if kind == "sliding_attention" else None,
            rope_theta=float(rope["rope_theta"]),
            rope_fraction=float(rope.get("partial_rotary_factor", 1.0)),
            rope_yarn=yarn,
        )
        if entry not in table:
            table.append(entry)
        pattern.append(table.index(entry))
    return tuple(table), tuple(pattern)


def transformer_config(numbers: dict, **how):
    """The program's `TransformerConfig` for the configuration's numbers."""
    from kubeflow_tpu.models.transformer import TransformerConfig

    kinds, pattern = attention_kinds(numbers)
    return TransformerConfig(
        vocab_size=numbers["vocab_size"], d_model=numbers["hidden_size"],
        n_layers=numbers["num_hidden_layers"], tie_embeddings=False,
        norm_eps=numbers["rms_norm_eps"],
        n_heads=numbers["num_attention_heads"],
        n_kv_heads=numbers["num_key_value_heads"], head_dim=numbers["head_dim"],
        attention_kinds=kinds, attention_pattern=pattern, attention_gate=True,
        dense_layers=len(numbers["mlp_only_layers"]),
        dense_d_ff=numbers["intermediate_size"],
        d_ff=numbers["moe_intermediate_size"], mlp_act="swiglu",
        num_experts=numbers["experts_routed"],
        experts_held=(numbers["experts_first"], numbers["num_experts"]),
        experts_per_token=numbers["num_experts_per_tok"], router="sigmoid",
        routed_scaling=float(numbers["moe_routed_scaling_factor"]),
        moe_shared_ff=numbers["shared_expert_intermediate_size"],
        router_force_balance=numbers.get("router_force_balance", False), **how,
    )


def _as_this_family(function):
    """`function` of `train_moe.py` as it stands, over this family's
    reference: those functions import `benchmarks.reference.zaya` by name
    when they are called and ask of it `init_params`, `param_specs`,
    `init_leaf` and `follow`, which `reference/laguna.py` answers under
    the same names, so for the length of the call that module stands in
    for it. (`laguna` imports `zaya` itself, so it is imported first.)"""
    import functools

    @functools.wraps(function)
    def call(*args, **kwargs):
        import benchmarks.reference as package
        from benchmarks.reference import laguna, zaya

        package.zaya = laguna
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
    rows a token (`held_share`), and the fullest held expert's load over
    the mean one's."""
    layers = numbers["mlp_layer_types"].count("sparse")
    held = sum(r["moe_tokens_held"] for r in records) / len(records) / layers
    return {
        "tokens_held_a_layer": held,
        "held_share": held / tokens_a_step,
        "load_max_over_mean": sum(r["moe_load_max"] for r in records)
        / sum(r["moe_load_mean"] for r in records),
        "records": len(records),
    }


def window_schedule(work: dict, numbers: dict) -> dict:
    """The band the window layers' kernels walk, by `flash_schedule`."""
    from kubeflow_tpu.ops.flash import flash_schedule

    sched = flash_schedule(
        work["seq_len"], work["seq_len"], head_dim=numbers["head_dim"],
        window=numbers["sliding_window"],
    )
    keys = (
        "window", "block_q", "grid_steps", "band_steps", "diag_steps",
        "edge_steps", "interior_steps", "diag_tile",
        "computed_pairs_over_needed", "bwd_fused", "bwd_fused_vmem_bytes",
    )
    return {k: sched[k] for k in keys}


for _name in (
    "model_numbers", "_program_path", "transformer_config", "build",
    "first_steps", "run_reference", "routed",
):
    setattr(_base, _name, globals()[_name])


def run(cell: dict, args, clock_start: float, say) -> dict:
    """One run of a train_window cell: `train_moe.run` over this family's
    glue, then the facts that are this family's own."""
    from benchmarks.lib import flops_window

    out = _base.run(cell, args, clock_start, say)
    facts, work = out["facts"], cell["workload"]
    parts = flops_window.flops_by_part(
        facts["numbers"], work["seq_len"], facts["moe"]["held_share"]
    )
    facts["flops_per_token"] = float(sum(parts.values()))
    facts["flops_by_part"] = parts
    facts["window"] = window_schedule(work, facts["numbers"])
    say("flops", per_token=facts["flops_per_token"], **parts)
    say("band", **facts["window"])
    return out
