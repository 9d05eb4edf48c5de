"""Driver of the `train_hybrid` kind: a Nemotron-H-style decoder (a stack of
single sublayers by `hybrid_override_pattern`: Mamba-2 mixers, latent top-k
expert layers beside a shared expert, attention without rotation; an untied
head) through `Trainer` + `fit()`.

The same run as `drivers/train_moe.py` makes — one trainer and one seeded
state through steps 1-3 (and a fourth, untimed and uncompared) in set-up
and on into the window, the plain reference after it, the same four numbers
compared — with this family's glue: the configuration's keys, the
`TransformerConfig` they become, where the program keeps each of the
reference's leaves (`reference/nemotron_h.py`), the routing counters
`fit()` reports (rows, token-expert pairs, an EXPERT layer), and FLOPs that
follow the rows really routed here (`lib/flops_hybrid.py`). What does not
depend on the family is imported from `drivers/train.py`.
"""

from __future__ import annotations

import gc
import math
import time

from benchmarks.drivers.train import (  # noqa: F401  (`gaps`: limits.py)
    ADAM_B1, LIMIT_KEYS, TRACE_AFTER_S, TRACE_S, _TimedTrace, compare, gaps,
)

WORKLOAD_REQUIRED = {"batch", "seq_len", "mesh", "remat", "optimizer", "limits"}
WORKLOAD_KEYS = WORKLOAD_REQUIRED | {
    "attention_impl", "expect_kernels", "reference_rows_per_block",
    "router_force_balance",
}
CONFIG_REQUIRED = {
    "hidden_size", "num_hidden_layers", "hybrid_override_pattern",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "mamba_num_heads", "mamba_head_dim", "ssm_state_size", "n_groups",
    "conv_kernel", "chunk_size", "time_step_min", "time_step_max",
    "time_step_floor", "n_routed_experts", "num_experts_per_tok",
    "routed_scaling_factor", "norm_topk_prob", "moe_latent_size",
    "moe_intermediate_size", "moe_shared_expert_intermediate_size",
    "n_shared_experts", "vocab_size", "layer_norm_epsilon",
    "tie_word_embeddings", "mlp_hidden_act", "mamba_hidden_act",
    "experts_routed", "experts_first",
}
CONFIG_KEYS = CONFIG_REQUIRED | {
    "model_type", "attention_bias", "expand", "intermediate_size",
    "mamba_proj_bias", "max_position_embeddings", "mlp_bias",
    "moe_shared_expert_overlap", "mtp_hybrid_override_pattern", "n_group",
    "norm_eps", "num_logits_to_keep", "num_nextn_predict_layers",
    "partial_rotary_factor", "rescale_prenorm_residual", "residual_in_fp32",
    "rope_theta", "sliding_window", "topk_group", "use_bias", "use_conv_bias",
    "use_mamba_kernels",
}
COUNTERS = ("moe_tokens_held", "moe_load_max", "moe_load_mean")


def preload() -> None:
    """The program's imports, made while the chip is still being reached;
    a program without the scan or a pattern of layer kinds fails here, at
    once."""
    import kubeflow_tpu.models.transformer as model
    import kubeflow_tpu.ops.moe  # noqa: F401
    import kubeflow_tpu.ops.ssd  # noqa: F401
    import kubeflow_tpu.parallel  # noqa: F401
    import kubeflow_tpu.testing.hlo  # noqa: F401
    import kubeflow_tpu.train  # noqa: F401

    if not hasattr(model, "Sublayer"):
        raise ImportError("the program's stack has no pattern of layer kinds")


def model_numbers(config: dict) -> dict:
    """The configuration's keys, checked for what the program's decoder
    can express."""
    pattern = config["hybrid_override_pattern"]
    if set(pattern) - set("ME*") or len(pattern) != config["num_hidden_layers"]:
        raise ValueError(
            f"pattern {pattern!r}: {config['num_hidden_layers']} layers of "
            "M, E and * are built"
        )
    if config["tie_word_embeddings"]:
        raise ValueError("this family's head is untied")
    if config["mlp_hidden_act"] != "relu2" or config["mamba_hidden_act"] != "silu":
        raise ValueError("relu2 experts and a silu mixer are built")
    for key in ("attention_bias", "mlp_bias", "mamba_proj_bias", "use_bias"):
        if config.get(key):
            raise ValueError(f"{key}: the program's layers have no biases")
    if not config.get("use_conv_bias", True):
        raise ValueError("the mixer's convolution has a bias")
    if config.get("n_group", 1) != 1 or config.get("topk_group", 1) != 1:
        raise ValueError("the router has no group-limited choice")
    if not config["norm_topk_prob"] or config["n_shared_experts"] != 1:
        raise ValueError("normalised weights and one shared expert are built")
    if config.get("num_nextn_predict_layers") or config.get("sliding_window"):
        raise ValueError("no multi-token module and no window are built")
    if config["mamba_num_heads"] % config["n_groups"]:
        raise ValueError("the state-space heads do not divide into groups")
    if (
        config["experts_first"] + config["n_routed_experts"]
        > config["experts_routed"]
    ):
        raise ValueError("the experts held are not a range of those routed")
    return {k: config[k] for k in CONFIG_REQUIRED}


_KERNELS = {  # leaves the program keeps as a Dense's `kernel`
    "in_proj": "ssm", "out_proj": "ssm", "latent_in": "moe",
    "latent_out": "moe", "wq": "attn", "wk": "attn", "wv": "attn",
    "wo": "attn",
}
_SSM_LEAVES = (
    "conv_kernel", "conv_bias", "dt_bias", "A_log", "D", "norm_scale",
)


def _program_path(name: str) -> tuple[str, ...]:
    """Where the program's `TransformerLM` keeps the reference's leaf."""
    if name in ("embedding", "lm_head"):
        return (name,)
    if name == "ln_final":
        return ("ln_final", "scale")
    _, i, leaf = name.split(".")
    if leaf == "ln":
        sub = ("ln", "scale")
    elif leaf in _KERNELS:
        sub = (_KERNELS[leaf], leaf, "kernel")
    elif leaf in ("shared_in", "shared_out"):
        sub = ("moe", "shared", "wi" if leaf == "shared_in" else "wo", "kernel")
    elif leaf in _SSM_LEAVES:
        sub = ("ssm", leaf)
    else:  # the router's leaves and the experts'
        sub = ("moe", leaf)
    return (f"layer_{i}", *sub)


def to_program_tree(flat: dict) -> dict:
    tree: dict = {}
    for name, leaf in flat.items():
        node = tree
        *parents, last = _program_path(name)
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


def from_program_tree(tree, names) -> dict:
    out = {}
    for name in names:
        node = tree
        for p in _program_path(name):
            node = node[p]
        out[name] = node
    return out


def transformer_config(numbers: dict, **how):
    """The program's `TransformerConfig` for the configuration's numbers."""
    from kubeflow_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=numbers["vocab_size"], d_model=numbers["hidden_size"],
        n_layers=numbers["num_hidden_layers"],
        layer_pattern=numbers["hybrid_override_pattern"],
        tie_embeddings=False, norm_eps=numbers["layer_norm_epsilon"],
        n_heads=numbers["num_attention_heads"],
        n_kv_heads=numbers["num_key_value_heads"], head_dim=numbers["head_dim"],
        rope_fraction=0.0,
        ssm_heads=numbers["mamba_num_heads"],
        ssm_head_dim=numbers["mamba_head_dim"],
        ssm_state=numbers["ssm_state_size"], ssm_groups=numbers["n_groups"],
        ssm_conv=numbers["conv_kernel"], ssm_chunk=numbers["chunk_size"],
        ssm_dt=(numbers["time_step_min"], numbers["time_step_max"],
                numbers["time_step_floor"]),
        d_ff=numbers["moe_intermediate_size"], mlp_act="relu2",
        num_experts=numbers["experts_routed"],
        experts_held=(numbers["experts_first"], numbers["n_routed_experts"]),
        experts_per_token=numbers["num_experts_per_tok"], router="sigmoid",
        routed_scaling=float(numbers["routed_scaling_factor"]),
        moe_latent=numbers["moe_latent_size"],
        moe_shared_ff=numbers["moe_shared_expert_intermediate_size"],
        router_force_balance=numbers.get("router_force_balance", False), **how,
    )


def build(cell: dict, seed: int, devices):
    """The trainer, the feed and the seeded state: the one object set-up
    drives and the window inherits."""
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import traffic
    from benchmarks.reference import nemotron_h as reference
    from kubeflow_tpu.models.transformer import TransformerLM
    from kubeflow_tpu.parallel import MeshSpec, build_mesh
    from kubeflow_tpu.testing.hlo import jaxpr_kernel_names
    from kubeflow_tpu.train import TrainConfig, Trainer
    from kubeflow_tpu.train.trainer import TrainState

    work, numbers = cell["workload"], model_numbers(cell["config"])
    # The selection drawn evenly in place of the untrained router's, in the
    # program and in the reference alike (reference/zaya.py's docstring).
    numbers["router_force_balance"] = bool(work.get("router_force_balance"))
    opt = work["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError("the reference follows adamw only")
    mesh = build_mesh(MeshSpec(**work["mesh"]), list(devices)[: cell["chips"]])
    cfg = transformer_config(
        numbers, attention_impl=work.get("attention_impl", "auto"),
        remat_policy=work["remat"],
    )
    config = TrainConfig(
        batch_size=work["batch"], learning_rate=opt["learning_rate"],
        warmup_steps=opt["warmup_steps"], total_steps=opt["schedule_steps"],
        weight_decay=opt["weight_decay"], optimizer="adamw",
        adam_mu_dtype=opt["mu_dtype"], label_smoothing=0.0,
        fsdp_params=False, train_metrics="loss",
    )

    class _HeldTrainer(Trainer):
        held = None          # the state the next fit() call starts from
        kernels = None       # (traced names, tpu_custom_call count)
        _step = None

        def init_state(self, rng):
            state, self.held = self.held, None
            if state is None:
                raise RuntimeError("no held state for this fit() call")
            return state

        def make_train_step(self):
            if self._step is None:
                jitted = super().make_train_step()

                def step(state, batch):
                    if self.kernels is None:
                        traced = jitted.trace(state, batch)
                        names = jaxpr_kernel_names(traced.jaxpr.jaxpr)
                        calls = traced.lower().as_text().count("tpu_custom_call")
                        self.kernels = (names, calls)
                    with jax.profiler.TraceAnnotation("bench:dispatch"):
                        return jitted(state, batch)

                self._step = step
            return self._step

    trainer = _HeldTrainer(
        TransformerLM(cfg, mesh=mesh), config, mesh,
        example_input_shape=(2, work["seq_len"]),
        example_input_dtype=jnp.int32, input_key="tokens", label_key="labels",
    )
    shardings = trainer.state_shardings()

    def seeded_state(k):
        params = to_program_tree(reference.init_params(k, numbers))
        return TrainState(
            step=jnp.zeros((), jnp.int32), params=params,
            opt_state=trainer.tx.init(params), batch_stats={}, guard={},
            apply_fn=trainer.model.apply, tx=trainer.tx,
        )

    make_state = jax.jit(seeded_state, out_shardings=shardings)

    def reseed(seed: int):
        """A new run from `seed` on the same trainer: its state, held for
        the next fit() call, and its feed."""
        key = traffic.seed_key(seed)
        trainer.held = None  # two states of this size do not fit a chip
        trainer.held = make_state(key)
        feed = traffic.TokenFeed(
            key, batch=work["batch"], seq_len=work["seq_len"],
            vocab_size=numbers["vocab_size"],
            sharding=trainer.batch_sharding(2),
        )
        return feed, key

    feed, key = reseed(seed)
    trainer.reseed = reseed
    return trainer, feed, key, numbers


def _first_grad_norms(state, names) -> dict:
    """Norm by leaf of the first gradient as the optimizer got it: Adam's
    first moment after one update is (1 - b1) * g."""
    import jax
    import jax.numpy as jnp

    holders = [
        s for s in jax.tree_util.tree_leaves(
            state.opt_state, is_leaf=lambda x: hasattr(x, "mu")
        ) if hasattr(s, "mu")
    ]
    if len(holders) != 1:
        raise RuntimeError("expected one Adam state in the optimizer state")
    norms = jax.jit(lambda t: {
        k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32) / (1 - ADAM_B1))))
        for k, v in t.items()
    })(from_program_tree(holders[0].mu, names))
    return {k: float(v) for k, v in norms.items()}


def _change_norms(params, key, numbers) -> dict:
    """Norm by leaf of (parameters now - seeded parameters), the seeded
    ones made again leaf by leaf inside the one jitted call."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import nemotron_h as reference

    specs = reference.param_specs(numbers)

    def norms(flat, k):
        return {
            name: jnp.sqrt(jnp.sum(jnp.square(
                flat[name] - reference.init_leaf(k, i, *spec, numbers)
            )))
            for i, (name, spec) in enumerate(specs.items())
        }

    out = jax.jit(norms)(from_program_tree(params, specs), key)
    return {k: float(v) for k, v in out.items()}


def first_steps(trainer, feed, key, numbers, fit, mark=lambda what: None) -> dict:
    """Drive the held state through steps 1..3 by the window's own call and
    feed; what the reference will be compared with, and the step time."""
    from benchmarks.reference import nemotron_h as reference

    names = list(reference.param_specs(numbers))
    r1 = fit(trainer, feed, 1, log_every=1, handle_signals=False)
    mark("step 1 (the step program built or loaded)")
    grad = _first_grad_norms(r1.state, names)
    mark("first gradient's norms")
    trainer.held = r1.state
    t0 = time.perf_counter()
    r3 = fit(trainer, feed, 3, log_every=1, handle_signals=False)
    step_s = (time.perf_counter() - t0) / 2
    mark("steps 2-3")
    change = _change_norms(r3.state.params, key, numbers)
    trainer.held = r3.state
    records = r1.history + r3.history
    if len(records) != 3:
        raise RuntimeError(f"expected three records, got {records}")
    return {
        "loss": [r["loss"] for r in records], "first_grad_norm": grad,
        "change_norm": change, "step_s": step_s,
        "counters": [{k: r[k] for k in COUNTERS} for r in records],
    }


def run_reference(cell, key, numbers, feed, devices, quant=None) -> dict:
    """The plain reference over the first three batches, given the same
    share of the experts and the vocabulary."""
    from benchmarks.reference import nemotron_h as reference

    if cell["chips"] != 1:
        raise ValueError("this reference is placed on one chip only")
    work = cell["workload"]
    return reference.follow(
        key, numbers, work["optimizer"], [feed.batch_at(i) for i in range(3)],
        rows_per_block=work.get("reference_rows_per_block"), quant=quant,
    )


def routed(records: list[dict], numbers: dict, tokens_a_step: int) -> dict:
    """What the counters of some steps' records say: the rows (token-expert
    pairs) an expert layer routed to the experts held here a step
    (`tokens_held_a_layer`, the name the accepted readers take), those
    rows a token (`held_share`: of a token's k, the mean number held
    here), and the fullest held expert's load over the mean one's."""
    layers = numbers["hybrid_override_pattern"].count("E")
    held = sum(r["moe_tokens_held"] for r in records) / len(records) / layers
    return {
        "tokens_held_a_layer": held,
        "held_share": held / tokens_a_step,
        "load_max_over_mean": sum(r["moe_load_max"] for r in records)
        / sum(r["moe_load_mean"] for r in records),
        "records": len(records),
    }


def run(cell: dict, args, clock_start: float, say) -> dict:
    """One run of a train_hybrid cell. Returns the harness's result parts."""
    import jax

    from benchmarks.lib import compare as cmp
    from benchmarks.lib import flops_hybrid
    from kubeflow_tpu.train import fit

    work = cell["workload"]
    devices = jax.devices()[: cell["chips"]]

    def mark(what):
        say("mark", what=what, s=round(time.perf_counter() - clock_start, 3))

    trainer, feed, key, numbers = build(cell, args.seed, devices)
    jax.block_until_ready(trainer.held.params)
    mark("trainer and seeded state")
    program = first_steps(trainer, feed, key, numbers, fit, mark)
    mark("first three steps")
    names, calls = trainer.kernels
    say("kernels", traced=sorted(set(names)), traced_calls=len(names),
        lowered_tpu_custom_calls=calls)
    # The lowering keeps one function for each distinct kernel, however
    # many layers call it; an interpreted kernel leaves no custom call.
    if work.get("expect_kernels", True) and (
        not names or calls < len(set(names))
        or not any(n.startswith("flash_") for n in names)
        or not any(n.startswith("moe_gmm_") for n in names)
        or not any(n.startswith("ssd_") for n in names)
    ):
        raise RuntimeError(
            f"the step traced the Pallas kernels {sorted(set(names))} and "
            f"lowered {calls} tpu_custom_call(s): a dense or interpreted "
            "fallback"
        )
    # One more step before the window: the first execution of the step
    # program after the norms' program (which builds every seeded leaf
    # again) takes its temporaries anew, 0.1 s in some processes and not
    # in others, which put the rate in two modes 1 % apart (PERF.md §6).
    warm = fit(trainer, feed, 4, log_every=1, handle_signals=False)
    trainer.held = warm.state
    del warm
    mark("a fourth step, to settle the device's memory")
    steps = max(3, math.ceil(args.seconds / program["step_s"]))
    tokens_a_step = work["batch"] * work["seq_len"]
    say("setup", step_s=program["step_s"], window_steps=steps,
        loss_first_steps=program["loss"])
    say("routed", steps="1-3", **routed(program["counters"], numbers, tokens_a_step))

    tracer = None
    if args.trace:
        tracer = _TimedTrace(args.trace_dir, TRACE_AFTER_S, TRACE_S)
    compiles = args.compile_counter
    feed.spans.clear()
    feed.first_draw = None
    compiles.reset()
    if tracer:
        tracer.start()
    result = fit(trainer, feed, 4 + steps, handle_signals=False)
    t_end = time.perf_counter()
    t_first = feed.first_draw
    compiled_in_window = compiles.count
    if tracer:
        tracer.join()
    if result.steps_done != steps:
        raise RuntimeError(f"fit() ran {result.steps_done} of {steps} steps")
    window_s = t_end - t_first
    tokens = steps * tokens_a_step
    rate = tokens / window_s / cell["chips"]
    loss_last = result.history[-1]["loss"]
    moe = routed(result.history, numbers, tokens_a_step)
    say("window", steps=steps, seconds=window_s, tokens=tokens,
        tokens_per_s_per_chip=rate, loss_last=loss_last,
        compilations_in_window=compiled_in_window, traced=bool(args.trace))
    say("routed", steps="window", **moe)
    if compiled_in_window:
        raise RuntimeError(
            f"{compiled_in_window} compilation(s) inside the measured window"
        )
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(
        s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
        for s in stats
    )
    say("memory", **{k: stats[0].get(k) for k in (
        "peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit")})
    spans = [("input", a, b) for a, b in feed.spans]

    # The program's state goes before the reference comes.
    del result
    trainer.held = None
    trainer._step = None
    jax.clear_caches()
    gc.collect()
    t0 = time.perf_counter()
    ref = run_reference(cell, key, numbers, feed, devices)
    say("reference", seconds=time.perf_counter() - t0)
    checks = cmp.Checks()
    compare(program, ref, work["limits"], checks)
    checks.at_most(
        "loss, last step of the window, |value - ln(vocab)| / ln(vocab)",
        abs(loss_last - math.log(numbers["vocab_size"]))
        / math.log(numbers["vocab_size"]),
        work["limits"]["window_loss"],
        "random tokens: the loss stays near ln(vocab) while training is sound",
    )
    return {
        "checks": checks,
        "attempted": steps, "failed": 0,
        "end_to_end": {
            "tokens_per_s_per_chip": rate,
            "setup_s": t_first - clock_start,
        },
        "memory_peak_bytes": int(peak),
        "spans": spans,
        "facts": {
            "tokens_per_s_per_chip": rate, "steps": steps,
            "window_s": window_s, "numbers": numbers, "moe": moe,
            "flops_per_token": flops_hybrid.hybrid_flops_per_token(
                numbers, work["seq_len"], moe["held_share"]
            ),
        },
    }
