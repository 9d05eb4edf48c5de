"""Driver of the `train_moe` kind: a ZAYA1-style decoder (CCA attention
over grouped K/V heads, a dropless expert layer that holds a share of the
experts, a router whose state is carried across layers) through `Trainer`
+ `fit()`.

The same run as `drivers/train.py` makes — one trainer and one seeded state
through steps 1-3 (and a fourth, untimed and uncompared) in set-up and on
into the window, the plain reference after it, the same four numbers
compared — with this family's glue: the
configuration's keys, the `TransformerConfig` they become, where the
program keeps each of the reference's leaves (`reference/zaya.py`), the
routing counters `fit()` reports, and FLOPs that follow the tokens really
routed here (`lib/flops_moe.py`). What does not depend on the family is
imported from `drivers/train.py`.
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
    "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "moe_intermediate_size",
    "num_experts", "num_experts_per_tok", "router_hidden_size", "vocab_size",
    "max_position_embeddings", "partial_rotary_factor", "rope_parameters",
    "rms_norm_eps", "cca_time0", "cca_time1", "tie_word_embeddings",
    "hidden_act", "experts_routed", "experts_first",
}
CONFIG_KEYS = CONFIG_REQUIRED | {
    "model_type", "attention_bias", "lm_head_bias", "layer_types",
    "sliding_window",
}
COUNTERS = ("moe_tokens_held", "moe_load_max", "moe_load_mean")


def preload() -> None:
    """The program's imports, made while the chip is still being reached;
    a program without the expert layer fails here, at once."""
    import kubeflow_tpu.models.transformer  # noqa: F401
    import kubeflow_tpu.ops.moe  # noqa: F401
    import kubeflow_tpu.parallel  # noqa: F401
    import kubeflow_tpu.testing.hlo  # noqa: F401
    import kubeflow_tpu.train  # noqa: F401


def model_numbers(config: dict) -> dict:
    """The configuration's keys, checked for what the program's decoder
    can express, plus the rope's base."""
    if config["num_experts_per_tok"] != 1:
        raise ValueError("the program's expert layer is top-1")
    if not config["tie_word_embeddings"] or config["hidden_act"] != "silu":
        raise ValueError("the program's LM has a tied head and SiLU gates only")
    if config.get("attention_bias") or config.get("lm_head_bias"):
        raise ValueError("the program's LM has no biases")
    if config.get("sliding_window"):
        raise ValueError("the program's attention has no window")
    kinds = set(config.get("layer_types", ["hybrid"]))
    if kinds != {"hybrid"}:
        raise ValueError(f"layer types {sorted(kinds)}: only `hybrid` is built")
    if config["experts_first"] + config["num_experts"] > config["experts_routed"]:
        raise ValueError("the experts held are not a range of those routed")
    out = {k: config[k] for k in CONFIG_REQUIRED if k != "rope_parameters"}
    out["rope_theta"] = float(config["rope_parameters"]["hybrid"]["rope_theta"])
    return out


_ATTN_LEAVES = ("conv0_q", "conv0_k", "conv1_q", "conv1_k", "tau")


def _program_path(name: str) -> tuple[str, ...]:
    """Where the program's `TransformerLM` keeps the reference's leaf."""
    if name == "embedding":
        return ("embedding",)
    if name == "ln_final":
        return ("ln_final", "scale")
    _, i, leaf = name.split(".")
    if leaf in ("ln_attn", "ln_moe"):
        sub = ("ln_attn" if leaf == "ln_attn" else "ln_mlp", "scale")
    elif leaf in ("wq", "wk", "wv", "wo"):
        sub = ("attn", leaf, "kernel")
    elif leaf in _ATTN_LEAVES:
        sub = ("attn", leaf)
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
        n_heads=numbers["num_attention_heads"],
        n_kv_heads=numbers["num_key_value_heads"], head_dim=numbers["head_dim"],
        d_ff=numbers["moe_intermediate_size"], rope_theta=numbers["rope_theta"],
        rope_fraction=numbers["partial_rotary_factor"],
        norm_eps=numbers["rms_norm_eps"], cca=True,
        cca_kernels=(numbers["cca_time0"], numbers["cca_time1"]),
        num_experts=numbers["experts_routed"],
        experts_held=(numbers["experts_first"], numbers["num_experts"]),
        router_hidden=numbers["router_hidden_size"],
        router_force_balance=numbers.get("router_force_balance", False), **how,
    )


def build(cell: dict, seed: int, devices):
    """The trainer, the feed and the seeded state: the one object set-up
    drives and the window inherits."""
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import traffic
    from benchmarks.reference import zaya as reference
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

    from benchmarks.reference import zaya as reference

    specs = reference.param_specs(numbers)

    def norms(flat, k):
        return {
            name: jnp.sqrt(jnp.sum(jnp.square(
                flat[name] - reference.init_leaf(k, i, *spec)
            )))
            for i, (name, spec) in enumerate(specs.items())
        }

    out = jax.jit(norms)(from_program_tree(params, specs), key)
    return {k: float(v) for k, v in out.items()}


def first_steps(trainer, feed, key, numbers, fit, mark=lambda what: None) -> dict:
    """Drive the held state through steps 1..3 by the window's own call and
    feed; what the reference will be compared with, and the step time."""
    from benchmarks.reference import zaya as reference

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
    from benchmarks.reference import zaya as reference

    if cell["chips"] != 1:
        raise ValueError("the zaya reference is placed on one chip only")
    work = cell["workload"]
    return reference.follow(
        key, numbers, work["optimizer"], [feed.batch_at(i) for i in range(3)],
        rows_per_block=work.get("reference_rows_per_block"), quant=quant,
    )


def routed(records: list[dict], numbers: dict, tokens_a_step: int) -> dict:
    """What the counters of some steps' records say: the mean share of a
    step's tokens a layer routed to the experts held here, those tokens a
    layer a step, and the fullest held expert's load over the mean one's."""
    layers = numbers["num_hidden_layers"]
    held = sum(r["moe_tokens_held"] for r in records) / len(records) / layers
    return {
        "tokens_held_a_layer": held,
        "held_share": held / tokens_a_step,
        "load_max_over_mean": sum(r["moe_load_max"] for r in records)
        / sum(r["moe_load_mean"] for r in records),
        "records": len(records),
    }


def run(cell: dict, args, clock_start: float, say) -> dict:
    """One run of a train_moe cell. Returns the harness's result parts."""
    import jax

    from benchmarks.lib import compare as cmp
    from benchmarks.lib import flops_moe
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
            "flops_per_token": flops_moe.moe_flops_per_token(
                numbers, work["seq_len"], moe["held_share"]
            ),
        },
    }
