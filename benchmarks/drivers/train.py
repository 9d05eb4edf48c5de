"""Driver of the `train` kind: a language model through `Trainer` + `fit()`.

The program is driven as a training job drives it (`chip_smoke._lm_trainer`
is the pattern): a `TransformerLM` from the configuration's numbers, a
`Trainer` on the cell's mesh, `fit()` over an iterable of batches. What the
benchmark adds stays in this file:

- the weights and the batches come from `--seed`
  (`reference/lm.init_params`, `lib/traffic.token_batch`), so the reference
  can make both again without the program;
- `fit()` builds its state and jits its step anew on every call. A job calls
  it once; the benchmark calls it three times on ONE trajectory (step 1,
  steps 2-3, then the window), so `_HeldTrainer` hands each call the state
  the last one left and the one jitted step — what a checkpoint restore
  does for a resumed job, without the checkpoint;
- spans round the calls into the program: `bench:input` round `next()`,
  `bench:dispatch` round the step call.

Set-up is everything before the window's first batch is drawn: imports,
weights, the step program (compiled, or loaded from the cache), the first
three steps, whose losses, first gradient and parameter change the
reference follows. The window is one `fit()` call at its defaults
(`log_every` 50): from its first batch drawn to its return with the last
loss read back. The reference runs after the window, when the program's
state is freed, and is not counted in anything.
"""

from __future__ import annotations

import gc
import math
import threading
import time

WORKLOAD_REQUIRED = {"batch", "seq_len", "mesh", "remat", "optimizer", "limits"}
WORKLOAD_KEYS = WORKLOAD_REQUIRED | {
    "attention_impl", "expect_kernels", "reference_rows_per_block",
}
CONFIG_REQUIRED = {
    "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "intermediate_size", "vocab_size",
    "max_position_embeddings", "rope_theta", "tie_word_embeddings",
    "hidden_act",
}
CONFIG_KEYS = CONFIG_REQUIRED | {
    "model_type", "attention_bias", "clip_qkv", "rope_scaling",
    "initializer_range",
}
LIMIT_KEYS = {"loss", "first_grad_norm", "change_norm", "window_loss"}

ADAM_B1 = 0.9  # optax.adamw's default; the first gradient is mu / (1 - b1)
TRACE_AFTER_S, TRACE_S = 2.0, 3.0  # the traced part of the window


def preload() -> None:
    """The program's imports, made while the chip is still being reached."""
    import kubeflow_tpu.models.transformer  # noqa: F401
    import kubeflow_tpu.parallel  # noqa: F401
    import kubeflow_tpu.testing.hlo  # noqa: F401
    import kubeflow_tpu.train  # noqa: F401


def model_numbers(config: dict) -> dict:
    """The configuration's published keys, checked for what the program's
    one decoder can express, plus the derived head size."""
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("the program's attention has no grouped K/V heads")
    if not config["tie_word_embeddings"] or config["hidden_act"] != "silu":
        raise ValueError("the program's LM has a tied head and SwiGLU only")
    if config.get("attention_bias") or config.get("clip_qkv"):
        raise ValueError("the program's LM has no biases and no qkv clipping")
    out = {k: config[k] for k in CONFIG_REQUIRED}
    out["head_dim"] = config["hidden_size"] // config["num_attention_heads"]
    return out


def _program_path(name: str) -> tuple[str, ...]:
    """Where the program's `TransformerLM` keeps the reference's leaf."""
    if name == "embedding":
        return ("embedding",)
    if name == "ln_final":
        return ("ln_final", "scale")
    _, i, leaf = name.split(".")
    sub = {
        "ln_attn": ("ln_attn", "scale"), "ln_mlp": ("ln_mlp", "scale"),
        "wq": ("attn", "wq", "kernel"), "wk": ("attn", "wk", "kernel"),
        "wv": ("attn", "wv", "kernel"), "wo": ("attn", "wo", "kernel"),
        "w_gate": ("mlp", "wi_gate", "kernel"),
        "w_up": ("mlp", "wi_up", "kernel"),
        "w_down": ("mlp", "wo", "kernel"),
    }[leaf]
    return (f"layer_{i}", *sub)


def _to_program_tree(flat: dict) -> dict:
    tree: dict = {}
    for name, leaf in flat.items():
        node = tree
        *parents, last = _program_path(name)
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


def _from_program_tree(tree, names) -> dict:
    out = {}
    for name in names:
        node = tree
        for p in _program_path(name):
            node = node[p]
        out[name] = node
    return out


def build(cell: dict, seed: int, devices):
    """The trainer, the feed and the seeded state: the one object set-up
    drives and the window inherits."""
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import traffic
    from benchmarks.reference import lm as reference
    from kubeflow_tpu.models.transformer import TransformerConfig, TransformerLM
    from kubeflow_tpu.parallel import MeshSpec, build_mesh
    from kubeflow_tpu.testing.hlo import jaxpr_kernel_names
    from kubeflow_tpu.train import TrainConfig, Trainer
    from kubeflow_tpu.train.trainer import TrainState

    work, numbers = cell["workload"], model_numbers(cell["config"])
    opt = work["optimizer"]
    if opt["name"] != "adamw":
        raise ValueError("the reference follows adamw only")
    mesh = build_mesh(MeshSpec(**work["mesh"]), list(devices)[: cell["chips"]])
    cfg = TransformerConfig(
        vocab_size=numbers["vocab_size"], d_model=numbers["hidden_size"],
        n_layers=numbers["num_hidden_layers"],
        n_heads=numbers["num_attention_heads"], head_dim=numbers["head_dim"],
        d_ff=numbers["intermediate_size"], rope_theta=numbers["rope_theta"],
        attention_impl=work.get("attention_impl", "auto"),
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
        params = _to_program_tree(reference.init_params(k, numbers))
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
    flat = _from_program_tree(holders[0].mu, names)
    norms = jax.jit(lambda t: {
        k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32) / (1 - ADAM_B1))))
        for k, v in t.items()
    })(flat)
    return {k: float(v) for k, v in norms.items()}


def _change_norms(params, key, numbers) -> dict:
    """Norm by leaf of (parameters now - seeded parameters), the seeded
    ones made again leaf by leaf inside the one jitted call."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import lm as reference

    specs = reference.param_specs(numbers)
    flat = _from_program_tree(params, specs)

    def norms(flat, k):
        return {
            name: jnp.sqrt(jnp.sum(jnp.square(
                flat[name] - reference.init_leaf(k, i, shape, std)
            )))
            for i, (name, (shape, std)) in enumerate(specs.items())
        }

    return {k: float(v) for k, v in jax.jit(norms)(flat, key).items()}


def first_steps(trainer, feed, key, numbers, fit, mark=lambda what: None) -> dict:
    """Drive the held state through steps 1..3 by the window's own call and
    feed; what the reference will be compared with, and the step time."""
    from benchmarks.reference import lm as reference

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
    losses = [r["loss"] for r in r1.history + r3.history]
    if len(losses) != 3:
        raise RuntimeError(f"expected three losses, got {losses}")
    return {
        "loss": losses, "first_grad_norm": grad, "change_norm": change,
        "step_s": step_s,
    }


def gaps(program: dict, ref: dict) -> dict:
    """The numbers the comparison reads, program against reference: the
    widest relative gap of the steps' losses, and for the two norms the
    worst leaf's gap and its name."""
    from benchmarks.lib.compare import worst_leaf_gap

    out = {"loss": max(
        abs(p - r) / abs(r) for p, r in zip(program["loss"], ref["loss"])
    )}
    for what in ("first_grad_norm", "change_norm"):
        out[what], out[what + "_leaf"] = worst_leaf_gap(program[what], ref[what])
    return out


def compare(program: dict, ref: dict, limits: dict, checks) -> None:
    if set(limits) != LIMIT_KEYS:
        raise ValueError(f"limits must be exactly {sorted(LIMIT_KEYS)}")
    read = gaps(program, ref)
    checks.at_most(
        "loss, steps 1-3, widest |program - reference| / reference",
        read["loss"], limits["loss"],
        f"program {program['loss']} reference {ref['loss']}",
    )
    for what in ("first_grad_norm", "change_norm"):
        checks.at_most(
            f"{what}, worst leaf, |program - reference| / "
            "max(reference leaf, median leaf)",
            read[what], limits[what], f"at {read[what + '_leaf']}",
        )


def run_reference(cell, key, numbers, feed, devices, quant=None) -> dict:
    """The plain reference over the first three batches. On several chips
    its state is spread over them for room; the arithmetic is the same."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks.reference import lm as reference

    work = cell["workload"]
    devices = list(devices)[: cell["chips"]]
    how = {}
    batches = [feed.batch_at(i) for i in range(3)]
    if len(devices) > 1:
        how = spread_over(devices)
        whole = NamedSharding(how.pop("mesh"), P())
        batches = [jax.device_put(b, whole) for b in batches]
    return reference.follow(
        key, numbers, work["optimizer"], batches,
        rows_per_block=work.get("reference_rows_per_block", 1),
        quant=quant, **how,
    )


def spread_over(devices) -> dict:
    """Where the reference's leaves and rows live on several chips: every
    matrix split over them along its first axis (its second, where the
    first counts the layers), a block's rows one share a chip. Placement only; the compiler moves what each product needs."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(list(devices), ("ref",))
    n = len(devices)

    def place(tree):
        out = {}
        for k, v in tree.items():
            lead = 1 if k.startswith("layers.") else 0
            split = v.ndim - lead > 1 and v.shape[lead] % n == 0
            out[k] = jax.lax.with_sharding_constraint(v, NamedSharding(
                mesh, P(*([None] * lead), "ref") if split else P(),
            ))
        return out

    def place_rows(x):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P("ref" if x.shape[0] % n == 0 else None))
        )

    return {"mesh": mesh, "place": place, "place_rows": place_rows}


class _TimedTrace:
    """Traces `seconds` of the window, starting `after` seconds into it,
    from a thread of its own: the host may run many steps ahead of the
    device, so steps counted on the host say nothing about device time."""

    def __init__(self, logdir: str, after: float, seconds: float):
        self.logdir, self.after, self.seconds = logdir, after, seconds
        self._thread = threading.Thread(target=self._run, daemon=True)
        self.error: Exception | None = None

    def start(self):
        self._thread.start()

    def _run(self):
        import jax

        try:
            time.sleep(self.after)
            jax.profiler.start_trace(self.logdir)
            time.sleep(self.seconds)
            jax.profiler.stop_trace()
        except Exception as e:  # raised again by join(), in the main thread
            self.error = e

    def join(self):
        self._thread.join()
        if self.error is not None:
            raise self.error


def run(cell: dict, args, clock_start: float, say) -> dict:
    """One run of a train cell. Returns the harness's result parts."""
    import jax

    from benchmarks.lib import compare as cmp
    from benchmarks.lib import flops
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
    ):
        raise RuntimeError(
            f"the step traced the Pallas kernels {sorted(set(names))} and "
            f"lowered {calls} tpu_custom_call(s): a dense or interpreted "
            "fallback"
        )
    steps = max(3, math.ceil(args.seconds / program["step_s"]))
    say("setup", step_s=program["step_s"], window_steps=steps,
        loss_first_steps=program["loss"])

    tracer = None
    if args.trace:
        tracer = _TimedTrace(args.trace_dir, TRACE_AFTER_S, TRACE_S)
    compiles = args.compile_counter
    feed.spans.clear()
    feed.first_draw = None
    compiles.reset()
    if tracer:
        tracer.start()
    result = fit(trainer, feed, 3 + steps, handle_signals=False)
    t_end = time.perf_counter()
    t_first = feed.first_draw
    compiled_in_window = compiles.count
    if tracer:
        tracer.join()
    if result.steps_done != steps:
        raise RuntimeError(f"fit() ran {result.steps_done} of {steps} steps")
    window_s = t_end - t_first
    tokens = steps * work["batch"] * work["seq_len"]
    rate = tokens / window_s / cell["chips"]
    loss_last = result.history[-1]["loss"]
    say("window", steps=steps, seconds=window_s, tokens=tokens,
        tokens_per_s_per_chip=rate, loss_last=loss_last,
        compilations_in_window=compiled_in_window, traced=bool(args.trace))
    if compiled_in_window:
        raise RuntimeError(
            f"{compiled_in_window} compilation(s) inside the measured window"
        )
    # The runtime keeps a program's temporaries in a reserved region that
    # `peak_bytes_in_use` leaves out (5.2 GB of state against 9.5 GB
    # reserved in this cell): the peak on a chip is the two together.
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
            "window_s": window_s, "numbers": numbers,
            "flops_per_token": flops.lm_flops_per_token(numbers, work["seq_len"]),
        },
    }
