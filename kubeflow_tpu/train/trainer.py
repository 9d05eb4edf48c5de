"""Sharded train-step factory.

The scaling recipe end-to-end: the model carries logical axis names, the
mesh carries physical axes, `flax.linen.logical_to_mesh_sharding` joins them
through the rules table, and one `jax.jit` with explicit in/out shardings
compiles the whole step — XLA inserts every collective (gradient psum over
dp, all-gather/reduce-scatter for fsdp, tp all-reduces) that the reference
obtained from parameter servers and Horovod rings (SURVEY.md §2.2).

No pmap, no per-device Python: a single traced program over the global mesh,
which is what lets the same trainer run 1 chip or a multi-slice pod.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Mapping

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from flax import core, struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kubeflow_tpu.parallel import sharding as shlib
from kubeflow_tpu.parallel.mesh import step_compiler_options
from kubeflow_tpu.train import profiling
from kubeflow_tpu.utils import memory


def _ensure_partitionable_rng() -> None:
    """Sharding-invariant initialization: the pinned jax defaults to the
    non-partitionable threefry, whose draws depend on the physical
    layout — the SAME PRNGKey then yields different params on a
    tp-sharded mesh than on one device (the exact semantics drift
    test_lm_tp_matches_single_device pins: "partitioning must not change
    semantics"). The partitionable form derives every element's bits
    from its logical index, so init_state is identical on any mesh.

    Called from Trainer construction — not at import — so merely
    importing this module never mutates process-global PRNG semantics;
    only actually binding a sharded trainer opts the process in.
    """
    if not jax.config.jax_threefry_partitionable:
        jax.config.update("jax_threefry_partitionable", True)


class TrainState(struct.PyTreeNode):
    """Step counter + params + optimizer + BN state, one donate-able pytree.

    `guard` is the anomaly guard's scalar pytree (`train/guard.py`) when
    the trainer was built with one, else an empty dict (no leaves). It
    lives inside TrainState so checkpoints carry it: a resumed or
    rolled-back run restores its skip counters with its params."""

    step: jax.Array
    params: core.FrozenDict | dict
    opt_state: optax.OptState
    batch_stats: core.FrozenDict | dict = struct.field(default_factory=dict)
    guard: dict = struct.field(default_factory=dict)
    apply_fn: Callable = struct.field(pytree_node=False, default=None)
    tx: optax.GradientTransformation = struct.field(pytree_node=False, default=None)

    def apply_gradients(self, *, grads, **updates) -> "TrainState":
        upd, new_opt = self.tx.update(grads, self.opt_state, self.params)
        return self.replace(
            step=self.step + 1,
            params=optax.apply_updates(self.params, upd),
            opt_state=new_opt,
            **updates,
        )


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    batch_size: int = 256
    learning_rate: float = 0.4
    warmup_steps: int = 200
    total_steps: int = 10_000
    momentum: float = 0.9
    weight_decay: float = 1e-4
    label_smoothing: float = 0.1
    # "sgd" (benchmark parity with tf_cnn_benchmarks' default) or "adamw"
    optimizer: str = "sgd"
    fsdp_params: bool = True
    # Per-step training metrics: "full" also computes accuracy (an
    # argmax over the logits — at LM vocab sizes that is a multi-GB
    # logits readback per step, which production LM trainers skip);
    # "loss" returns the objective only. Eval always computes both.
    train_metrics: str = "full"
    # adamw first-moment dtype. The optimizer step is pure HBM
    # bandwidth (measured 677 GB/s = 83% of v5e peak on the 350M LM
    # bench); storing mu in bf16 halves its read+write traffic for a
    # measured +1.1% step throughput with no observable loss impact —
    # the MaxText default. The second moment stays f32 (it accumulates
    # squares; bf16 there costs real precision). "float32" opts out.
    adam_mu_dtype: str = "bfloat16"
    # Per-microbatch gradient accumulation: split each batch into
    # `accum_steps` microbatches and run them through a `lax.scan` whose
    # per-tick forward is wrapped in `jax.checkpoint`, differentiating
    # through the scan — the backward walks the microbatches in reverse,
    # recomputing each tick's forward, so activation memory is bounded
    # by ONE microbatch in flight instead of the whole batch. Composes
    # with the model's per-block `remat_policy` (which governs what
    # the per-tick recompute itself saves — e.g. "flash" still pins
    # attention outputs + lse within a tick). Works on any
    # mesh, pp or not; grads and loss equal the full-batch step's (mean
    # of equal-sized microbatch means). 1 = off.
    accum_steps: int = 1
    # The model computes its own objective: the train/eval steps call
    # `apply(variables, batch[input], train=..., labels=batch[label])`
    # and take the returned SCALAR as the loss instead of computing
    # cross-entropy on returned logits. This is how the pipelined
    # transformer's last-stage loss path is driven (the logits never
    # leave the last pp stage — only the loss scalar crosses the pp
    # axis). Requires train_metrics="loss" (no logits → no accuracy)
    # and label_smoothing=0.0 (the model's objective, not the
    # trainer's, defines any smoothing).
    loss_in_model: bool = False

    def __post_init__(self) -> None:
        # A typo ("Full", "all") would silently behave as "loss" and drop
        # per-step accuracy; fail loudly instead.
        if self.train_metrics not in ("full", "loss"):
            raise ValueError(
                f"train_metrics must be 'full' or 'loss', got "
                f"{self.train_metrics!r}"
            )
        if self.optimizer not in ("sgd", "adamw"):
            raise ValueError(f"unknown optimizer {self.optimizer!r}")
        if self.adam_mu_dtype not in ("bfloat16", "float32"):
            raise ValueError(
                f"adam_mu_dtype must be 'bfloat16' or 'float32', got "
                f"{self.adam_mu_dtype!r}"
            )
        if self.accum_steps < 1:
            raise ValueError(
                f"accum_steps must be >= 1, got {self.accum_steps}"
            )
        if self.batch_size % self.accum_steps:
            raise ValueError(
                f"batch_size ({self.batch_size}) must divide into "
                f"{self.accum_steps} accumulation microbatches"
            )
        if self.loss_in_model:
            if self.train_metrics != "loss":
                raise ValueError(
                    "loss_in_model=True returns no logits; accuracy is "
                    "unavailable — set train_metrics='loss'"
                )
            if self.label_smoothing:
                raise ValueError(
                    "loss_in_model=True delegates the objective to the "
                    "model; TrainConfig.label_smoothing would be "
                    "silently ignored — set it to 0.0"
                )


def decay_mask(params) -> Any:
    """Weight decay applies to matrices/filters only — never to the 1-D
    params (BN/LN scales and biases)."""
    return jax.tree_util.tree_map(lambda p: p.ndim > 1, params)


def make_optimizer(config: TrainConfig) -> optax.GradientTransformation:
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=config.learning_rate,
        warmup_steps=config.warmup_steps,
        decay_steps=max(config.total_steps, config.warmup_steps + 1),
    )
    if config.optimizer == "sgd":
        return optax.chain(
            optax.add_decayed_weights(config.weight_decay, mask=decay_mask),
            optax.sgd(schedule, momentum=config.momentum, nesterov=True),
        )
    if config.optimizer == "adamw":
        return optax.adamw(
            schedule,
            weight_decay=config.weight_decay,
            mu_dtype=jnp.bfloat16
            if config.adam_mu_dtype == "bfloat16"
            else jnp.float32,
        )
    raise ValueError(f"unknown optimizer {config.optimizer!r}")


def _summed_by_name(counters) -> dict:
    """A "counters" collection (module path -> name -> value) as
    {name: sum over every module that sowed it}."""
    out: dict = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(counters)[0]:
        name = next(
            p.key for p in reversed(path)
            if isinstance(p, jax.tree_util.DictKey)
        )
        out[name] = out.get(name, 0.0) + leaf
    return out


def softmax_cross_entropy(
    logits, labels, label_smoothing: float = 0.0, where=None,
):
    """Fused gather-based cross entropy (equals
    `optax.softmax_cross_entropy(logits, smoothed_onehot).mean()`); with
    `where` (bool, broadcast over `labels`) the mean over the positions it
    takes alone: a position that has no target is masked HERE, over whole
    logits, because a slice of them to a row count that is no whole number
    of tiles (S - 1 of S) is a relayout of [tokens, vocab] float32 on the
    TPU (20 ms of a 300 ms step where it was tried, PERF.md §6, PR 48).

    The one-hot formulation materializes a [B, S, vocab] dense target and
    streams it from HBM alongside the logits; at LM vocab sizes that is
    gigabytes per step of pure bandwidth waste on an HBM-bound chip. The
    identity `CE = logsumexp(logits) - logits[label]` (smoothing mixes in
    `logsumexp - mean(logits)`, the uniform-target term) needs only a
    rank-reducing reduce and a gather, both of which XLA fuses into the
    logits producer."""
    logits = logits.astype(jnp.float32)
    log_z = jax.scipy.special.logsumexp(logits, axis=-1)
    label_logits = jnp.take_along_axis(
        logits, labels[..., None], axis=-1
    )[..., 0]
    nll = log_z - label_logits
    if label_smoothing:
        uniform = log_z - logits.mean(axis=-1)
        nll = (1.0 - label_smoothing) * nll + label_smoothing * uniform
    if where is None:
        return nll.mean()
    where = jnp.broadcast_to(where, nll.shape)
    return jnp.sum(jnp.where(where, nll, 0.0)) / jnp.sum(where)


class Trainer:
    """Binds (model, config, mesh) into sharded init/train-step callables."""

    def __init__(
        self,
        model: nn.Module,
        config: TrainConfig,
        mesh: Mesh,
        rules: Mapping[str, Any] | None = None,
        example_input_shape: tuple = (2, 224, 224, 3),
        input_key: str = "image",
        label_key: str = "label",
        example_input_dtype: Any = jnp.float32,
        guard: "Any | None" = None,
    ):
        _ensure_partitionable_rng()
        self.model = model
        self.config = config
        self.mesh = mesh
        # Optional AnomalyGuard (train/guard.py): when set, every train
        # step screens loss/grad-norm on device and skips anomalous
        # updates instead of applying them (see make_train_step).
        self.guard = guard
        self.rules = dict(
            rules
            if rules is not None
            else shlib.default_rules(fsdp_params=config.fsdp_params)
        )
        self.tx = make_optimizer(config)
        # The init dummy batch must divide evenly over the mesh batch axes
        # (model code may shard_map over them, e.g. ring attention).
        dp_total = shlib.batch_shard_count(mesh)
        lead = example_input_shape[0]
        if lead % dp_total:
            lead = dp_total * max(1, -(-lead // dp_total))
        self.example_input_shape = (lead, *example_input_shape[1:])
        self.example_input_dtype = example_input_dtype
        self.input_key = input_key
        self.label_key = label_key
        self._shardings = None
        self._abstract = None
        self._step_program: profiling.StepProgram | None = None

    # -- state construction ------------------------------------------------

    def _init_boxed(self, rng) -> TrainState:
        """Init keeping flax Partitioned boxes so logical names survive
        through eval_shape into the optimizer state (optax tree_maps rebuild
        the boxes, which is how momentum inherits the param shardings)."""
        dummy = jnp.zeros(self.example_input_shape, self.example_input_dtype)
        variables = self.model.init(rng, dummy, train=False)
        params = variables["params"]
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=self.tx.init(params),
            batch_stats=variables.get("batch_stats", {}),
            guard=self.guard.init_state() if self.guard is not None else {},
            apply_fn=self.model.apply,
            tx=self.tx,
        )

    def _abstract_boxed(self) -> TrainState:
        if self._abstract is None:
            self._abstract = jax.eval_shape(
                self._init_boxed, jax.random.PRNGKey(0)
            )
        return self._abstract

    def state_shardings(self) -> TrainState:
        """NamedSharding tree for TrainState, from logical annotations."""
        if self._shardings is None:
            logical = nn.get_partition_spec(self._abstract_boxed())
            self._shardings = nn.logical_to_mesh_sharding(
                logical, self.mesh, list(self.rules.items())
            )
        return self._shardings

    def abstract_state(self) -> TrainState:
        """ShapeDtypeStruct pytree with shardings attached — the template
        for sharded checkpoint restore (each device reads its own shards)."""
        abstract = nn.meta.unbox(self._abstract_boxed())
        return jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            abstract,
            self.state_shardings(),
        )

    def init_state(self, rng) -> TrainState:
        shardings = self.state_shardings()
        init = jax.jit(
            lambda r: nn.meta.unbox(self._init_boxed(r)),
            out_shardings=shardings,
        )
        return init(rng)

    def batch_sharding(self, ndim: int = 1) -> NamedSharding:
        return shlib.batch_sharding(self.mesh, ndim)

    def step_memory(self) -> memory.StepMemory:
        """What the step holds on one device whatever the model does, by
        `abstract_state()`'s shapes and shardings, and the limit of a
        device this process addresses: what the step states to the model
        it traces (`utils/memory.py`). Constants of the trainer and the
        chip's kind, so every trace of the step, in every process of the
        job, reads the same."""

        def shard_bytes(tree) -> int:
            return sum(
                math.prod(a.sharding.shard_shape(a.shape)) * a.dtype.itemsize
                for a in jax.tree_util.tree_leaves(tree)
            )

        abstract = self.abstract_state()
        return memory.StepMemory(
            state_bytes=shard_bytes(abstract),
            grad_bytes=shard_bytes(abstract.params),
            limit_bytes=memory.device_limit(self.mesh),
        )

    # -- elastic resize ----------------------------------------------------

    def resize(self, mesh: Mesh) -> "Trainer":
        """A new Trainer bound to `mesh` — the trainer half of the
        elastic gang-resize transition (docs/resilience.md).

        Only the data-parallel axes (dp/fsdp) may change size: the
        model-parallel axes (pp/sp/ep/tp) define how PARAMETERS are laid
        out across chips, and reshaping those mid-run is a different
        (restart-shaped) operation. The divisor math is validated up
        front (`parallel.mesh.resize_spec`) so a degenerate target
        fails with the arithmetic spelled out instead of an opaque
        reshape error deep in sharding."""
        from kubeflow_tpu.parallel.mesh import mesh_spec_of, resize_spec

        old_spec = mesh_spec_of(self.mesh)
        new_spec = mesh_spec_of(mesh)
        for axis in ("pp", "sp", "ep", "tp"):
            old_n, new_n = getattr(old_spec, axis), getattr(new_spec, axis)
            if old_n != new_n:
                raise ValueError(
                    f"elastic resize reshapes only the data-parallel "
                    f"axes; {axis} changed {old_n} -> {new_n} — "
                    f"model-parallel resharding needs a gang restart"
                )
        # Spell out the device/batch divisor math for the target dp
        # (fsdp rides along as part of the batch-shard product).
        resize_spec(
            dataclasses.replace(old_spec, fsdp=new_spec.fsdp),
            new_spec.dp,
            n_devices=int(mesh.devices.size),
            global_batch=self.config.batch_size,
        )
        return Trainer(
            self.model,
            self.config,
            mesh,
            rules=self.rules,
            example_input_shape=self.example_input_shape,
            input_key=self.input_key,
            label_key=self.label_key,
            example_input_dtype=self.example_input_dtype,
            guard=self.guard,
        )

    def reshard_state(self, state: TrainState) -> TrainState:
        """Re-shard a LIVE TrainState onto this trainer's mesh — the
        happy-path resize needs no checkpoint round-trip. Leaf-wise
        `jax.device_put` onto the new NamedShardings (jax reshards
        across device sets, so a state living on the old mesh's devices
        lands distributed over the new mesh's), rebuilt on THIS
        trainer's treedef so the static fields (apply_fn, tx) are this
        trainer's own rather than the old mesh's closures."""
        shardings = self.state_shardings()
        src = jax.tree_util.tree_leaves(state)
        dst = jax.tree_util.tree_leaves(shardings)
        if len(src) != len(dst):
            raise ValueError(
                f"TrainState has {len(src)} leaves but this trainer's "
                f"state tree has {len(dst)} — resize must keep the "
                "model/optimizer/guard structure identical"
            )
        leaves = [jax.device_put(x, s) for x, s in zip(src, dst)]
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(shardings), leaves
        )

    # -- the step ----------------------------------------------------------

    def make_train_step(self):
        cfg = self.config
        guard = self.guard
        input_key = self.input_key
        label_key = self.label_key
        mesh = self.mesh
        batch_parts = tuple(shlib.batch_axes(mesh))
        # Accuracy needs logits; the loss-in-model path never sees them.
        has_acc = cfg.train_metrics == "full" and not cfg.loss_in_model
        # Stated to the model for a step of one batch alone. Over
        # microbatches the scan's backward holds the accumulated
        # gradients, a tick's and more beside the state (2.5 times the
        # gradients' bytes on top, by the chip's compiler for a described
        # v5e, PERF.md §7): no count of them here is a bound, so nothing
        # is stated and the model keeps what it kept before.
        step_memory = self.step_memory() if cfg.accum_steps == 1 else None

        def train_step(state: TrainState, batch):
            def forward_loss(params, mb, stats_in):
                """(loss, (batch_stats, accuracy, counters)) for one
                (micro)batch. The loss is the cross entropy of the logits
                the model returns or, under `loss_in_model`, the model's
                own scalar: a `TransformerLM` with a multi-token module
                returns `main_loss + mtp_weight * mtp_loss` (both by
                `softmax_cross_entropy`, the second over the labels
                shifted by one more) and reports the two apart among
                the counters.

                Metrics that survive accumulation are SCALARS computed
                in here (accuracy is an argmax reduced to a mean, never
                the logits themselves), so the per-tick backward frees
                each microbatch's logits before the next tick runs.
                `stats_in` is the batch_stats this tick reads — under
                accumulation each microbatch consumes the previous
                tick's updated stats (sequential BN semantics), not the
                step's starting stats."""
                variables = {"params": params}
                # "counters" is the channel for scalars a model counts
                # as it runs (the expert layer's routed tokens): summed
                # by name over the modules that sow them and reported
                # beside the loss, never part of the objective.
                mutable = ["counters"]
                if stats_in:
                    variables["batch_stats"] = stats_in
                    mutable.append("batch_stats")

                if cfg.loss_in_model:
                    # The model owns the objective (e.g. the pipelined
                    # transformer's last-stage per-microbatch CE): apply
                    # returns the scalar loss directly.
                    def forward(variables):
                        return state.apply_fn(
                            variables, mb[input_key], train=True,
                            labels=mb[label_key], mutable=mutable,
                        )
                else:
                    def forward(variables):
                        return state.apply_fn(
                            variables, mb[input_key], train=True,
                            mutable=mutable,
                        )

                with memory.stated(step_memory):
                    out, new_vars = forward(variables)
                if cfg.loss_in_model:
                    loss = out
                    acc = jnp.zeros(())
                else:
                    with jax.named_scope("loss"):
                        loss = softmax_cross_entropy(
                            out, mb[label_key], cfg.label_smoothing
                        )
                        acc = (
                            jnp.mean(
                                (jnp.argmax(out, -1) == mb[label_key])
                                .astype(jnp.float32)
                            )
                            if has_acc
                            else jnp.zeros(())
                        )
                return loss, (
                    new_vars.get("batch_stats", stats_in), acc,
                    _summed_by_name(new_vars.get("counters", {})),
                )

            accum = cfg.accum_steps
            if accum == 1:
                (loss, (bstats, acc, counters)), grads = jax.value_and_grad(
                    forward_loss, has_aux=True
                )(state.params, batch, state.batch_stats)
            else:
                lead = jax.tree_util.tree_leaves(batch)[0].shape[0]
                if lead % accum:
                    raise ValueError(
                        f"batch ({lead}) must divide into "
                        f"{accum} accumulation microbatches"
                    )
                microbatches = jax.tree_util.tree_map(
                    lambda a: a.reshape(
                        (accum, a.shape[0] // accum) + a.shape[1:]
                    ),
                    batch,
                )
                # Each microbatch keeps the batch sharding on its (now
                # second) example axis; the scan axis is unsharded.
                microbatches = jax.lax.with_sharding_constraint(
                    microbatches,
                    NamedSharding(mesh, P(None, batch_parts)),
                )
                # Per-tick checkpoint: differentiating through the scan
                # re-runs ONE microbatch's forward per backward tick —
                # activation memory is bounded by microbatches in
                # flight, not the whole batch. The model's remat_policy
                # still governs what that per-tick recompute itself
                # saves.
                tick = jax.checkpoint(forward_loss)

                def accum_loss(params):
                    def body(carry, mb):
                        lsum, asum, bs = carry
                        # Thread batch_stats tick to tick: each
                        # microbatch's BN update builds on the previous
                        # one's, so the step's final stats reflect
                        # EVERY microbatch (sequential-small-batch
                        # semantics), not just the last.
                        loss, (bs, acc, counted) = tick(params, mb, bs)
                        return (lsum + loss, asum + acc, bs), counted

                    carry0 = (jnp.zeros(()), jnp.zeros(()),
                              state.batch_stats)
                    (lsum, asum, bstats), counted = jax.lax.scan(
                        body, carry0, microbatches
                    )
                    # Mean over equal-sized microbatches == the
                    # full-batch mean, so grads match accum_steps=1.
                    return lsum / accum, (
                        bstats, asum / accum,
                        {k: v.sum(axis=0) for k, v in counted.items()},
                    )

                (loss, (bstats, acc, counters)), grads = jax.value_and_grad(
                    accum_loss, has_aux=True
                )(state.params)

            # What the model counted (the expert layer's routed tokens),
            # summed over the modules that sowed it; {} for most models.
            metrics = {"loss": loss, "counters": counters}
            if has_acc:
                metrics["accuracy"] = acc
            # The scopes name the device's time for a profile's reader
            # (`profiling.program_scopes`); they change no operation.
            if guard is None:
                with jax.named_scope(profiling.UPDATE_SCOPE):
                    state = state.apply_gradients(
                        grads=grads, batch_stats=bstats
                    )
                return state, metrics

            # Anomaly guard: screen this step's loss/grad-norm AND the
            # finiteness of the updated params ON DEVICE (a finite
            # gradient can still overflow a param to inf — an accepted
            # overflow would poison every later checkpoint), then
            # select between the applied and the skipped state
            # leaf-wise. A rejected step keeps params, optimizer state
            # and BN stats untouched (the bad batch must not leak into
            # anything), but still advances the step counter so
            # checkpoint/data bookkeeping stays step-aligned. The
            # verdict never syncs to the host — the select + isfinite
            # cost extra HBM passes over the state, not a device fence.
            with jax.named_scope(profiling.UPDATE_SCOPE):
                applied = state.apply_gradients(
                    grads=grads, batch_stats=bstats
                )
            with jax.named_scope("guard"):
                grad_norm = optax.global_norm(grads)
                # batch_stats are screened too: a huge-but-finite poison
                # batch can keep loss/grads/params finite (BN normalizes it
                # away) while its batch variance overflows the f32 running
                # stats to inf — accepted, that inf rides into every later
                # checkpoint and breaks eval/serving (train=False).
                update_finite = jnp.bool_(True)
                for leaf in jax.tree_util.tree_leaves(
                    (applied.params, applied.batch_stats)
                ):
                    if jnp.issubdtype(leaf.dtype, jnp.floating):
                        update_finite &= jnp.all(jnp.isfinite(leaf))
                gstate, ok = guard.apply(
                    state.guard, loss, grad_norm, update_finite=update_finite
                )
                applied = applied.replace(guard=gstate)
                skipped = state.replace(step=state.step + 1, guard=gstate)
                state = jax.tree_util.tree_map(
                    lambda a, b: jnp.where(ok, a, b), applied, skipped
                )
            metrics.update(guard.metrics(gstate, ok, grad_norm))
            return state, metrics

        step = jax.jit(
            train_step,
            donate_argnums=0,
            out_shardings=(self.state_shardings(), None),
            compiler_options=step_compiler_options(self.mesh),
        )
        # Under the name a profile's `XLA Modules` line prints: how a
        # reader of the profile finds this step's table of scopes.
        self._step_program = profiling.StepProgram(
            step, root=type(self.model).__name__
        )
        profiling.step_programs()[f"jit_{train_step.__name__}"] = (
            self._step_program
        )
        return step

    def note_step_arguments(self, state: TrainState, batch) -> None:
        """What `fit()` tells the trainer before its first step: the
        shapes, dtypes and shardings the step really runs with, kept as
        `ShapeDtypeStruct`s for `step_scopes()`."""
        if self._step_program is not None:
            self._step_program.note(state, batch)

    def step_scopes(self, batch=None) -> dict[str, profiling.Scope]:
        """Where each instruction of the compiled train step came from
        (`profiling.program_scopes`): module path, phase, kind. Made on
        demand and kept: the step `make_train_step()` built is lowered and
        compiled at the arguments `fit()` noted: out of jit's own caches
        where they still hold the real step, else compiled again
        (`profiling.StepProgram.text`). A trainer that has not
        stepped gives `abstract_state()` and needs a `batch` (arrays or
        `ShapeDtypeStruct`s)."""
        if self._step_program is None:
            self.make_train_step()
        program = self._step_program
        if batch is not None:
            program.note(self.abstract_state(), batch)
        return program()

    def make_eval_step(self):
        cfg = self.config
        input_key, label_key = self.input_key, self.label_key

        def eval_step(state: TrainState, batch):
            variables = {"params": state.params}
            if state.batch_stats:
                variables["batch_stats"] = state.batch_stats
            if cfg.loss_in_model:
                # The model computes its own objective; no logits ever
                # reach the host side of the step, so loss is the only
                # eval metric on this path. An objective with a further
                # term (a multi-token module's) is no other model's
                # "loss": such a model counts its next-token loss apart
                # (`main_loss`), which is what is reported, the further
                # term beside it.
                loss, counted = state.apply_fn(
                    variables, batch[input_key], train=False,
                    labels=batch[label_key], mutable=["counters"],
                )
                counted = _summed_by_name(counted.get("counters", {}))
                further = {
                    k: counted[k] for k in ("mtp_loss",) if k in counted
                }
                return {"loss": counted.get("main_loss", loss), **further}
            logits = state.apply_fn(variables, batch[input_key], train=False)
            return {
                "loss": softmax_cross_entropy(logits, batch[label_key]),
                "accuracy": jnp.mean(
                    (jnp.argmax(logits, -1) == batch[label_key]).astype(
                        jnp.float32
                    )
                ),
            }

        return jax.jit(eval_step)
