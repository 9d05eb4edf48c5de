"""kftpu-lint program pass: declarative per-program contracts.

The AST rules pin what the SOURCE says; these contracts pin what the
TRACED PROGRAM does — the `testing/hlo.py` accounting (collective
counts, per-buffer all-reduce sizes, jaxpr scan lengths), generalized
from five hand-rolled tests into one table. Each `ProgramContract`
names a program builder (trace the train step, the interleaved
pipeline, the fused flash grad, the serving batch) and the assertions
that hold over its compiled HLO / traced jaxpr:

- collective families expected present / forbidden;
- every all-reduced buffer below a program-specific element cap (the
  scalar-psum-only wire contract, measured not grepped);
- exact kernel-trace counts in the grad jaxpr (fused backward engaged,
  two-pass kernels dead);
- remat no-forward-rerun (the checkpointed grad traces the forward
  kernel exactly as often as the plain grad);
- no quadratic [S, S] buffer anywhere in the traced program;
- schedule-model booleans (`flash_schedule`'s single-KV-pass and
  byte-ratio accounting — the same numbers `bench.py` gates on).

Violations surface as ordinary lint findings with path
``<program:NAME>`` so they ride the same baseline/reporting path as
the AST rules. Tracing is slow (seconds, jax import + compilation), so
the CLI runs this pass only under ``--programs``;
`tests/test_program_contracts.py` runs it in tier-1. Builders need the
test topology (8 virtual CPU devices) — the CLI sets it up before
jax's first import.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Callable

from kubeflow_tpu.ci.lint.engine import Finding


@dataclasses.dataclass(frozen=True)
class Program:
    """What a builder hands the assertion layer."""

    hlo: str | None = None
    jaxpr: str | None = None
    # `name=` of every traced pallas_call (testing.hlo.pallas_kernel_names).
    kernels: tuple[str, ...] = ()
    meta: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class ProgramContract:
    """One row of the contract table. String-valued fields name keys
    in the built program's `meta` dict, so the table stays declarative
    while builders supply the numbers."""

    name: str
    description: str
    build: Callable[[], Program]
    # HLO: collective families that must / must not appear.
    expect_collectives: tuple[str, ...] = ()
    forbid_collectives: tuple[str, ...] = ()
    # HLO: every all-reduced buffer stays under meta[<key>] elements.
    allreduce_cap: str | None = None
    # traced pallas_call names: name prefix -> exact call count.
    kernel_counts: dict = dataclasses.field(default_factory=dict)
    # jaxpr: no shape token matching meta[<key>] (regex) anywhere.
    forbid_jaxpr_shapes: str | None = None
    # meta keys that must be truthy / pairs that must be equal /
    # (container_key, member_key) membership.
    meta_true: tuple[str, ...] = ()
    meta_equal: tuple[tuple[str, str], ...] = ()
    meta_contains: tuple[tuple[str, str], ...] = ()


def _require_devices(n: int) -> None:
    import jax

    if len(jax.devices()) < n:
        raise RuntimeError(
            f"program contracts need >= {n} devices; set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "before jax first imports (the lint CLI and tests/conftest "
            "both do)"
        )


# -- builders ---------------------------------------------------------------


def _build_train_step() -> Program:
    """The classification train step on a dp=2 mesh: cross-replica
    traffic is gradient-sized all-reduce, never activations or
    gathered params."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.parallel import MeshSpec, build_mesh
    from kubeflow_tpu.testing.hlo import compiled_hlo
    from kubeflow_tpu.testing.tinymodels import TinyMLP
    from kubeflow_tpu.train import TrainConfig, Trainer

    _require_devices(2)
    mesh = build_mesh(MeshSpec(dp=2), jax.devices()[:2])
    trainer = Trainer(
        TinyMLP(),
        TrainConfig(
            batch_size=4, total_steps=2, warmup_steps=1, optimizer="sgd"
        ),
        mesh,
        example_input_shape=(4, 8, 8, 1),
    )
    state = trainer.init_state(jax.random.PRNGKey(0))
    step = trainer.make_train_step()
    # Shard the batch the way the data path does — with a replicated
    # batch the partitioner legally replicates the whole step and the
    # contract would be vacuous.
    batch = {
        "image": jax.device_put(
            jnp.zeros((4, 8, 8, 1), jnp.float32),
            trainer.batch_sharding(4),
        ),
        "label": jax.device_put(
            jnp.zeros((4,), jnp.int32), trainer.batch_sharding(1)
        ),
    }
    # Largest parameter buffer: grads are param-shaped, so any
    # all-reduce above this is activations/logits leaking into the
    # cross-dp channel.
    cap = 1 + max(
        leaf.size for leaf in jax.tree_util.tree_leaves(state.params)
    )
    return Program(
        hlo=compiled_hlo(step, state, batch),
        meta={"param_cap": cap},
    )


def _build_pipeline(interleave: int) -> Program:
    """The interleaved pipelined LM loss path (PR 4's wire contract):
    activations move by collective-permute, the only all-reduce near
    activation size is none, and the traced loop is the published
    schedule's."""
    import flax.linen as nn
    import jax

    from kubeflow_tpu.models.transformer import (
        PipelinedTransformerLM,
        TransformerConfig,
    )
    from kubeflow_tpu.parallel import (
        MeshSpec,
        build_mesh,
        pipeline_schedule,
    )
    from kubeflow_tpu.testing.hlo import compiled_hlo, scan_lengths

    _require_devices(2)
    cfg = TransformerConfig(
        vocab_size=64, d_model=16, n_layers=4, n_heads=2, head_dim=8,
        d_ff=16, remat_policy="none", dtype=jax.numpy.float32,
        attention_impl="dense",
    )
    mesh = build_mesh(MeshSpec(dp=1, pp=2), jax.devices()[:2])
    tokens = jax.random.randint(jax.random.PRNGKey(0), (8, 64), 0, 64)
    labels = jax.random.randint(jax.random.PRNGKey(9), (8, 64), 0, 64)
    pipe = PipelinedTransformerLM(
        cfg, n_stages=2 * interleave, num_microbatches=4, mesh=mesh,
        interleave=interleave,
    )
    params = nn.meta.unbox(
        jax.jit(pipe.init)(jax.random.PRNGKey(1), tokens)
    )["params"]

    def loss_grad(p):
        return jax.value_and_grad(
            lambda q: pipe.apply({"params": q}, tokens, labels=labels)
        )(p)

    sched = pipeline_schedule(2 * interleave, 4, interleave)
    return Program(
        hlo=compiled_hlo(jax.jit(loss_grad), params),
        meta={
            # One microbatch's activations: [mb, S, d_model].
            "microbatch_activation": (8 // 4) * 64 * cfg.d_model,
            "scan_lengths": scan_lengths(loss_grad, params),
            "loop_ticks": sched["loop_ticks"],
        },
    )


def _build_fused_flash_grad() -> Program:
    """The flash attention grad at a compact-causal shape: the fused
    one-pass backward engaged (two-pass kernels dead), remat="flash"
    never re-runs the forward kernel, no [S, S] buffer anywhere, the
    fused kernel's ref streams pinned, and the schedule model's
    single-KV-pass + byte-ratio accounting holding."""
    import inspect

    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.transformer import KERNEL_RESULTS
    from kubeflow_tpu.ops import flash
    from kubeflow_tpu.testing.hlo import pallas_kernel_names

    s, block, bh, d = 256, 128, 2, 32
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(keys[0], (1, s, bh, d))
    k = jax.random.normal(keys[1], (1, s, bh, d))
    v = jax.random.normal(keys[2], (1, s, bh, d))

    def loss(q, k, v):
        return jnp.sum(
            flash.flash_attention(
                q, k, v, causal=True, block_q=block, block_k=block,
                interpret=True,
            ).astype(jnp.float32) ** 2
        )

    grads = lambda f: jax.grad(f, argnums=(0, 1, 2))
    # What `remat_policy="flash"` pins round a block whatever its plan
    # admits beside (`_block_cls`).
    pinned = jax.checkpoint_policies.save_only_these_names(*KERNEL_RESULTS)
    grad_ckpt = grads(jax.checkpoint(loss, policy=pinned))
    kernels_plain = pallas_kernel_names(grads(loss), q, k, v)
    kernels_ckpt = pallas_kernel_names(grad_ckpt, q, k, v)
    fwd_count = lambda names: sum(n.startswith("flash_fwd_") for n in names)

    sched = flash.flash_schedule(s, s, block_q=block, block_k=block)
    # Byte-model accounting at the deep-triangle flagship shape (the
    # bench-gated regime, nq >= 8, default 1024-blocks so the lse rides
    # packed): the ratio approaches 1/2 as the triangle deepens and
    # only means anything there.
    deep = flash.flash_schedule(16384, 16384)
    noncausal = flash.flash_schedule(16384, 16384, causal=False)
    refs = [
        p
        for p in inspect.signature(flash._dqkv_kernel_fused).parameters
        if p.endswith("_ref")
    ]
    return Program(
        jaxpr=str(jax.make_jaxpr(grad_ckpt)(q, k, v)),
        kernels=tuple(kernels_ckpt),
        meta={
            "seq_shape": rf"\[(?:\d+,)*{s},{s}\]",
            "fwd_count_plain": fwd_count(kernels_plain),
            "fwd_count_ckpt": fwd_count(kernels_ckpt),
            "bwd_fused": sched["bwd_fused"],
            "single_kv_pass": (
                sched["bwd_total_grid_steps"] == sched["grid_steps"]
            ),
            "deep_fused": deep["bwd_fused"],
            "deep_single_kv_pass": (
                deep["bwd_total_grid_steps"] == deep["grid_steps"]
            ),
            "noncausal_two_pass": (
                not noncausal["bwd_fused"]
                and noncausal["bwd_total_grid_steps"]
                == 2 * noncausal["grid_steps"]
            ),
            "byte_model_ok": (
                deep["bwd_hbm_bytes_fused"]
                <= 0.62 * deep["bwd_hbm_bytes_two_pass"]
            ),
            "streams_pinned": refs
            == [
                "rows_ref", "cols_ref", "q_ref", "k_ref", "v_ref",
                "do_ref", "lse_ref", "delta_ref", "dq_ref", "dk_ref",
                "dv_ref",
            ],
        },
    )


def _build_elastic_resize_step() -> Program:
    """The train step traced on a SHRUNK mesh after an elastic resize
    (ISSUE 9): the steady-state step must be indistinguishable from a
    fresh dp train step — gradient-sized all-reduce only. The resize
    transition's resharding traffic (device_put across device sets)
    happens ONCE at the boundary and must not leak a collective
    (all-gather / collective-permute / all-to-all) into the compiled
    per-step program, or every post-resize step pays for the one-time
    move."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.parallel import MeshSpec, build_mesh
    from kubeflow_tpu.testing.hlo import compiled_hlo
    from kubeflow_tpu.testing.tinymodels import TinyMLP
    from kubeflow_tpu.train import TrainConfig, Trainer

    _require_devices(4)
    mesh4 = build_mesh(MeshSpec(dp=4), jax.devices()[:4])
    trainer4 = Trainer(
        TinyMLP(),
        TrainConfig(
            batch_size=8, total_steps=2, warmup_steps=1,
            optimizer="sgd", fsdp_params=False,
        ),
        mesh4,
        example_input_shape=(8, 8, 8, 1),
    )
    state4 = trainer4.init_state(jax.random.PRNGKey(0))
    # The elastic transition under test: resize 4 -> 2, live reshard.
    mesh2 = build_mesh(MeshSpec(dp=2), jax.devices()[:2])
    trainer2 = trainer4.resize(mesh2)
    state2 = trainer2.reshard_state(state4)
    step = trainer2.make_train_step()
    batch = {
        "image": jax.device_put(
            jnp.zeros((8, 8, 8, 1), jnp.float32),
            trainer2.batch_sharding(4),
        ),
        "label": jax.device_put(
            jnp.zeros((8,), jnp.int32), trainer2.batch_sharding(1)
        ),
    }
    cap = 1 + max(
        leaf.size for leaf in jax.tree_util.tree_leaves(state2.params)
    )
    shrunk_devices = set(mesh2.devices.reshape(-1))
    return Program(
        hlo=compiled_hlo(step, state2, batch),
        meta={
            "param_cap": cap,
            # The resharded state actually LIVES on the shrunk mesh —
            # a reshard that silently kept old-mesh residency would
            # make every step a cross-mesh fetch.
            "state_on_shrunk_mesh": all(
                set(leaf.sharding.device_set) <= shrunk_devices
                for leaf in jax.tree_util.tree_leaves(state2)
            ),
        },
    )


def _build_serving_batch() -> Program:
    """One servable bucket execution: a single-device program — no
    collective of any family may appear (a sharded-serving refactor
    that silently leaves one in costs every request a device fence).

    Also pins the binary wire path (ISSUE 15): the tensor-frame
    encode/decode in `serving/wire.py` and the server's binary request/
    response helpers must never regrow a ``tolist()`` or a per-element
    JSON encode — that text round-trip is exactly the overhead the
    protocol removed (docs/perf.md §serving wire path)."""
    import ast as ast_mod
    import pathlib

    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.serving import server as server_mod
    from kubeflow_tpu.serving import wire as wire_mod
    from kubeflow_tpu.serving.servable import Servable
    from kubeflow_tpu.testing.hlo import compiled_hlo
    from kubeflow_tpu.testing.tinymodels import TinyMLP

    binary_fns = {
        wire_mod.__file__: {"encode_tensor", "decode_tensor"},
        server_mod.__file__: {
            "_binary_instances", "_binary_prediction_response",
        },
    }
    found: set = set()
    text_hops: list[str] = []
    for path, names in binary_fns.items():
        tree = ast_mod.parse(pathlib.Path(path).read_text())
        for node in ast_mod.walk(tree):
            if (
                isinstance(node, ast_mod.FunctionDef)
                and node.name in names
            ):
                found.add(node.name)
                for sub in ast_mod.walk(node):
                    if isinstance(sub, ast_mod.Attribute) and sub.attr in (
                        "tolist", "dumps", "loads",
                    ):
                        text_hops.append(f"{node.name}: .{sub.attr}")
                    if isinstance(sub, ast_mod.Name) and sub.id == "json":
                        text_hops.append(f"{node.name}: json")

    model = TinyMLP()
    x = jnp.zeros((4, 8, 8, 1), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x)
    sv = Servable(
        name="contract", apply_fn=model.apply, variables=variables,
        max_batch=4,
    )
    return Program(
        hlo=compiled_hlo(sv._jitted, sv.variables, x),
        meta={
            # All four functions found (a rename would silently exempt
            # them from the scan) and none round-trips through text.
            "binary_wire_clean": (
                not text_hops
                and found == set().union(*binary_fns.values())
            ),
            "text_hops": text_hops,
        },
    )


def _build_serving_batch_continuous() -> Program:
    """The continuous-batching flush step (ISSUE 11): late admission
    actually engages (a request arriving after the cut rides the group
    that is about to execute), turning it off restores cut-and-wait, the
    executed bucket program still carries zero collectives, and the
    flush path performs no host sync (no block_until_ready/device_get —
    a sync in the scheduler loop would serialize every flush against
    device completion)."""
    import ast as ast_mod
    import pathlib
    import threading
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.serving import batching as batching_mod
    from kubeflow_tpu.serving.batching import BatchingConfig, BatchingQueue
    from kubeflow_tpu.serving.servable import Servable
    from kubeflow_tpu.testing.hlo import compiled_hlo
    from kubeflow_tpu.testing.tinymodels import TinyMLP

    def drive(continuous: bool) -> list[tuple[int, int]]:
        """Choreograph a flush: group X (width 2) blocks mid-execution
        while a second width-3 request arrives; with continuous batching
        it must ride group Y's execution in the SAME flush window.
        Returns (signature_width, batch_rows) per servable call."""
        gate = threading.Event()
        x_running = threading.Event()
        calls: list[tuple[int, int]] = []

        class _Probe:
            name = "contract-continuous"
            version = 1

            def predict(self, batch):
                arr = np.asarray(batch)
                calls.append((arr.shape[1], arr.shape[0]))
                if arr.shape[1] == 2:
                    x_running.set()
                    gate.wait(10)
                return arr

        queue = BatchingQueue(
            _Probe(),
            BatchingConfig(
                max_batch=2, timeout_ms=2000.0, continuous=continuous
            ),
        )

        def wait_for_depth(n: int) -> None:
            deadline = time.monotonic() + 10
            while queue.stats()["queue_depth"] != n:
                if time.monotonic() > deadline:
                    raise TimeoutError("batching choreography stalled")
                time.sleep(0.001)

        threads = []

        def submit(width: int) -> None:
            t = threading.Thread(
                target=queue.predict,
                args=(np.zeros((1, width), np.float32),),
                daemon=True,
            )
            t.start()
            threads.append(t)

        submit(2)            # x1 — pending first, so group X runs first
        wait_for_depth(1)
        submit(3)            # y1 — fills max_batch, cuts the flush
        if not x_running.wait(10):
            raise TimeoutError("group X never started executing")
        submit(3)            # y2 — arrives AFTER the cut
        wait_for_depth(1)    # ... and sits pending
        gate.set()           # group Y executes next: late-admits y2?
        for t in threads:
            t.join(timeout=10)
        queue.close()
        return calls

    continuous_calls = drive(continuous=True)
    cutwait_calls = drive(continuous=False)

    # AST scan of the flush path: every scheduler-side function must be
    # present (a rename would silently exempt it) and free of host sync.
    flush_fns = {
        "_take_batch", "_cut_locked", "_admit_late",
        "_record_wait_locked", "_loop", "_run_group",
    }
    tree = ast_mod.parse(
        pathlib.Path(batching_mod.__file__).read_text()
    )
    found: set = set()
    syncs: list[str] = []
    for node in ast_mod.walk(tree):
        if (
            isinstance(node, ast_mod.FunctionDef)
            and node.name in flush_fns
        ):
            found.add(node.name)
            for sub in ast_mod.walk(node):
                if isinstance(sub, ast_mod.Attribute) and sub.attr in (
                    "block_until_ready", "device_get", "device_put",
                ):
                    syncs.append(f"{node.name}: .{sub.attr}")
                if isinstance(sub, ast_mod.Name) and sub.id == "jax":
                    syncs.append(f"{node.name}: jax")

    # The program the flush executes — one servable bucket at the merged
    # window size; the wire contract is unchanged by late admission.
    model = TinyMLP()
    x = jnp.zeros((4, 8, 8, 1), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x)
    sv = Servable(
        name="contract", apply_fn=model.apply, variables=variables,
        max_batch=4,
    )
    return Program(
        hlo=compiled_hlo(sv._jitted, sv.variables, x),
        meta={
            # y1+y2 merged into one width-3 execution of 2 rows.
            "continuous_admitted": (3, 2) in continuous_calls,
            # Off restores cut-and-wait: y2 runs in its own later flush.
            "cut_and_wait_no_late": (3, 2) not in cutwait_calls
            and cutwait_calls.count((3, 1)) == 2,
            "no_host_sync_in_flush": not syncs and found == flush_fns,
            "host_syncs": syncs,
        },
    )


def _build_serving_multiplex_registry() -> Program:
    """The per-model queue path (ISSUE 17): a multiplexed replica runs
    the SAME bucket program as a single-model one — the registry only
    routes to a per-model `BatchingQueue`, so zero collectives may
    appear, and the registry's hot path (predict → `_resident_queue` →
    `_page_in` → LRU eviction) must stay free of host sync. A
    `block_until_ready` in `_page_in` would stall every model behind a
    cold one's weight load; one in `predict` would fence every request
    on device completion."""
    import ast as ast_mod
    import pathlib

    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.serving import registry as registry_mod
    from kubeflow_tpu.serving.batching import BatchingConfig
    from kubeflow_tpu.serving.registry import PagingConfig, ServableRegistry
    from kubeflow_tpu.serving.servable import Servable
    from kubeflow_tpu.testing.hlo import compiled_hlo
    from kubeflow_tpu.testing.tinymodels import TinyMLP

    hot_fns = {
        "predict", "_resident_queue", "_page_in", "_claim_load_locked",
        "_evict_locked", "_demote_locked",
    }
    tree = ast_mod.parse(
        pathlib.Path(registry_mod.__file__).read_text()
    )
    found: set = set()
    syncs: list[str] = []
    for node in ast_mod.walk(tree):
        if (
            isinstance(node, ast_mod.FunctionDef)
            and node.name in hot_fns
        ):
            found.add(node.name)
            for sub in ast_mod.walk(node):
                if isinstance(sub, ast_mod.Attribute) and sub.attr in (
                    "block_until_ready", "device_get", "device_put",
                ):
                    syncs.append(f"{node.name}: .{sub.attr}")
                if isinstance(sub, ast_mod.Name) and sub.id == "jax":
                    syncs.append(f"{node.name}: jax")

    # The bucket program a paged-in model executes — built through the
    # registry's own factory path, so the HLO is the one the per-model
    # queue actually flushes.
    model = TinyMLP()
    x = jnp.zeros((4, 8, 8, 1), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x)

    def factory(rspec: dict) -> Servable:
        return Servable(
            name=rspec["model"], apply_fn=model.apply,
            variables=variables, max_batch=4,
        )

    reg = ServableRegistry(
        factory,
        batching=BatchingConfig(max_batch=4, timeout_ms=2.0),
        paging=PagingConfig(max_resident=1),
    )
    try:
        reg.ensure({"model": "contract-mux"})
        reg.predict("contract-mux", x[:1])  # page-in + one flush
        sv = reg._entries["contract-mux"].queue.servable
        hlo = compiled_hlo(sv._jitted, sv.variables, x)
    finally:
        reg.close()
    return Program(
        hlo=hlo,
        meta={
            "no_host_sync_in_registry": not syncs and found == hot_fns,
            "host_syncs": syncs,
        },
    )


def _build_rl_learner_step() -> Program:
    """The RL learner is the stock Trainer on a dp mesh (ISSUE 12):
    its compiled step must be indistinguishable from any other dp train
    step — gradient-sized all-reduce only. Trajectory ingestion,
    serving traffic, and publication all live OFF the device program."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.parallel import MeshSpec, build_mesh
    from kubeflow_tpu.rl.env import EnvConfig
    from kubeflow_tpu.rl.loop import RLConfig, build_learner
    from kubeflow_tpu.testing.hlo import compiled_hlo

    _require_devices(2)
    cfg = RLConfig(
        env=EnvConfig(seed=0, obs_dim=8, n_actions=4, n_envs=8, horizon=4),
        hidden=16,
        total_steps=4,
    )
    mesh = build_mesh(MeshSpec(dp=2), jax.devices()[:2])
    trainer = build_learner(cfg, mesh)
    state = trainer.init_state(jax.random.PRNGKey(0))
    step = trainer.make_train_step()
    b = cfg.batch_size
    batch = {
        "obs": jax.device_put(
            jnp.zeros((b, cfg.env.obs_dim), jnp.float32),
            trainer.batch_sharding(2),
        ),
        "target": jax.device_put(
            jnp.zeros((b, 2), jnp.float32), trainer.batch_sharding(2)
        ),
    }
    cap = 1 + max(
        leaf.size for leaf in jax.tree_util.tree_leaves(state.params)
    )
    return Program(
        hlo=compiled_hlo(step, state, batch),
        meta={"param_cap": cap},
    )


def _build_rl_actor_policy() -> Program:
    """The actor side of the actor–learner split: the policy program
    the serving replicas execute is single-device (zero collectives —
    actors scale by adding replicas, never by sharding a rollout), and
    the host-side acting loop (`_actor_loop`, `rollout`,
    `sample_actions`) is numpy-only — no jax, no device sync. A
    `block_until_ready` in the acting path would serialize every
    rollout against device completion and the Sebulba split would
    quietly degrade to lockstep."""
    import ast as ast_mod
    import pathlib

    import jax.numpy as jnp

    from kubeflow_tpu.rl import env as env_mod
    from kubeflow_tpu.rl import loop as loop_mod
    from kubeflow_tpu.rl.policy import (
        init_policy_variables,
        make_policy_servable,
    )
    from kubeflow_tpu.testing.hlo import compiled_hlo

    servable = make_policy_servable(
        "contract-policy",
        init_policy_variables(obs_dim=8, n_actions=4, hidden=16),
        version=1,
        n_actions=4,
        hidden=16,
        max_batch=8,
    )

    acting_fns = {
        loop_mod.__file__: {"_actor_loop"},
        env_mod.__file__: {"rollout", "sample_actions"},
    }
    found: set = set()
    syncs: list[str] = []
    for path, fns in acting_fns.items():
        tree = ast_mod.parse(pathlib.Path(path).read_text())
        for node in ast_mod.walk(tree):
            if (
                isinstance(node, ast_mod.FunctionDef)
                and node.name in fns
            ):
                found.add(node.name)
                for sub in ast_mod.walk(node):
                    if isinstance(sub, ast_mod.Attribute) and sub.attr in (
                        "block_until_ready", "device_get", "device_put",
                    ):
                        syncs.append(f"{node.name}: .{sub.attr}")
                    if isinstance(sub, ast_mod.Name) and sub.id == "jax":
                        syncs.append(f"{node.name}: jax")

    return Program(
        hlo=compiled_hlo(
            servable._jitted,
            servable.variables,
            jnp.zeros((8, 8), jnp.float32),
        ),
        meta={
            "no_host_sync_in_acting": (
                not syncs
                and found == {"_actor_loop", "rollout", "sample_actions"}
            ),
            "host_syncs": syncs,
        },
    )


# -- the table --------------------------------------------------------------

CONTRACTS: tuple[ProgramContract, ...] = (
    ProgramContract(
        name="train-step-dp",
        description="dp train step: grad-sized all-reduce only",
        build=_build_train_step,
        expect_collectives=("all-reduce",),
        forbid_collectives=("all-to-all",),
        allreduce_cap="param_cap",
    ),
    ProgramContract(
        name="pipeline-wire-v1",
        description="GPipe loss path: ppermute + scalar psum only",
        build=lambda: _build_pipeline(1),
        expect_collectives=("collective-permute",),
        allreduce_cap="microbatch_activation",
        meta_contains=(("scan_lengths", "loop_ticks"),),
    ),
    ProgramContract(
        name="pipeline-wire-v2",
        description="interleaved loss path: same wire contract, "
        "v2 schedule ticks",
        build=lambda: _build_pipeline(2),
        expect_collectives=("collective-permute",),
        allreduce_cap="microbatch_activation",
        meta_contains=(("scan_lengths", "loop_ticks"),),
    ),
    ProgramContract(
        name="fused-flash-grad",
        description="fused one-pass backward engaged; remat never "
        "re-runs the forward kernel; no [S,S] buffer",
        build=_build_fused_flash_grad,
        kernel_counts={
            "flash_bwd_fused": 1,
            "flash_dq_": 0,
            "flash_dkv_": 0,
        },
        forbid_jaxpr_shapes="seq_shape",
        meta_true=(
            "bwd_fused", "single_kv_pass", "deep_fused",
            "deep_single_kv_pass", "noncausal_two_pass",
            "byte_model_ok", "streams_pinned",
        ),
        meta_equal=(("fwd_count_ckpt", "fwd_count_plain"),),
    ),
    ProgramContract(
        name="elastic-resize",
        description="post-resize step on the shrunk mesh: grad-sized "
        "all-reduce only, no resharding collective in steady state",
        build=_build_elastic_resize_step,
        expect_collectives=("all-reduce",),
        forbid_collectives=(
            "all-gather", "reduce-scatter", "all-to-all",
            "collective-permute",
        ),
        allreduce_cap="param_cap",
        meta_true=("state_on_shrunk_mesh",),
    ),
    ProgramContract(
        name="serving-batch",
        description="servable bucket program: zero collectives; binary "
        "wire path free of tolist/JSON text hops",
        build=_build_serving_batch,
        forbid_collectives=(
            "all-gather", "reduce-scatter", "all-reduce",
            "collective-permute", "all-to-all",
        ),
        meta_true=("binary_wire_clean",),
    ),
    ProgramContract(
        name="serving-multiplex",
        description="per-model queue path: same zero-collective bucket "
        "program; registry hot path free of host sync",
        build=_build_serving_multiplex_registry,
        forbid_collectives=(
            "all-gather", "reduce-scatter", "all-reduce",
            "collective-permute", "all-to-all",
        ),
        meta_true=("no_host_sync_in_registry",),
    ),
    ProgramContract(
        name="rl-learner-step",
        description="RL learner step: grad-sized all-reduce only",
        build=_build_rl_learner_step,
        expect_collectives=("all-reduce",),
        forbid_collectives=(
            "all-gather", "all-to-all", "collective-permute",
        ),
        allreduce_cap="param_cap",
    ),
    ProgramContract(
        name="rl-actor-learner",
        description="actor policy program: zero collectives; acting "
        "loop free of host sync",
        build=_build_rl_actor_policy,
        forbid_collectives=(
            "all-gather", "reduce-scatter", "all-reduce",
            "collective-permute", "all-to-all",
        ),
        meta_true=("no_host_sync_in_acting",),
    ),
    ProgramContract(
        name="serving-batch-continuous",
        description="continuous-batching flush: late admission "
        "engages, zero collectives, no host sync in the flush path",
        build=_build_serving_batch_continuous,
        forbid_collectives=(
            "all-gather", "reduce-scatter", "all-reduce",
            "collective-permute", "all-to-all",
        ),
        meta_true=(
            "continuous_admitted", "cut_and_wait_no_late",
            "no_host_sync_in_flush",
        ),
    ),
)


# -- the runner -------------------------------------------------------------


def check_contract(contract: ProgramContract) -> list[Finding]:
    """Build the program and evaluate every declarative assertion;
    returns findings (empty = contract holds)."""
    from kubeflow_tpu.testing.hlo import (
        allreduce_element_counts,
        collective_counts,
    )

    path = f"<program:{contract.name}>"
    out: list[Finding] = []

    def fail(msg: str) -> None:
        out.append(Finding(path, 0, "program-contract", msg))

    try:
        prog = contract.build()
    except Exception as e:  # surface, don't crash the whole run
        fail(f"builder raised {type(e).__name__}: {e}")
        return out

    if contract.expect_collectives or contract.forbid_collectives:
        counts = collective_counts(prog.hlo or "")
        for op in contract.expect_collectives:
            if not counts.get(op):
                fail(
                    f"expected {op!r} in compiled HLO but found none "
                    f"(counts: {counts}) — the sharding silently "
                    "degenerated"
                )
        for op in contract.forbid_collectives:
            if counts.get(op):
                fail(
                    f"forbidden {op!r} appears {counts[op]}x in "
                    "compiled HLO — the program materializes what it "
                    "should stream"
                )
    if contract.allreduce_cap is not None:
        cap = prog.meta[contract.allreduce_cap]
        big = [
            n for n in allreduce_element_counts(prog.hlo or "") if n >= cap
        ]
        if big:
            fail(
                f"all-reduce of {big} elements >= "
                f"{contract.allreduce_cap}={cap} — the scalar/grad-only "
                "wire contract regressed"
            )
    for prefix, want_n in sorted(contract.kernel_counts.items()):
        got = sum(name.startswith(prefix) for name in prog.kernels)
        if got != want_n:
            fail(
                f"program traces {got} {prefix!r}* kernel call(s) "
                f"{list(prog.kernels)}, contract says {want_n}"
            )
    if contract.forbid_jaxpr_shapes is not None:
        rx = prog.meta[contract.forbid_jaxpr_shapes]
        hits = sorted(set(re.findall(rx, prog.jaxpr or "")))
        if hits:
            fail(
                f"quadratic buffer shape(s) {hits} in the traced "
                "program — the score matrix is materializing"
            )
    for key in contract.meta_true:
        if not prog.meta.get(key):
            fail(f"`{key}` is falsy: {prog.meta.get(key)!r}")
    for a, b in contract.meta_equal:
        if prog.meta[a] != prog.meta[b]:
            fail(f"`{a}`={prog.meta[a]!r} != `{b}`={prog.meta[b]!r}")
    for container, member in contract.meta_contains:
        if prog.meta[member] not in prog.meta[container]:
            fail(
                f"`{member}`={prog.meta[member]!r} not in "
                f"`{container}`={prog.meta[container]!r}"
            )
    return out


def run_contract(name: str) -> None:
    """Assert one contract holds — the thin-wrapper entry point tests
    keep their historical names on."""
    by_name = {c.name: c for c in CONTRACTS}
    findings = check_contract(by_name[name])
    assert not findings, "\n".join(f.render() for f in findings)


def contract_findings() -> list[Finding]:
    """Every contract, as lint findings (the `--programs` backend)."""
    out: list[Finding] = []
    for contract in CONTRACTS:
        out.extend(check_contract(contract))
    return out
