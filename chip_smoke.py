#!/usr/bin/env python3
"""Does the system start on the chip? One process, one TPU chip, a few minutes.

    python3 chip_smoke.py            # one chip: device, kernels, train, serve
    python3 chip_smoke.py --chips 4  # four chips: sharded steps and a routed fleet only

Drives the two main paths through the entry points a user calls, at the full
width of models the repo supports (depth may be cut; weights are random, from
a fixed seed), and checks what comes out by the repo's own means:

- **device**: JAX's default backend is a TPU, else the run fails at once.
  There is no CPU mode here; `tests/test_chip_smoke.py` calls the phase
  functions below at tiny sizes with the Pallas interpreter instead.
- **kernels**: `flash_attention` forward and gradient, compiled, bf16, against
  `dense_attention` in float32 on the same inputs.
- **train**: the 350M LM exactly as `bench.py` builds it, through `fit()` with
  a `Checkpointer` (save, `restore_latest`), at S=2048, 8192 and 16384; the
  compiled step's text must hold the Pallas kernels (`tpu_custom_call`).
- **serve**: ResNet-50 at 224 px from a checkpoint, the way
  `python -m kubeflow_tpu.serving --model resnet=<dir>` loads it, behind HTTP on
  localhost in a thread of this process; JSON and `KFT1` binary `:predict`
  requests in different batch buckets, against `module.apply`.

With `--chips 4` none of that runs. Instead: the LM step on dp=2 x tp=2 (flash
under `shard_map`) and on sp=4 (`ring_flash_attention`) against the one-chip
step in the same process, with parameters and batch shown to be spread over
the chips; then four one-chip `Servable`s behind `Router`.

Any phase that fails makes the exit code non-zero. The last line of standard
output is one JSON object, `{"ok": ..., "device": {"platform", "kind",
"count"}}`; everything else worth seeing is printed on earlier lines. Times
printed here are smoke observations on a cold process, not benchmark results.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import gc
import json
import statistics
import sys
import tempfile
import threading
import time
import traceback
import urllib.request

SEED = 0

# The 350M LM of `bench.py::bench_lm`: vocab 32 000, d_model 1024, 16 layers,
# 8 heads x 128, d_ff 4096, attention_impl="auto", adamw, bf16.
LM_350M = dict(
    vocab_size=32_000, d_model=1024, n_layers=16, n_heads=8, head_dim=128,
    d_ff=4096,
)
# (seq_len, batch, remat_policy, steps): the shapes `python bench.py` reaches.
TRAIN_RUNS = ((2048, 8, "none", 6), (8192, 2, "none", 3), (16384, 2, "mlp", 3))


def say(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def bwd_schedule(names) -> str:
    """Which flash backward a traced program runs, from its kernel names."""
    fused = "flash_bwd_fused" in names
    two_pass = any(n.startswith(("flash_dq_", "flash_dkv_")) for n in names)
    if fused and two_pass:
        return "mixed"
    return "fused" if fused else "two-pass" if two_pass else "none"


# -- device -------------------------------------------------------------------


class SmokeFailure(RuntimeError):
    def __init__(self, device, msg: str):
        super().__init__(msg)
        self.device = device


def phase_device(chips: int) -> dict:
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    say("device", **device)
    if device["platform"] != "tpu":
        raise SmokeFailure(
            device, f"default backend is {device['platform']!r}, not a TPU"
        )
    if device["count"] < chips:
        raise SmokeFailure(
            device, f"--chips {chips} needs {chips} devices, "
            f"JAX reports {device['count']}"
        )
    import flax
    import jaxlib
    import optax
    import orbax.checkpoint as ocp

    from kubeflow_tpu.sidecar.controller import default_device_probe

    say(
        "versions", python=sys.version.split()[0], jax=jax.__version__,
        jaxlib=jaxlib.__version__, flax=flax.__version__,
        optax=optax.__version__, orbax=ocp.__version__,
    )
    # The sidecar gates a worker on the chips' device nodes (it must not
    # ask JAX, which would take the chip): shown here beside what JAX sees.
    say("device", sidecar_device_probe=default_device_probe())
    return device


# -- kernels ------------------------------------------------------------------


def phase_kernels(
    shapes=((2, 8, 2048), (1, 1, 16384)),
    head_dim: int = 128,
    dtype="bfloat16",
    interpret: bool | None = None,
    rel_tol: float = 5e-2,
    **flash_kw,
) -> list[dict]:
    """`flash_attention` forward and gradient against `dense_attention` in
    float32 on the same inputs, per (batch, heads, seq) shape; each of o,
    dq, dk, dv must be within `rel_tol` x max(1, max|reference|) — bf16
    rounding, where a wrong kernel is off by the reference's own size. With
    `interpret=None` the kernels are compiled for the device, and the
    compiled program must hold them."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.ops.attention import dense_attention
    from kubeflow_tpu.ops.flash import flash_attention, flash_schedule
    from kubeflow_tpu.testing.hlo import pallas_kernel_names, tpu_kernel_calls

    rows = []
    for b, h, s in shapes:
        keys = jax.random.split(jax.random.PRNGKey(SEED + s), 4)
        q, k, v, g = (
            jax.random.normal(kx, (b, s, h, head_dim), jnp.float32).astype(dtype)
            for kx in keys
        )
        f32 = lambda x: x.astype(jnp.float32)

        def value_and_grads(attn):
            def loss(q, k, v, g):
                o = attn(q, k, v)
                return jnp.sum(f32(o) * f32(g)), o

            return jax.jit(
                jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)
            )

        flash = value_and_grads(functools.partial(
            flash_attention, causal=True, interpret=interpret, **flash_kw
        ))
        dense = value_and_grads(
            functools.partial(dense_attention, causal=True)
        )
        names = pallas_kernel_names(flash, q, k, v, g)
        t0 = time.perf_counter()
        compiled = flash.lower(q, k, v, g).compile()
        compile_s = time.perf_counter() - t0
        n_calls = tpu_kernel_calls(compiled.as_text())
        if interpret is not True and n_calls != len(names):
            raise AssertionError(
                f"kernels S={s}: traced {names} but the compiled program "
                f"holds {n_calls} tpu_custom_call(s) — not compiled kernels"
            )
        (_, o), grads = compiled(q, k, v, g)
        # The reference multiplies in float32 too, not in the MXU's
        # default single bf16 pass.
        with jax.default_matmul_precision("highest"):
            (_, o_ref), grads_ref = dense(f32(q), f32(k), f32(v), g)
        errs = {}
        for name, got, want in zip(
            ("o", "dq", "dk", "dv"), (o, *grads), (o_ref, *grads_ref)
        ):
            got, want = f32(got), f32(want)
            if not bool(jnp.all(jnp.isfinite(got))):
                raise AssertionError(f"kernels S={s}: non-finite {name}")
            err = float(jnp.max(jnp.abs(got - want)))
            scale = max(1.0, float(jnp.max(jnp.abs(want))))
            errs[name] = err
            if err > rel_tol * scale:
                raise AssertionError(
                    f"kernels S={s}: max|{name} - dense f32| = {err:.4g} "
                    f"> {rel_tol} x {scale:.4g}"
                )
        sched = flash_schedule(
            s, s, head_dim=head_dim, dtype_bytes=jnp.dtype(dtype).itemsize,
            **{k_: v_ for k_, v_ in flash_kw.items() if k_.startswith("block")},
        )
        if sched["bwd_fused"] != (bwd_schedule(names) == "fused"):
            raise AssertionError(
                f"kernels S={s}: flash_schedule says bwd_fused="
                f"{sched['bwd_fused']} but the traced kernels are {names}"
            )
        row = dict(
            B=b, H=h, S=s, d=head_dim, kernels=",".join(names),
            bwd=bwd_schedule(names), compiled_kernel_calls=n_calls,
            compile_s=round(compile_s, 2),
            **{f"max_err_{n}": f"{e:.3g}" for n, e in errs.items()},
        )
        say("kernels", **row)
        rows.append(row)
    return rows


# -- train --------------------------------------------------------------------


def _lm_trainer(model: dict, seq_len: int, batch: int, remat: str, mesh,
                attention_impl: str, guard=None, **cfg_kw):
    import jax.numpy as jnp

    from kubeflow_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )
    from kubeflow_tpu.train import SyntheticTokens, TrainConfig, Trainer

    cfg = TransformerConfig(
        **model, attention_impl=attention_impl, remat_policy=remat, **cfg_kw
    )
    config = TrainConfig(
        batch_size=batch, learning_rate=3e-4, total_steps=10_000,
        optimizer="adamw", label_smoothing=0.0, fsdp_params=False,
        train_metrics="loss",
    )
    trainer = Trainer(
        TransformerLM(cfg, mesh=mesh), config, mesh,
        example_input_shape=(2, seq_len), example_input_dtype=jnp.int32,
        input_key="tokens", label_key="labels", guard=guard,
    )
    data = SyntheticTokens(
        mesh, batch_size=batch, seq_len=seq_len, vocab_size=cfg.vocab_size
    )
    return trainer, data


def _compile_step(trainer, data):
    """AOT-compile the trainer's step for the shapes `data` yields.
    Returns (seconds, compiled text, traced kernel names)."""
    import jax

    from kubeflow_tpu.testing.hlo import jaxpr_kernel_names

    batch = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        next(iter(data)),
    )
    traced = trainer.make_train_step().trace(trainer.abstract_state(), batch)
    names = jaxpr_kernel_names(traced.jaxpr.jaxpr)
    lowered = traced.lower()
    t0 = time.perf_counter()
    compiled = lowered.compile()
    return time.perf_counter() - t0, compiled.as_text(), names


def _tree_checksum(tree) -> float:
    import jax
    import jax.numpy as jnp

    return float(sum(
        jnp.sum(jnp.abs(x.astype(jnp.float32)))
        for x in jax.tree_util.tree_leaves(tree)
        if jnp.issubdtype(x.dtype, jnp.floating)
    ))


def _check_history(tag: str, history: list[dict], steps: int) -> list[float]:
    import math

    losses = [rec["loss"] for rec in history]
    if len(losses) != steps or not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"{tag}: losses {losses} over {steps} steps")
    # The synthetic batch repeats and the warmup schedule starts at lr 0,
    # so steps 1 and 2 see the same parameters: the loss moves from the
    # third step on. Hence every run takes at least three.
    if steps < 3 or len(set(losses)) < 2:
        raise AssertionError(f"{tag}: loss does not change: {losses}")
    return losses


def phase_train(
    model: dict = LM_350M,
    runs=TRAIN_RUNS,
    attention_impl: str = "auto",
    expect_compiled_kernels: bool = True,
    **cfg_kw,
) -> list[dict]:
    """A few steps of the LM through `fit()` at each (seq, batch, remat,
    steps) of `runs`, on one device. The first run also goes through a
    `Checkpointer` (the first step's save, the final save, `restore_latest`)
    and compiles its step twice to show the compile cache cold and warm."""
    import jax

    from kubeflow_tpu.ops.flash import flash_schedule
    from kubeflow_tpu.parallel import MeshSpec, build_mesh
    from kubeflow_tpu.testing.hlo import tpu_kernel_calls
    from kubeflow_tpu.train import Checkpointer, fit

    mesh = build_mesh(MeshSpec(), jax.devices()[:1])
    layers = model["n_layers"]
    rows = []
    for i, (seq_len, batch, remat, steps) in enumerate(runs):
        tag = f"train S={seq_len}"
        trainer, data = _lm_trainer(
            model, seq_len, batch, remat, mesh, attention_impl, **cfg_kw
        )
        cold_s, text, names = _compile_step(trainer, data)
        n_calls = tpu_kernel_calls(text)
        schedule = bwd_schedule(names)
        # `auto` must not have taken the dense branch, and nothing may have
        # run interpreted: every traced kernel is a tpu_custom_call.
        if not names:
            raise AssertionError(f"{tag}: no Pallas kernel in the traced step")
        if expect_compiled_kernels and n_calls != len(names):
            raise AssertionError(
                f"{tag}: traced {len(names)} kernels, compiled step holds "
                f"{n_calls} tpu_custom_call(s)"
            )
        fused = flash_schedule(
            seq_len, seq_len, head_dim=model["head_dim"], dtype_bytes=2
        )["bwd_fused"]
        if fused != (schedule == "fused"):
            raise AssertionError(
                f"{tag}: flash_schedule bwd_fused={fused}, traced {schedule}"
            )
        row = dict(
            S=seq_len, batch=batch, remat=remat, layers=layers,
            kernels_traced=len(names), compiled_kernel_calls=n_calls,
            bwd=schedule, compile_cold_s=round(cold_s, 1),
        )
        # Only the first run checkpoints (2.8 GB of state at full size).
        with (
            tempfile.TemporaryDirectory(prefix="chip_smoke_train_")
            if i == 0 else contextlib.nullcontext()
        ) as ckpt_dir:
            checkpointer = None
            if ckpt_dir is not None:
                # A second jit of the same step finds the first's program
                # in the persistent cache: the warm figure.
                warm_s, _, _ = _compile_step(trainer, data)
                row["compile_warm_s"] = round(warm_s, 1)
                checkpointer = Checkpointer(
                    ckpt_dir, save_interval_steps=steps
                )
            t0 = time.perf_counter()
            result = fit(
                trainer, data, steps, rng=jax.random.PRNGKey(SEED),
                checkpointer=checkpointer, log_every=1, handle_signals=False,
            )
            fit_s = time.perf_counter() - t0
            losses = _check_history(tag, result.history, steps)
            # Steps after the first (which compiles): host-clock seconds
            # between log boundaries, each fenced by the loss readback.
            step_s = [batch / r["examples_per_sec"] for r in result.history[1:]]
            row.update(
                steps=steps, fit_s=round(fit_s, 1),
                step_s_median=round(statistics.median(step_s), 4),
                loss_first=round(losses[0], 4), loss_last=round(losses[-1], 4),
            )
            if checkpointer is not None:
                saved = checkpointer.all_steps()
                restored = checkpointer.restore_latest(trainer.abstract_state())
                if restored is None or int(restored.step) != steps:
                    raise AssertionError(
                        f"{tag}: restore_latest gave "
                        f"{None if restored is None else restored.step}, "
                        f"want step {steps} (saved {saved})"
                    )
                want = _tree_checksum(result.state.params)
                got = _tree_checksum(restored.state.params)
                if got != want:
                    raise AssertionError(
                        f"{tag}: restored params checksum {got} != {want}"
                    )
                checkpointer.close()
                row.update(ckpt_steps=saved, restored_step=int(restored.step))
                del restored
            del result
        say("train", **row)
        rows.append(row)
        # The next shape needs most of the chip's memory: drop this one's
        # state and loaded programs (the persistent cache keeps them).
        del trainer, data
        jax.clear_caches()
        gc.collect()
    return rows


# -- serve --------------------------------------------------------------------


def _post(url: str, body: bytes, content_type: str, accept: str) -> bytes:
    req = urllib.request.Request(
        url, data=body, method="POST",
        headers={"Content-Type": content_type, "Accept": accept},
    )
    # Straight to localhost, whatever proxy the environment names.
    opener = urllib.request.build_opener(urllib.request.ProxyHandler({}))
    with opener.open(req, timeout=120) as resp:
        return resp.read()


def phase_serve(
    module=None,
    image_px: int = 224,
    max_batch: int = 8,
    request_sizes=(1, 3, 8),
    rel_tol: float = 2e-2,
) -> list[dict]:
    """Write a seeded checkpoint with the training `Checkpointer`, load it
    as the model-server binary does (`Servable.from_checkpoint` ->
    `ModelRepository` -> `ModelServerApp`), serve HTTP on localhost from a
    thread of this process, and compare JSON and binary `:predict` answers
    with `module.apply` on the same inputs."""
    import jax
    import numpy as np

    from kubeflow_tpu.models.resnet import resnet50
    from kubeflow_tpu.serving import ModelRepository, ModelServerApp, Servable
    from kubeflow_tpu.serving import wire
    from kubeflow_tpu.train import Checkpointer
    from kubeflow_tpu.web.wsgi import serve

    module = module if module is not None else resnet50()
    example = np.zeros((1, image_px, image_px, 3), np.float32)
    variables = jax.jit(lambda r: module.init(r, example))(
        jax.random.PRNGKey(SEED)
    )
    with tempfile.TemporaryDirectory(prefix="chip_smoke_serve_") as ckpt_dir:
        writer = Checkpointer(ckpt_dir)
        writer.save(1, variables, force=True)
        writer.wait()
        writer.close()
        t0 = time.perf_counter()
        servable = Servable.from_checkpoint(
            "resnet", module, ckpt_dir, example, max_batch=max_batch,
            train=False,
        )
        load_s = time.perf_counter() - t0
    rows = []
    app = ModelServerApp(ModelRepository([servable]))
    server, thread = serve(app, host="127.0.0.1", port=0)
    try:
        url = f"http://127.0.0.1:{server.server_port}/v1/models/resnet:predict"
        say(
            "serve", model=type(module).__name__, px=image_px,
            buckets=servable._bucket_sizes,
            load_and_warm_s=round(load_s, 1), version=servable.version,
        )
        reference = jax.jit(
            lambda v, x: module.apply(v, x, train=False)
        )
        rng = np.random.RandomState(SEED)
        for n in request_sizes:
            x = rng.rand(n, image_px, image_px, 3).astype(np.float32)
            want = np.asarray(reference(variables, x))
            scale = max(1.0, float(np.abs(want).max()))
            t0 = time.perf_counter()
            body = json.dumps({"instances": x.tolist()}).encode()
            got_json = np.asarray(
                json.loads(
                    _post(url, body, "application/json", "application/json")
                )["predictions"],
                np.float32,
            )
            json_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            got_bin = wire.decode_tensor(
                _post(
                    url, wire.encode_tensor(x), wire.TENSOR_CONTENT_TYPE,
                    wire.TENSOR_CONTENT_TYPE,
                )
            )
            bin_s = time.perf_counter() - t0
            row = dict(
                batch=n, bucket=servable._bucket_for(n),
                json_request_s=round(json_s, 3),
                binary_request_s=round(bin_s, 3),
            )
            for kind, got in (("json", got_json), ("binary", got_bin)):
                if got.shape != want.shape or not np.isfinite(got).all():
                    raise AssertionError(
                        f"serve batch={n} {kind}: shape {got.shape} vs "
                        f"{want.shape}, or non-finite"
                    )
                err = float(np.abs(got - want).max())
                row[f"max_err_{kind}"] = f"{err:.3g}"
                if err > rel_tol * scale:
                    raise AssertionError(
                        f"serve batch={n} {kind}: max|served - module.apply|"
                        f" = {err:.4g} > {rel_tol} x {scale:.4g}"
                    )
            say("serve", **row)
            rows.append(row)
    finally:
        app.close_batchers()
        server.shutdown()
        server.server_close()
        thread.join(timeout=10)
    return rows


# -- four chips ---------------------------------------------------------------


def _spread(tag: str, arrays, n_devices: int, want_sharded: bool) -> dict:
    """Where a pytree's arrays live: every leaf must have a shard on each
    of `n_devices` devices, and (with `want_sharded`) at least one leaf's
    shards must be smaller than the whole — spread, not copied."""
    import jax

    leaves = jax.tree_util.tree_leaves(arrays)
    devices = set()
    sharded = 0
    for leaf in leaves:
        shards = leaf.addressable_shards
        devices |= {s.device for s in shards}
        if len({s.device for s in shards}) != n_devices:
            raise AssertionError(
                f"{tag}: a leaf of shape {leaf.shape} lives on "
                f"{len({s.device for s in shards})} device(s), not {n_devices}"
            )
        sharded += any(s.data.shape != leaf.shape for s in shards)
    if want_sharded and not sharded:
        raise AssertionError(f"{tag}: every leaf is a full copy on each device")
    return dict(leaves=len(leaves), sharded_leaves=sharded,
                devices=len(devices))


def _device_bytes(devices) -> list[int] | None:
    """Bytes in use on each device, or None where the backend keeps no
    such count (the CPU's). A TPU that reports none is an error."""
    gc.collect()
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        if any(d.platform == "tpu" for d in devices):
            raise AssertionError("a TPU device reports no memory_stats()")
        return None
    return [int(s["bytes_in_use"]) for s in stats]


def phase_sharded_train(
    model: dict = {**LM_350M, "n_layers": 4},
    cases=(("dp2_tp2", dict(dp=2, tp=2), 2048, 8),
           ("sp4", dict(sp=4), 8192, 2)),
    steps: int = 3,
    attention_impl: str = "auto",
    expect_compiled_kernels: bool = True,
    loss_rtol: float = 1e-2,
    grad_norm_rtol: float = 5e-2,
    **cfg_kw,
) -> list[dict]:
    """The LM step on each sharded mesh of `cases` against the one-chip step
    in this process: same seed, same data, `steps` steps through `fit()`
    with the anomaly guard on (its grad-norm is the second witness). Loss
    must agree within `loss_rtol` and grad-norm within `grad_norm_rtol` at
    every step — bf16 matmuls reduced in another order, not another model.
    """
    import jax

    from kubeflow_tpu.parallel import MeshSpec, build_mesh
    from kubeflow_tpu.testing.hlo import collective_counts, tpu_kernel_calls
    from kubeflow_tpu.train import AnomalyGuard, fit

    devices = jax.devices()[:4]
    one_chip = build_mesh(MeshSpec(), devices[:1])
    rows = []
    for name, axes, seq_len, batch in cases:
        tag = f"sharded {name}"

        def run(mesh):
            trainer, data = _lm_trainer(
                model, seq_len, batch, "none", mesh, attention_impl,
                guard=AnomalyGuard(), **cfg_kw,
            )
            compile_s, text, names = _compile_step(trainer, data)
            result = fit(
                trainer, data, steps, rng=jax.random.PRNGKey(SEED),
                log_every=1, handle_signals=False,
            )
            _check_history(tag, result.history, steps)
            return trainer, data, result, compile_s, text, names

        _, _, ref, _, _, ref_names = run(one_chip)
        ref_hist = ref.history
        del ref
        mesh = build_mesh(MeshSpec(**axes), devices)
        trainer, data, result, compile_s, text, names = run(mesh)
        n_calls = tpu_kernel_calls(text)
        if not names or (expect_compiled_kernels and n_calls == 0):
            raise AssertionError(
                f"{tag}: kernels traced {names}, compiled calls {n_calls}"
            )
        collectives = collective_counts(text)
        if "sp" in axes:
            # The ring moves K/V by neighbour hops; a gathered sequence
            # would mean the sp axis degenerated.
            if not collectives["collective-permute"] or collectives["all-gather"]:
                raise AssertionError(f"{tag}: collectives {collectives}")
        for got, want in zip(result.history, ref_hist):
            for key, rtol in (("loss", loss_rtol),
                              ("grad_norm", grad_norm_rtol)):
                if abs(got[key] - want[key]) > rtol * abs(want[key]):
                    raise AssertionError(
                        f"{tag}: step {got['step']} {key} {got[key]} vs "
                        f"one-chip {want[key]} (rtol {rtol})"
                    )
        params = _spread(
            f"{tag} params", result.state.params, len(devices),
            want_sharded="tp" in axes,
        )
        batch_spread = _spread(
            f"{tag} batch", next(iter(data)), len(devices),
            want_sharded="dp" in axes,
        )
        per_device = _device_bytes(devices)
        if per_device is not None and (
            min(per_device) <= 0 or max(per_device) > 4 * min(per_device)
        ):
            raise AssertionError(
                f"{tag}: bytes in use per device {per_device} — not spread"
            )
        row = dict(
            mesh=axes, S=seq_len, batch=batch, layers=model["n_layers"],
            kernels=",".join(sorted(set(names))),
            one_chip_kernels=",".join(sorted(set(ref_names))),
            compiled_kernel_calls=n_calls, compile_s=round(compile_s, 1),
            collective_permutes=collectives["collective-permute"],
            all_gathers=collectives["all-gather"],
            all_reduces=collectives["all-reduce"],
            loss=[round(r["loss"], 4) for r in result.history],
            loss_one_chip=[round(r["loss"], 4) for r in ref_hist],
            grad_norm=[round(r["grad_norm"], 4) for r in result.history],
            grad_norm_one_chip=[round(r["grad_norm"], 4) for r in ref_hist],
            loss_rtol=loss_rtol, grad_norm_rtol=grad_norm_rtol,
            params=params, batch_arrays=batch_spread,
            bytes_in_use_per_device=per_device,
        )
        say("sharded_train", **row)
        rows.append(row)
        del trainer, data, result
    return rows


def phase_replicas(
    module=None,
    image_px: int = 224,
    max_batch: int = 4,
    n_replicas: int = 4,
    n_requests: int = 16,
    rel_tol: float = 2e-2,
) -> dict:
    """`n_replicas` one-chip `Servable`s, each pinned to its own device with
    `device=`, behind one `Router`; concurrent requests must all match
    `module.apply`, every replica must serve some, and the weights must sit
    on `n_replicas` different devices."""
    import jax
    import numpy as np

    from kubeflow_tpu.models.resnet import resnet50
    from kubeflow_tpu.serving import BatchingConfig, Router, Servable
    from kubeflow_tpu.serving.replica import LocalReplica
    from kubeflow_tpu.utils.metrics import MetricsRegistry

    module = module if module is not None else resnet50()
    devices = jax.devices()[:n_replicas]
    example = np.zeros((1, image_px, image_px, 3), np.float32)
    variables = jax.jit(lambda r: module.init(r, example))(
        jax.random.PRNGKey(SEED)
    )
    before = _device_bytes(devices)
    router = Router()
    replicas, registries = [], []
    t0 = time.perf_counter()
    for i, device in enumerate(devices):
        servable = Servable.from_module(
            "resnet", module, variables, max_batch=max_batch,
            warmup_example=example[0], device=device, train=False,
        )
        registry = MetricsRegistry()
        replica = LocalReplica(
            f"replica-{i}", servable,
            BatchingConfig(max_batch=max_batch, timeout_ms=2.0), registry,
        )
        router.add(replica)
        replicas.append((replica, servable))
        registries.append(registry)
    load_s = time.perf_counter() - t0
    try:
        weight_devices = []
        for _, servable in replicas:
            placed = {
                d for leaf in jax.tree_util.tree_leaves(servable.variables)
                for d in leaf.devices()
            }
            if len(placed) != 1:
                raise AssertionError(f"replicas: one servable on {placed}")
            weight_devices.append(next(iter(placed)))
        if len(set(weight_devices)) != n_replicas:
            raise AssertionError(
                f"replicas: weights on {weight_devices}, want {n_replicas} "
                "different devices"
            )
        reference = jax.jit(lambda v, x: module.apply(v, x, train=False))
        rng = np.random.RandomState(SEED)
        inputs = [
            rng.rand(1 + i % max_batch, image_px, image_px, 3).astype(
                np.float32
            )
            for i in range(n_requests)
        ]
        wants = [np.asarray(reference(variables, x)) for x in inputs]
        outs: list = [None] * n_requests
        errors: list = []
        start = threading.Barrier(n_requests)

        def client(i: int) -> None:
            try:
                start.wait(timeout=60)
                outs[i] = np.asarray(router.predict(inputs[i]))
            except Exception as e:  # a thread's: re-raised by the caller
                errors.append(e)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n_requests)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        wall_s = time.perf_counter() - t0
        if errors:
            raise errors[0]
        max_err = 0.0
        for i, (got, want) in enumerate(zip(outs, wants)):
            if got is None or got.shape != want.shape:
                raise AssertionError(f"replicas: request {i} unanswered")
            err = float(np.abs(got - want).max())
            max_err = max(max_err, err)
            if err > rel_tol * max(1.0, float(np.abs(want).max())):
                raise AssertionError(
                    f"replicas: request {i} off by {err:.4g}"
                )
        served = [
            int(reg.counter(
                "serving_batched_instances_total",
                "instances served through the batcher", ("model",),
            ).value(model="resnet"))
            for reg in registries
        ]
        if min(served) == 0:
            raise AssertionError(
                f"replicas: instances served per replica {served} — "
                "a replica took no traffic"
            )
        after = _device_bytes(devices)
        grew = None
        if after is not None:
            grew = [a - b for a, b in zip(after, before)]
            if min(grew) <= 0:
                raise AssertionError(
                    f"replicas: bytes in use grew by {grew} per device — "
                    "some device holds no replica"
                )
        row = dict(
            replicas=n_replicas, px=image_px, max_batch=max_batch,
            weight_devices=[d.id for d in weight_devices],
            load_and_warm_s=round(load_s, 1), requests=n_requests,
            requests_wall_s=round(wall_s, 3), max_err=f"{max_err:.3g}",
            instances_served_per_replica=served,
            bytes_in_use_growth_per_device=grew,
        )
        say("replicas", **row)
        return row
    finally:
        for replica, _ in replicas:
            replica.close()


# -- main ---------------------------------------------------------------------


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4 = only the phases that exist across chips (sharded train "
        "steps against the one-chip step, replicas behind the router)",
    )
    args = parser.parse_args(argv)
    device = None
    t_start = time.perf_counter()
    try:
        device = phase_device(args.chips)
        import os

        from kubeflow_tpu.utils.compile_cache import enable_compile_cache

        cache_dir = enable_compile_cache()
        entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
        say("cache", dir=cache_dir, entries_at_start=entries,
            state="warm" if entries else "cold")
        phases = (
            (phase_kernels, phase_train, phase_serve) if args.chips == 1
            else (phase_sharded_train, phase_replicas)
        )
        for phase in phases:
            t0 = time.perf_counter()
            phase()
            say("phase", name=phase.__name__,
                seconds=round(time.perf_counter() - t0, 1))
    except Exception as e:
        # The one boundary: the failure is printed, reported in the last
        # line, and the exit code is 1 — never 0 from here.
        traceback.print_exc()
        device = getattr(e, "device", device)
        print(f"[smoke] FAILED: {type(e).__name__}: {e}", flush=True)
        print(json.dumps({"ok": False, "device": device}), flush=True)
        return 1
    say("smoke", total_seconds=round(time.perf_counter() - t_start, 1))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
