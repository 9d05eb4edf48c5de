"""SPMD pipeline parallelism: microbatched GPipe and interleaved
(circular) schedules over the `pp` mesh axis.

The reference has no pipeline parallelism at all (SURVEY.md §2.2 — absent);
here it is a first-class mesh axis with an actual schedule, built the TPU
way: every pp rank runs the SAME traced program (`shard_map`), stages hand
activations to their successor with `lax.ppermute` over ICI, and the
steady-state keeps all stages busy while the warmup/drain ticks are the
classic pipeline bubble.

Two schedules, one loop:

- **GPipe** (`interleave=1`): each rank holds ONE stage slice; total loop
  ticks = `M + pp - 1`, bubble fraction `(pp-1)/(M+pp-1)`.
- **Interleaved / circular** (`interleave=v > 1`): each rank holds `v`
  NON-ADJACENT stage slices (`n_stages = v * pp`; rank r owns stages
  r, pp+r, 2pp+r, ...). A microbatch circulates the pp ring v times, so
  each loop tick applies 1/v of a rank's layers and the warmup/drain
  shrinks to `(pp-1)/v` GPipe-equivalent ticks — the bubble drops ~v×
  for the same hardware and model ("Exploring the limits of Concurrency
  in ML Training on Google TPUs", PAPERS.md). Wrapped activations wait
  their turn in a per-rank circular buffer (`M - pp` ticks at most),
  which is why `num_microbatches >= pp` is required.

Shape contract:

- `stage_params`: a pytree whose leaves are stacked per stage on the
  leading axis (`[n_stages, ...]` in pipeline order — stage `s` at index
  `s`; sharded `P("pp", ...)`, logical axis name "stage"). The
  interleaved slice-to-rank permutation is internal.
- `x`: the global batch `[B, ...]`, sharded over the batch axes (dp/fsdp)
  and replicated over pp. It is split into `num_microbatches` equal
  microbatches along axis 0.
- `stage_fn(params_slice, microbatch) -> microbatch` — pure, same output
  shape (the usual residual-block contract).

Cross-pp wire contract (the perf_opt this module is shaped around):

- **Training (`loss_fn` given) moves scalars only across pp.** The final
  microbatch activations stay local to the last stage; each microbatch's
  loss is computed there (sequentially, `lax.map`, so logits-sized
  intermediates exist one microbatch at a time) and ONE scalar is
  psum-ed. The old design all-reduced the entire `[M, mb, ...]` output
  buffer over pp — gigabytes per step for data only one rank produced.
  Gradients ride the `ppermute` transposes (scalar loss → per-hop
  activation cotangents), exactly the forward wire pattern reversed.
- The activations-returning path (no `loss_fn` — eval/inference) never
  all-reduces either: the last stage's buffer is rotated around the ring
  with `pp-1` neighbor hops (`_broadcast_from_last`). A lint in
  `tests/test_ci_tools.py` pins that no non-scalar `lax.psum` ever
  reappears in this module.

Gradients flow through `ppermute` (it has a transpose rule), so the same
function trains under `jax.grad`.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from kubeflow_tpu.parallel.sharding import batch_axes, batch_shard_count


def pipeline_schedule(
    n_stages: int, num_microbatches: int, interleave: int = 1
) -> dict:
    """The static schedule accounting for a pipeline configuration — the
    same numbers `spmd_pipeline` builds its loop from, so what the bench
    reports is the schedule that actually ran (the `flash_schedule`
    trick from ops/flash.py, applied to the pipeline layer).

    Returns:
      - ``loop_ticks``: `lax.fori_loop` iterations; each applies ONE of a
        rank's `interleave` stage slices (`M*v + pp - 1`).
      - ``stage_ticks``: loop ticks normalized to GPipe-equivalent stage
        ticks (`loop_ticks / v` — `v` loop ticks do the work one GPipe
        tick does, since each slice is `1/v` of a rank's layers).
      - ``model_stage_ticks``: the `M + S/v - 1` roofline the interleaved
        schedule is measured against (equals `stage_ticks` at v=1).
      - ``bubble``: idle fraction, `(pp-1) / loop_ticks`.
    """
    if interleave < 1:
        raise ValueError(f"interleave must be >= 1, got {interleave}")
    if n_stages % interleave:
        raise ValueError(
            f"n_stages ({n_stages}) must be a multiple of interleave "
            f"({interleave})"
        )
    if num_microbatches < 1:
        raise ValueError(
            f"num_microbatches must be >= 1, got {num_microbatches}"
        )
    pp = n_stages // interleave
    loop_ticks = num_microbatches * interleave + pp - 1
    return {
        "n_stages": n_stages,
        "pp": pp,
        "interleave": interleave,
        "num_microbatches": num_microbatches,
        "loop_ticks": loop_ticks,
        "stage_ticks": loop_ticks / interleave,
        "model_stage_ticks": num_microbatches + n_stages / interleave - 1,
        "bubble": (pp - 1) / loop_ticks,
    }


def bubble_fraction(
    n_stages: int, num_microbatches: int, interleave: int = 1
) -> float:
    """The fraction of ticks each rank idles.

    GPipe (`interleave=1`): `(S-1)/(M+S-1)` — unchanged from the original
    single-slice schedule. Interleaved: each of the `pp = S/v` ranks does
    `M*v` slice-ticks of real work inside `M*v + pp - 1` loop ticks, so
    the bubble is `(pp-1)/(M*v + pp - 1)` — ~v× smaller.
    """
    return pipeline_schedule(n_stages, num_microbatches, interleave)["bubble"]


def _interleave_order(pp: int, v: int) -> list[int]:
    """Stacked-order permutation placing rank r's k-th local slice at
    global stage `k*pp + r` (the non-adjacent, circular assignment)."""
    return [k * pp + r for r in range(pp) for k in range(v)]


def _broadcast_from_last(outputs: jax.Array, axis: str, pp: int) -> jax.Array:
    """Replicate the last rank's buffer to every pp rank with `pp-1`
    neighbor `ppermute` hops — a ring broadcast, never an all-reduce of
    the activation buffer (the hot-path wire contract this module keeps;
    see the module docstring and the test_ci_tools lint)."""
    rank = lax.axis_index(axis)
    ring = [(i, (i + 1) % pp) for i in range(pp)]
    buf = outputs
    for hop in range(1, pp):
        buf = lax.ppermute(buf, axis, ring)
        outputs = jnp.where((pp - 1 + hop) % pp == rank, buf, outputs)
    return outputs


def spmd_pipeline(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    x: jax.Array,
    *,
    mesh: Mesh,
    num_microbatches: int,
    axis: str = "pp",
    interleave: int = 1,
    loss_fn: Callable[..., jax.Array] | None = None,
    targets: Any = None,
    loss_params: Any = None,
    inject_fn: Callable[..., jax.Array] | None = None,
) -> jax.Array:
    """Run `x` through `n_stages = interleave * mesh.shape[axis]` pipeline
    stages.

    Without `loss_fn`, returns the final activations with the same
    sharding as `x`. With `loss_fn(out_mb, target_mb, loss_params)` — a
    per-microbatch MEAN objective computed where the last stage's outputs
    live — returns the scalar mean loss over all microbatches, and the
    only cross-pp collective in the whole fwd+bwd program is that
    scalar's psum plus the (weight-sized, unavoidable) gradient psum of
    any replicated `loss_params` (activation gradients ride the ppermute
    transposes).

    `targets` is a pytree of `[B, ...]` arrays microbatched like `x`;
    `loss_params` is a pytree of extra values `loss_fn` needs (e.g. the
    tied embedding for an LM head), passed in replicated.

    `inject_fn(mb, loss_params) -> activation` maps a raw microbatch of
    `x` to the first stage's input (e.g. an embedding lookup). Keep
    differentiable input prep HERE rather than upstream of the call: `x`
    enters replicated over pp, so a float `x` that is already the output
    of traced compute drags a full `[B, ...]`-sized cotangent all-reduce
    across pp through the shard_map boundary — an int token batch has no
    cotangent at all, and `inject_fn`'s own gradients flow into
    `loss_params`' scalar-masked psum instead.
    """
    pp = mesh.shape[axis]
    n_stages = pp * interleave
    sched = pipeline_schedule(n_stages, num_microbatches, interleave)
    for leaf in jax.tree_util.tree_leaves(stage_params):
        if leaf.shape[0] != n_stages:
            raise ValueError(
                f"stage_params leaves must be stacked [S={n_stages}, ...] "
                f"(interleave={interleave} x {axis}={pp}); got leading dim "
                f"{leaf.shape[0]}"
            )
    batch = tuple(batch_axes(mesh))
    batch_shards = batch_shard_count(mesh)
    local_batch, rem = divmod(x.shape[0], batch_shards)
    if rem:
        raise ValueError(
            f"batch {x.shape[0]} does not shard evenly over "
            f"{batch_shards} batch-axis devices"
        )
    # Validated for EVERY n_stages, including the degenerate single-stage
    # pipeline below — a config that errors on pp>1 must not silently
    # pass on pp=1.
    if local_batch % num_microbatches:
        raise ValueError(
            f"per-shard batch {local_batch} must divide into "
            f"{num_microbatches} microbatches"
        )
    if interleave > 1 and num_microbatches < pp:
        raise ValueError(
            f"interleaved schedule needs num_microbatches "
            f"({num_microbatches}) >= {axis} ranks ({pp}): a wrapped "
            f"microbatch re-enters rank 0 {num_microbatches} ticks after "
            f"injection but only becomes available after {pp}"
        )
    if loss_fn is not None and targets is None:
        raise ValueError("loss_fn requires targets")

    if n_stages == 1:
        # Degenerate pipeline: just apply the single stage (and the
        # objective on the full batch — the mean over equal microbatches
        # equals the full-batch mean, so the contract is unchanged).
        params0 = jax.tree_util.tree_map(lambda p: p[0], stage_params)
        x0 = inject_fn(x, loss_params) if inject_fn is not None else x
        out = stage_fn(params0, x0)
        if loss_fn is None:
            return out
        return loss_fn(out, targets, loss_params)

    if interleave > 1:
        # Re-stack from pipeline order to rank-contiguous order so the
        # P(axis) sharding below hands rank r exactly its v non-adjacent
        # slices (stages r, pp+r, ...). One gather of the weights per
        # step; its transpose scatters the gradients straight back.
        order = jnp.asarray(_interleave_order(pp, interleave))
        stage_params = jax.tree_util.tree_map(
            lambda p: jnp.take(p, order, axis=0), stage_params
        )

    param_spec = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
    x_spec = P(batch)
    tgt_spec = jax.tree_util.tree_map(lambda _: P(batch), targets)
    lp_spec = jax.tree_util.tree_map(lambda _: P(), loss_params)
    M, v = num_microbatches, interleave
    total = M * v
    ring = [(i, (i + 1) % pp) for i in range(pp)]

    def split_mb(a):
        return jnp.reshape(
            a, (M, a.shape[0] // M) + a.shape[1:]
        )

    def run_schedule(params, local_x, lp):
        """The pipeline loop. Returns the per-rank `[M, mb, ...]` output
        buffer — real data on the last rank, zeros elsewhere."""
        rank = lax.axis_index(axis)
        mb = split_mb(local_x)

        def feed_fn(m):
            raw = mb[m]
            return inject_fn(raw, lp) if inject_fn is not None else raw

        # First-stage input shape, which the in-flight state buffers
        # share (inject_fn may change trailing dims/dtype, e.g. an
        # embedding lookup's tokens -> activations).
        probe = jax.eval_shape(
            feed_fn, jax.ShapeDtypeStruct((), jnp.int32)
        )
        state = jnp.zeros(probe.shape, probe.dtype)
        outputs = jnp.zeros((M,) + probe.shape, probe.dtype)
        # Circular buffer for wrapped activations (interleave only):
        # rank 0 re-injects microbatch m for repeat w+1 exactly
        # (w+1)*M + m ticks in, M - pp ticks after its wrap arrives.
        circ = jnp.zeros((M,) + probe.shape, probe.dtype) if v > 1 else None

        def tick(t, carry):
            state, outputs, circ = carry
            # Rank r's work item this tick: microbatch `m`, repeat `w`
            # (= local slice index). The staircase `t - rank` is the
            # pipeline's defining skew.
            idx = t - rank
            valid = jnp.logical_and(idx >= 0, idx < total)
            idxc = jnp.clip(idx, 0, total - 1)
            m = idxc % M
            w = idxc // M
            # Rank 0 sources fresh microbatches on repeat 0, wrapped
            # ones from the circular buffer after; everyone else
            # consumes the neighbor handoff.
            inj = feed_fn(m)
            if v > 1:
                feed = jnp.where(w == 0, inj, circ[m])
            else:
                feed = inj
            x_in = jnp.where(rank == 0, feed, state)
            if v > 1:
                my = jax.tree_util.tree_map(
                    lambda p: lax.dynamic_index_in_dim(
                        p, w, 0, keepdims=False
                    ),
                    params,
                )
            else:
                my = jax.tree_util.tree_map(lambda p: p[0], params)
            y = stage_fn(my, x_in)
            # The last rank's last repeat emits microbatch m.
            emit = jnp.logical_and(
                valid,
                jnp.logical_and(rank == pp - 1, w == v - 1),
            )
            outputs = outputs.at[m].set(jnp.where(emit, y, outputs[m]))
            # Neighbor handoff (ring: last -> 0 carries the wrap; for
            # v=1 rank 0 overwrites it with its next injection).
            y = lax.ppermute(y, axis, ring)
            if v > 1:
                # File the arriving wrap under its microbatch id. Only
                # rank 0's buffer is ever read; other ranks file their
                # (differently-sourced) arrivals into slots they never
                # consume.
                src = t - (pp - 1)
                srcc = jnp.clip(src, 0, total - 1)
                wrap = jnp.logical_and(
                    jnp.logical_and(src >= 0, src < total),
                    srcc // M < v - 1,
                )
                sm = srcc % M
                circ = circ.at[sm].set(jnp.where(wrap, y, circ[sm]))
            return y, outputs, circ

        _, outputs, _ = lax.fori_loop(
            0, sched["loop_ticks"], tick, (state, outputs, circ)
        )
        return outputs

    if loss_fn is None:

        @partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(param_spec, x_spec, lp_spec),
            out_specs=x_spec,
            check_vma=False,
        )
        def run(params, local_x, lp):
            outputs = run_schedule(params, local_x, lp)
            outputs = _broadcast_from_last(outputs, axis, pp)
            return jnp.reshape(
                outputs, (outputs.shape[0] * outputs.shape[1],)
                + outputs.shape[2:]
            )

        return run(stage_params, x, loss_params)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(param_spec, x_spec, tgt_spec, lp_spec),
        out_specs=P(),
        check_vma=False,
    )
    def run_loss(params, local_x, local_targets, lp):
        outputs = run_schedule(params, local_x, lp)
        tgt = jax.tree_util.tree_map(split_mb, local_targets)
        # Per-microbatch objective, sequentially (lax.map): logits-sized
        # intermediates exist for ONE microbatch at a time, which is the
        # whole activation-memory point of microbatching the loss.
        def one(m):
            return loss_fn(
                outputs[m],
                jax.tree_util.tree_map(lambda a: a[m], tgt),
                lp,
            )

        losses = lax.map(one, jnp.arange(M))
        # Every rank ran the (masked) objective on its local buffer, but
        # only the last stage's is real; the ONLY cross-pp collective in
        # the program is this scalar's psum (summed over the batch
        # shards in the same reduction).
        local_loss = jnp.where(
            lax.axis_index(axis) == pp - 1, jnp.sum(losses), 0.0
        )
        return lax.psum(local_loss, (axis,) + batch) / (M * batch_shards)

    return run_loss(stage_params, x, targets, loss_params)
