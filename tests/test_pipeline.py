"""SPMD pipeline parallelism: GPipe and interleaved (circular) schedules
over the pp axis via shard_map + ppermute, with the last-stage loss path
(scalar-only cross-pp traffic) — the reference has none (SURVEY.md §2.2)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.parallel import (
    MeshSpec,
    build_mesh,
    bubble_fraction,
    pipeline_schedule,
    spmd_pipeline,
)


def _stage_fn(params, x):
    # One residual MLP stage: x + relu(x @ w1) @ w2.
    return x + jax.nn.relu(x @ params["w1"]) @ params["w2"]


def _stacked_params(key, n_stages, d, hidden):
    k1, k2 = jax.random.split(key)
    return {
        "w1": jax.random.normal(k1, (n_stages, d, hidden)) * 0.1,
        "w2": jax.random.normal(k2, (n_stages, hidden, d)) * 0.1,
    }


def _sequential(params, x):
    for s in range(params["w1"].shape[0]):
        x = _stage_fn(jax.tree_util.tree_map(lambda p: p[s], params), x)
    return x


@pytest.mark.parametrize("pp,microbatches", [(2, 2), (2, 4), (4, 4), (4, 8)])
def test_pipeline_matches_sequential(pp, microbatches):
    mesh = build_mesh(MeshSpec(dp=1, pp=pp), jax.devices()[:pp])
    params = _stacked_params(jax.random.PRNGKey(0), pp, 8, 16)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 8))
    out = jax.jit(
        lambda p, x: spmd_pipeline(
            _stage_fn, p, x, mesh=mesh, num_microbatches=microbatches
        )
    )(params, x)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_sequential(params, x)),
        rtol=1e-5, atol=1e-5,
    )


def test_pipeline_composes_with_dp():
    """dp x pp: the batch shards over dp while stages split over pp."""
    mesh = build_mesh(MeshSpec(dp=2, pp=2), jax.devices()[:4])
    params = _stacked_params(jax.random.PRNGKey(2), 2, 4, 8)
    x = jax.random.normal(jax.random.PRNGKey(3), (8, 4))
    out = jax.jit(
        lambda p, x: spmd_pipeline(
            _stage_fn, p, x, mesh=mesh, num_microbatches=2
        )
    )(params, x)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_sequential(params, x)),
        rtol=1e-5, atol=1e-5,
    )


def test_pipeline_gradients_match_sequential():
    """ppermute transposes cleanly: training through the pipeline gives
    the same gradients as the unpipelined program."""
    mesh = build_mesh(MeshSpec(dp=1, pp=2), jax.devices()[:2])
    params = _stacked_params(jax.random.PRNGKey(4), 2, 4, 8)
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 4))

    def loss_pipe(p):
        y = spmd_pipeline(_stage_fn, p, x, mesh=mesh, num_microbatches=2)
        return jnp.sum(y**2)

    def loss_seq(p):
        return jnp.sum(_sequential(p, x) ** 2)

    g_pipe = jax.jit(jax.grad(loss_pipe))(params)
    g_seq = jax.grad(loss_seq)(params)
    for leaf_p, leaf_s in zip(
        jax.tree_util.tree_leaves(g_pipe), jax.tree_util.tree_leaves(g_seq)
    ):
        np.testing.assert_allclose(
            np.asarray(leaf_p), np.asarray(leaf_s), rtol=1e-4, atol=1e-5
        )


def test_single_stage_degenerates():
    mesh = build_mesh(MeshSpec(dp=1, pp=1), jax.devices()[:1])
    params = _stacked_params(jax.random.PRNGKey(6), 1, 4, 8)
    x = jax.random.normal(jax.random.PRNGKey(7), (4, 4))
    out = spmd_pipeline(_stage_fn, params, x, mesh=mesh, num_microbatches=2)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_sequential(params, x)), rtol=1e-6
    )


def test_validation_errors():
    mesh = build_mesh(MeshSpec(dp=1, pp=2), jax.devices()[:2])
    params = _stacked_params(jax.random.PRNGKey(8), 3, 4, 8)  # wrong S
    x = jnp.zeros((4, 4))
    with pytest.raises(ValueError, match="stacked"):
        spmd_pipeline(_stage_fn, params, x, mesh=mesh, num_microbatches=2)
    good = _stacked_params(jax.random.PRNGKey(8), 2, 4, 8)
    with pytest.raises(ValueError, match="microbatches"):
        spmd_pipeline(_stage_fn, good, x, mesh=mesh, num_microbatches=3)


def test_degenerate_single_stage_still_validates_microbatches():
    """A config that errors on pp>1 must not silently pass on pp=1: the
    microbatch-divisibility check runs BEFORE the degenerate single-stage
    early return."""
    mesh = build_mesh(MeshSpec(dp=1, pp=1), jax.devices()[:1])
    params = _stacked_params(jax.random.PRNGKey(8), 1, 4, 8)
    with pytest.raises(ValueError, match="microbatches"):
        spmd_pipeline(
            _stage_fn, params, jnp.zeros((4, 4)), mesh=mesh,
            num_microbatches=3,
        )


def test_interleave_validation_errors():
    mesh = build_mesh(MeshSpec(dp=1, pp=2), jax.devices()[:2])
    x = jnp.zeros((4, 4))
    # Stacked dim must equal interleave * pp.
    two = _stacked_params(jax.random.PRNGKey(8), 2, 4, 8)
    with pytest.raises(ValueError, match="interleave"):
        spmd_pipeline(
            _stage_fn, two, x, mesh=mesh, num_microbatches=2, interleave=2
        )
    # A wrapped microbatch re-enters rank 0 M ticks after injection but
    # only arrives after pp — M < pp would deadlock into garbage.
    four = _stacked_params(jax.random.PRNGKey(8), 4, 4, 8)
    with pytest.raises(ValueError, match="interleaved schedule needs"):
        spmd_pipeline(
            _stage_fn, four, x, mesh=mesh, num_microbatches=1, interleave=2
        )


def test_bubble_fraction():
    assert bubble_fraction(4, 4) == pytest.approx(3 / 7)
    assert bubble_fraction(1, 8) == 0.0
    # More microbatches amortize the bubble.
    assert bubble_fraction(4, 32) < bubble_fraction(4, 8)


def test_bubble_fraction_interleaved():
    # The v=1 values are the original GPipe formula, pinned unchanged.
    assert bubble_fraction(4, 4, interleave=1) == pytest.approx(3 / 7)
    assert bubble_fraction(8, 16, interleave=1) == pytest.approx(7 / 23)
    assert bubble_fraction(1, 8, interleave=1) == 0.0
    # Same stage count on pp = S/v ranks: the bubble shrinks ~v x.
    assert bubble_fraction(4, 4, interleave=2) == pytest.approx(1 / 9)
    assert bubble_fraction(4, 4, interleave=2) < bubble_fraction(4, 4)
    assert bubble_fraction(8, 8, interleave=4) == pytest.approx(1 / 33)
    # interleave must divide the stage count.
    with pytest.raises(ValueError, match="multiple of interleave"):
        bubble_fraction(4, 4, interleave=3)


def test_pipeline_schedule_accounting():
    s = pipeline_schedule(4, 8, interleave=2)
    assert s["pp"] == 2 and s["loop_ticks"] == 8 * 2 + 1
    assert s["stage_ticks"] == pytest.approx(8.5)
    assert s["model_stage_ticks"] == pytest.approx(8 + 4 / 2 - 1)
    assert s["stage_ticks"] <= s["model_stage_ticks"]
    # GPipe meets the model exactly.
    g = pipeline_schedule(4, 8, interleave=1)
    assert g["loop_ticks"] == 11
    assert g["stage_ticks"] == g["model_stage_ticks"] == 11


@pytest.mark.parametrize(
    "pp,v,microbatches", [(2, 2, 2), (2, 2, 4), (4, 2, 8), (2, 3, 4)]
)
def test_interleaved_pipeline_matches_sequential(pp, v, microbatches):
    """Circular schedule, v non-adjacent slices per rank: same math as
    running the v*pp stages sequentially."""
    mesh = build_mesh(MeshSpec(dp=1, pp=pp), jax.devices()[:pp])
    params = _stacked_params(jax.random.PRNGKey(0), pp * v, 8, 16)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 8))
    out = jax.jit(
        lambda p, x: spmd_pipeline(
            _stage_fn, p, x, mesh=mesh, num_microbatches=microbatches,
            interleave=v,
        )
    )(params, x)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_sequential(params, x)),
        rtol=1e-5, atol=1e-5,
    )


def _mse(out, tgt, lp):
    return jnp.mean((out - tgt) ** 2)


@pytest.mark.parametrize("pp,v,dp", [(4, 1, 1), (2, 2, 1), (2, 2, 2)])
def test_pipeline_loss_and_grads_match_single_rank(pp, v, dp):
    """Grad parity (the scalar-only loss path): pp=2 and pp=4, with and
    without interleave, match the pp=1 single-rank reference's loss AND
    gradients — the ppermute transposes carry exactly the cotangents the
    terminal all-reduce used to."""
    params = _stacked_params(jax.random.PRNGKey(0), 4, 8, 16)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 8))
    tgt = jax.random.normal(jax.random.PRNGKey(2), (8, 8))

    ref_mesh = build_mesh(MeshSpec(dp=1, pp=1), jax.devices()[:1])
    # pp=1, interleave=4: the degenerate ring still runs the circular
    # schedule; it doubles as the single-rank reference for the loss
    # contract (and equals plain sequential + mse).
    ref = jax.jit(
        jax.value_and_grad(
            lambda p: spmd_pipeline(
                _stage_fn, p, x, mesh=ref_mesh, num_microbatches=4,
                interleave=4, loss_fn=_mse, targets=tgt,
            )
        )
    )(params)
    seq_loss = jnp.mean((_sequential(params, x) - tgt) ** 2)
    np.testing.assert_allclose(float(ref[0]), float(seq_loss), rtol=1e-6)

    mesh = build_mesh(MeshSpec(dp=dp, pp=pp), jax.devices()[:pp * dp])
    loss, grads = jax.jit(
        jax.value_and_grad(
            lambda p: spmd_pipeline(
                _stage_fn, p, x, mesh=mesh, num_microbatches=4,
                interleave=v, loss_fn=_mse, targets=tgt,
            )
        )
    )(params)
    np.testing.assert_allclose(float(loss), float(ref[0]), rtol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(ref[1])
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        )


# -- pipelined transformer --------------------------------------------------


def test_pipelined_transformer_matches_flat():
    """Same Block weights, pipelined schedule: logits must match the flat
    TransformerLM when the stacked params are the flat layers restacked."""
    from kubeflow_tpu.models.transformer import (
        PipelinedTransformerLM,
        TransformerConfig,
        TransformerLM,
    )

    cfg = TransformerConfig(
        vocab_size=64, d_model=16, n_layers=4, n_heads=2, head_dim=8,
        d_ff=32, remat_policy="none", dtype=jnp.float32, attention_impl="dense",
    )
    mesh = build_mesh(MeshSpec(dp=2, pp=2), jax.devices()[:4])
    tokens = jax.random.randint(jax.random.PRNGKey(0), (4, 8), 0, 64)

    pipe = PipelinedTransformerLM(cfg, n_stages=2, num_microbatches=2,
                                  mesh=mesh)
    variables = jax.jit(pipe.init)(jax.random.PRNGKey(1), tokens)
    logits_pipe = jax.jit(lambda v, t: pipe.apply(v, t))(variables, tokens)

    # Rebuild the flat model's params from the stacked stage params:
    # stages/blocks/layer_i[stage s] -> layer_{s*per_stage + i}.
    flat = TransformerLM(cfg)
    stacked = variables["params"]["stages"]["blocks"]
    flat_params = {
        "embedding": variables["params"]["embedding"],
        "ln_final": variables["params"]["ln_final"],
    }
    per_stage = cfg.n_layers // 2
    for s in range(2):
        for i in range(per_stage):
            flat_params[f"layer_{s * per_stage + i}"] = (
                jax.tree_util.tree_map(
                    lambda p: p[s], stacked[f"layer_{i}"]
                )
            )
    logits_flat = flat.apply({"params": flat_params}, tokens)
    np.testing.assert_allclose(
        np.asarray(logits_pipe), np.asarray(logits_flat),
        rtol=2e-4, atol=2e-4,
    )


def test_pipelined_transformer_trains():
    """The pipelined model trains end-to-end through the Trainer (loss
    decreases) on a dp x pp mesh."""
    from kubeflow_tpu.models.transformer import (
        PipelinedTransformerLM,
        TransformerConfig,
    )
    from kubeflow_tpu.train import SyntheticTokens, TrainConfig, Trainer

    cfg = TransformerConfig(
        vocab_size=32, d_model=16, n_layers=2, n_heads=2, head_dim=8,
        d_ff=32, remat_policy="none", dtype=jnp.float32, attention_impl="dense",
    )
    mesh = build_mesh(MeshSpec(dp=2, pp=2), jax.devices()[:4])
    model = PipelinedTransformerLM(cfg, n_stages=2, num_microbatches=2,
                                   mesh=mesh)
    config = TrainConfig(batch_size=8, learning_rate=0.05, warmup_steps=1,
                         total_steps=8, optimizer="adamw")
    trainer = Trainer(
        model, config, mesh,
        example_input_shape=(4, 8),
        input_key="tokens", label_key="labels",
        example_input_dtype=jnp.int32,
    )
    state = trainer.init_state(jax.random.PRNGKey(0))
    data = SyntheticTokens(mesh, 8, seq_len=8, vocab_size=32)
    step = trainer.make_train_step()
    losses = []
    for batch in data:
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
        if len(losses) >= 8:
            break
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses


def test_pipelined_transformer_validation():
    from kubeflow_tpu.models.transformer import (
        PipelinedTransformerLM,
        TransformerConfig,
    )

    cfg = TransformerConfig(vocab_size=16, d_model=8, n_layers=3,
                            n_heads=1, head_dim=8, d_ff=16, remat_policy="none")
    tokens = jnp.zeros((4, 4), jnp.int32)
    with pytest.raises(ValueError, match="stages"):
        PipelinedTransformerLM(cfg, n_stages=2, num_microbatches=2).init(
            jax.random.PRNGKey(0), tokens
        )
    moe = TransformerConfig(vocab_size=16, d_model=8, n_layers=2,
                            n_heads=1, head_dim=8, d_ff=16, num_experts=2)
    with pytest.raises(ValueError, match="MoE"):
        PipelinedTransformerLM(moe, n_stages=2, num_microbatches=2).init(
            jax.random.PRNGKey(0), tokens
        )


def test_pipeline_composes_with_tp_and_fsdp():
    """The full 3D layout: stages over pp, weights over fsdp, heads/mlp
    over tp — one traced program, XLA inserts every collective."""
    from kubeflow_tpu.models.transformer import (
        PipelinedTransformerLM,
        TransformerConfig,
    )
    from kubeflow_tpu.train import SyntheticTokens, TrainConfig, Trainer

    cfg = TransformerConfig(
        vocab_size=32, d_model=16, n_layers=2, n_heads=2, head_dim=8,
        d_ff=32, remat_policy="none", dtype=jnp.float32, attention_impl="dense",
    )
    mesh = build_mesh(MeshSpec(fsdp=2, pp=2, tp=2), jax.devices()[:8])
    model = PipelinedTransformerLM(cfg, n_stages=2, num_microbatches=2,
                                   mesh=mesh)
    trainer = Trainer(
        model,
        TrainConfig(batch_size=8, learning_rate=0.05, warmup_steps=1,
                    total_steps=6, optimizer="adamw", fsdp_params=True),
        mesh,
        example_input_shape=(4, 8),
        input_key="tokens", label_key="labels",
        example_input_dtype=jnp.int32,
    )
    state = trainer.init_state(jax.random.PRNGKey(0))
    # Stage-stacked weights really shard over pp AND fsdp AND tp.
    wq = state.params["stages"]["blocks"]["layer_0"]["attn"]["wq"]["kernel"]
    spec = str(wq.sharding.spec)
    assert "pp" in spec and "tp" in spec and "fsdp" in spec, spec
    data = SyntheticTokens(mesh, 8, seq_len=8, vocab_size=32)
    step = trainer.make_train_step()
    losses = []
    for batch in data:
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
        if len(losses) >= 6:
            break
    assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses


# -- last-stage loss path (scalar-only cross-pp) ----------------------------


def _tiny_lm_cfg(**kw):
    from kubeflow_tpu.models.transformer import TransformerConfig

    base = dict(
        vocab_size=64, d_model=16, n_layers=4, n_heads=2, head_dim=8,
        d_ff=32, remat_policy="none", dtype=jnp.float32, attention_impl="dense",
    )
    base.update(kw)
    return TransformerConfig(**base)


@pytest.mark.parametrize("v", [1, 2])
def test_pipelined_loss_and_grads_match_flat(v):
    """pp=2, with and without interleave: the pipelined loss path's loss
    AND gradients match the flat (single-stage) TransformerLM's
    cross-entropy on the restacked weights."""
    import flax.linen as nn

    from kubeflow_tpu.models.transformer import (
        PipelinedTransformerLM,
        TransformerLM,
    )
    from kubeflow_tpu.train.trainer import softmax_cross_entropy

    cfg = _tiny_lm_cfg()
    n_stages = 2 * v
    mesh = build_mesh(MeshSpec(dp=2, pp=2), jax.devices()[:4])
    tokens = jax.random.randint(jax.random.PRNGKey(0), (8, 8), 0, 64)
    labels = jax.random.randint(jax.random.PRNGKey(9), (8, 8), 0, 64)

    pipe = PipelinedTransformerLM(
        cfg, n_stages=n_stages, num_microbatches=4, mesh=mesh, interleave=v
    )
    params = nn.meta.unbox(
        jax.jit(pipe.init)(jax.random.PRNGKey(1), tokens)
    )["params"]
    loss_p, grads_p = jax.jit(
        jax.value_and_grad(
            lambda p: pipe.apply({"params": p}, tokens, labels=labels)
        )
    )(params)

    flat = TransformerLM(cfg)
    stacked = params["stages"]["blocks"]
    per_stage = cfg.n_layers // n_stages
    flat_params = {
        "embedding": params["embedding"],
        "ln_final": params["ln_final"],
    }
    for s in range(n_stages):
        for i in range(per_stage):
            flat_params[f"layer_{s * per_stage + i}"] = (
                jax.tree_util.tree_map(lambda p: p[s], stacked[f"layer_{i}"])
            )
    loss_f, grads_f = jax.jit(
        jax.value_and_grad(
            lambda p: softmax_cross_entropy(
                flat.apply({"params": p}, tokens), labels
            )
        )
    )(flat_params)
    np.testing.assert_allclose(float(loss_p), float(loss_f), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(grads_p["embedding"]),
        np.asarray(grads_f["embedding"]),
        rtol=2e-4, atol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(grads_p["ln_final"]["scale"]),
        np.asarray(grads_f["ln_final"]["scale"]),
        rtol=2e-4, atol=1e-5,
    )
    for s in range(n_stages):
        for i in range(per_stage):
            g_p = jax.tree_util.tree_map(
                lambda p: p[s], grads_p["stages"]["blocks"][f"layer_{i}"]
            )
            g_f = grads_f[f"layer_{s * per_stage + i}"]
            for a, b in zip(
                jax.tree_util.tree_leaves(g_p),
                jax.tree_util.tree_leaves(g_f),
            ):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5
                )


@pytest.mark.parametrize("v", [1, 2])
def test_pipeline_loss_scalar_only_cross_pp_collectives(v):
    """Collective-accounting regression (the wire contract), now a
    thin wrapper over the `pipeline-wire-v{1,2}` rows of the kftpu-lint
    program-contract table (ISSUE 8, `ci/lint/contracts.py`): the
    compiled fwd+bwd of the pipelined loss path contains NO all-reduce
    at or above one microbatch's activations ([mb, S, d_model] — the
    shapes make even that outweigh the largest weight buffer), moves
    activations by collective-permute, and loops exactly the published
    schedule's tick count."""
    from kubeflow_tpu.ci.lint.contracts import run_contract

    run_contract(f"pipeline-wire-v{v}")


def test_grad_accumulation_matches_full_batch():
    """TrainConfig.accum_steps on a NON-pp mesh: one train step with
    accumulation produces the same loss, accuracy, and updated params as
    the full-batch step (mean of equal microbatch means)."""
    from kubeflow_tpu.models.transformer import TransformerLM
    from kubeflow_tpu.train import SyntheticTokens, TrainConfig, Trainer

    cfg = _tiny_lm_cfg(n_layers=2, vocab_size=32)
    mesh = build_mesh(MeshSpec(dp=2), jax.devices()[:2])
    batch = next(iter(SyntheticTokens(mesh, 8, seq_len=8, vocab_size=32)))
    results = {}
    for accum in (1, 4):
        config = TrainConfig(
            batch_size=8, learning_rate=0.1, warmup_steps=1,
            total_steps=4, optimizer="sgd", accum_steps=accum,
        )
        trainer = Trainer(
            TransformerLM(cfg, mesh=mesh), config, mesh,
            example_input_shape=(4, 8), input_key="tokens",
            label_key="labels", example_input_dtype=jnp.int32,
        )
        state = trainer.init_state(jax.random.PRNGKey(0))
        state, metrics = trainer.make_train_step()(state, batch)
        results[accum] = (state, metrics)
    for a, b in zip(
        jax.tree_util.tree_leaves(results[1][0].params),
        jax.tree_util.tree_leaves(results[4][0].params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6
        )
    for key in ("loss", "accuracy"):
        np.testing.assert_allclose(
            float(results[1][1][key]), float(results[4][1][key]), rtol=1e-5
        )


def test_grad_accumulation_threads_batch_stats():
    """BN models under accum_steps: each microbatch's batch_stats update
    builds on the previous tick's (sequential-small-batch semantics) —
    the step's final stats must equal manually folding the microbatches
    through the model one after another, not just the last microbatch's
    update of the starting stats."""
    from kubeflow_tpu.models.resnet import tiny_resnet
    from kubeflow_tpu.train import SyntheticImages, TrainConfig, Trainer

    mesh = build_mesh(MeshSpec(dp=1), jax.devices()[:1])
    config = TrainConfig(
        batch_size=8, learning_rate=0.1, warmup_steps=1, total_steps=4,
        accum_steps=2,
    )
    trainer = Trainer(
        tiny_resnet(), config, mesh, example_input_shape=(2, 32, 32, 3)
    )
    state = trainer.init_state(jax.random.PRNGKey(0))
    batch = next(iter(SyntheticImages(
        mesh, batch_size=8, image_size=32, num_classes=10,
        dtype=jnp.float32,
    )))
    # Manual fold FIRST (the train step donates and deletes `state`'s
    # buffers): microbatch 1 with the starting stats, microbatch 2 with
    # microbatch 1's updated stats.
    stats = state.batch_stats
    for i in range(2):
        mb = batch["image"][i * 4:(i + 1) * 4]
        _, out = state.apply_fn(
            {"params": state.params, "batch_stats": stats}, mb,
            train=True, mutable=["batch_stats"],
        )
        stats = out["batch_stats"]
    stats = jax.tree_util.tree_map(np.asarray, stats)

    new_state, _ = trainer.make_train_step()(state, batch)
    for a, b in zip(
        jax.tree_util.tree_leaves(new_state.batch_stats),
        jax.tree_util.tree_leaves(stats),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )


def test_pipelined_interleaved_trains_with_accumulation():
    """The full composition: interleaved schedule + last-stage loss
    through the Trainer (loss_in_model) + gradient accumulation on top —
    loss decreases, eval works."""
    from kubeflow_tpu.models.transformer import PipelinedTransformerLM
    from kubeflow_tpu.train import SyntheticTokens, TrainConfig, Trainer

    cfg = _tiny_lm_cfg(vocab_size=32)
    mesh = build_mesh(MeshSpec(dp=2, pp=2), jax.devices()[:4])
    model = PipelinedTransformerLM(
        cfg, n_stages=4, num_microbatches=2, mesh=mesh, interleave=2
    )
    config = TrainConfig(
        batch_size=8, learning_rate=0.05, warmup_steps=1, total_steps=8,
        optimizer="adamw", label_smoothing=0.0, train_metrics="loss",
        loss_in_model=True, accum_steps=2,
    )
    trainer = Trainer(
        model, config, mesh, example_input_shape=(4, 8),
        input_key="tokens", label_key="labels",
        example_input_dtype=jnp.int32,
    )
    state = trainer.init_state(jax.random.PRNGKey(0))
    data = SyntheticTokens(mesh, 8, seq_len=8, vocab_size=32)
    step = trainer.make_train_step()
    losses = []
    for batch in data:
        state, m = step(state, batch)
        assert "accuracy" not in m  # no logits on this path
        losses.append(float(m["loss"]))
        if len(losses) >= 8:
            break
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses
    eval_metrics = trainer.make_eval_step()(state, batch)
    assert np.isfinite(float(eval_metrics["loss"]))


def test_loss_in_model_config_validation():
    from kubeflow_tpu.train import TrainConfig

    with pytest.raises(ValueError, match="train_metrics"):
        TrainConfig(loss_in_model=True)
    with pytest.raises(ValueError, match="label_smoothing"):
        TrainConfig(loss_in_model=True, train_metrics="loss")
    with pytest.raises(ValueError, match="accum_steps"):
        TrainConfig(accum_steps=0)
    with pytest.raises(ValueError, match="accumulation"):
        TrainConfig(batch_size=6, accum_steps=4)
    # The valid combination constructs.
    TrainConfig(
        loss_in_model=True, train_metrics="loss", label_smoothing=0.0,
        accum_steps=2, batch_size=8,
    )
