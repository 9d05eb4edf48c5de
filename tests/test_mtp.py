"""The multi-token module of `TransformerLM` (`mtp_layers`): what it reads
and predicts, the two uses of the embedding and of the head, the weight, the
parameter tree without it and the refusals; and `attend`'s three forms for
latent attention by the values' width (`ops/attention.latent_form`)."""

import dataclasses
import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models.transformer import (
    AttentionKind, MultiTokenModule, PipelinedTransformerLM, TransformerConfig,
    TransformerLM, _attention_kinds,
)
from kubeflow_tpu.ops import attention as attention_ops
from kubeflow_tpu.ops.attention import attend, dense_attention, latent_form
from kubeflow_tpu.train.trainer import softmax_cross_entropy

B, S, V = 2, 16, 64
CFG = TransformerConfig(
    vocab_size=V, d_model=32, n_layers=3, n_heads=2, head_dim=24, q_latent=12,
    kv_latent=8, rope_head_dim=8, v_head_dim=32,
    attention_kinds=(AttentionKind(n_heads=2, rope_theta=1e6),),
    attention_pattern=(0, 0, 0), dense_layers=1, dense_d_ff=48, d_ff=16,
    num_experts=8, experts_held=(0, 4), experts_per_token=2, router="sigmoid",
    routed_scaling=1.8, moe_shared_ff=16, tie_embeddings=False, norm_eps=1e-5,
    mtp_layers=1, mtp_weight=0.3, dtype=jnp.float32, attention_impl="dense",
    remat_policy="none",
)


def _unboxed(tree):
    return jax.tree_util.tree_map(
        lambda v: v.value if hasattr(v, "value") else v, tree,
        is_leaf=lambda v: hasattr(v, "value"),
    )


@pytest.fixture(scope="module")
def seeded():
    tokens = jax.random.randint(jax.random.PRNGKey(4), (B, S + 1), 0, V)
    params = _unboxed(
        TransformerLM(CFG).init(jax.random.PRNGKey(1), tokens[:, :-1])["params"]
    )
    return params, tokens[:, :-1], tokens[:, 1:]


def _losses(cfg, params, tokens, labels):
    loss, counted = TransformerLM(cfg).apply(
        {"params": params}, tokens, labels=labels, mutable=["counters"]
    )
    return loss, counted["counters"]


def test_the_objective_is_main_plus_the_weight_times_the_modules(seeded):
    params, tokens, labels = seeded
    loss, counted = jax.jit(functools.partial(_losses, CFG))(params, tokens, labels)
    main, mtp = counted["main_loss"], counted["mtp_loss"]
    np.testing.assert_allclose(loss, main + 0.3 * mtp, rtol=1e-6)
    logits = TransformerLM(CFG).apply({"params": params}, tokens)
    assert logits.shape == (B, S, V)  # without labels: the main logits alone
    np.testing.assert_allclose(
        main, softmax_cross_entropy(logits, labels), rtol=1e-6
    )
    # the routing counters gain the module's row: three sparse blocks
    assert set(counted) >= {"layer_1", "layer_2", "mtp"}
    assert "moe_tokens_held" in counted["mtp"]["block"]["moe"]


def test_the_module_predicts_the_token_after_next_and_masks_the_last(seeded):
    """Position i's second target is t_(i+2) = labels[i + 1], so the LAST
    label is position S - 2's; what is masked is position S - 1, which has
    none: its input (the last token, and the last label as the module's
    embedding reads it there) moves no term of `mtp_loss`."""
    params, tokens, labels = seeded
    both = jax.jit(lambda t, l: (lambda c: (c["main_loss"], c["mtp_loss"]))(
        _losses(CFG, params, t, l)[1]
    ))
    main, mtp = both(tokens, labels)
    other = lambda x, at: x.at[:, at].set((x[:, at] + 1) % V)
    # the last position's input: the main loss moves, the module's does not
    main_last, mtp_last = both(other(tokens, S - 1), labels)
    assert float(mtp_last) == float(mtp) and float(main_last) != float(main)
    # the module's own loss at every position, by the shared head
    further = lambda l: _further(params, tokens, l)
    nll = lambda z, t: jax.nn.logsumexp(z, -1) - jnp.take_along_axis(
        z, t[..., None], -1
    )[..., 0]
    by_position = nll(further(labels)[:, :-1], labels[:, 1:])
    np.testing.assert_allclose(jnp.mean(by_position), mtp, rtol=1e-6)
    # were the target t_(i+1), the main model's, it would read otherwise
    assert abs(float(jnp.mean(nll(further(labels), labels))) - float(mtp)) > 1e-3
    # a changed t_(i+2) moves position i's term (and what position i + 1
    # reads), and no earlier one
    at = S // 2
    moved = nll(further(other(labels, at))[:, :-1], other(labels, at)[:, 1:])
    np.testing.assert_array_equal(moved[:, :at - 1], by_position[:, :at - 1])
    assert np.all(np.asarray(moved[:, at - 1]) != np.asarray(by_position[:, at - 1]))


def _further(params, tokens, labels):
    """The module's logits, through `MultiTokenModule` behind the main
    model's normed output."""
    plain = TransformerLM(dataclasses.replace(CFG, mtp_layers=0))
    _, seen = plain.apply(
        {"params": {k: v for k, v in params.items() if k != "mtp"}}, tokens,
        mutable=["intermediates"],
        capture_intermediates=lambda module, _: module.name == "ln_final",
    )
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), tokens.shape)
    return MultiTokenModule(CFG).apply(
        {"params": params["mtp"]},
        seen["intermediates"]["ln_final"]["__call__"][0], labels,
        params["embedding"], params["lm_head"], positions, None,
    )


def test_the_embedding_and_the_head_get_the_sum_of_their_two_uses(seeded):
    """The main model and `MultiTokenModule` apart, the module handed
    matrices of its own: the whole model's gradient of the embedding (of
    the head) is the sum of the two."""
    params, tokens, labels = seeded
    main_params = {k: v for k, v in params.items() if k != "mtp"}
    plain = TransformerLM(dataclasses.replace(CFG, mtp_layers=0))
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), tokens.shape)

    def apart(main_params, module_params, embed, head):
        logits, seen = plain.apply(
            {"params": main_params}, tokens, mutable=["intermediates"],
            capture_intermediates=lambda module, _: module.name == "ln_final",
        )
        h = seen["intermediates"]["ln_final"]["__call__"][0]
        further = MultiTokenModule(CFG).apply(
            {"params": module_params}, h, labels, embed, head, positions, None
        )
        return softmax_cross_entropy(logits, labels) + 0.3 * (
            softmax_cross_entropy(further[:, :-1], labels[:, 1:])
        )

    loss, (g_main, g_module, g_embed, g_head) = jax.jit(jax.value_and_grad(
        apart, argnums=(0, 1, 2, 3)
    ))(main_params, params["mtp"], params["embedding"], params["lm_head"])
    whole, grads = jax.jit(jax.value_and_grad(
        lambda p: _losses(CFG, p, tokens, labels)[0]
    ))(params)
    np.testing.assert_allclose(loss, whole, rtol=1e-6)
    for name, second in (("embedding", g_embed), ("lm_head", g_head)):
        assert np.any(second) and np.any(g_main[name]), name
        np.testing.assert_allclose(
            grads[name], g_main[name] + second, atol=1e-7, rtol=1e-5,
            err_msg=name,
        )
    jax.tree_util.tree_map(
        lambda got, want: np.testing.assert_allclose(
            got, want, atol=1e-7, rtol=1e-5
        ), grads["mtp"], g_module,
    )


def test_a_weight_of_zero_gives_the_main_models_gradients(seeded):
    """... and the module's leaves exactly none."""
    params, tokens, labels = seeded
    grad = lambda cfg, p: jax.jit(jax.grad(
        lambda p: _losses(cfg, p, tokens, labels)[0]
    ))(p)
    off = grad(dataclasses.replace(CFG, mtp_weight=0.0), params)
    main_params = {k: v for k, v in params.items() if k != "mtp"}
    plain = grad(dataclasses.replace(CFG, mtp_layers=0), main_params)
    assert not any(np.any(g) for g in jax.tree_util.tree_leaves(off["mtp"]))
    # (to rounding: XLA fuses the two programs' sums in another order)
    jax.tree_util.tree_map(
        lambda got, want: np.testing.assert_allclose(
            got, want, atol=5e-6, rtol=1e-5
        ), {k: v for k, v in off.items() if k != "mtp"}, plain,
    )
    # ... and without the module the parameter tree is the parent's
    tree = lambda cfg: jax.tree_util.tree_structure(_unboxed(jax.eval_shape(
        TransformerLM(cfg).init, jax.random.PRNGKey(0), tokens
    )["params"]))
    assert tree(dataclasses.replace(CFG, mtp_layers=0)) == (
        jax.tree_util.tree_structure(main_params)
    )
    assert tree(CFG) == jax.tree_util.tree_structure(params)


def test_fit_records_both_losses_and_eval_reports_the_main_one(seeded):
    """Through `Trainer` + `fit()` under `loss_in_model`: `loss` is L, the
    two terms ride the counters into every record, and the eval step
    reports the MAIN loss as `loss` (no other model's eval is a sum) with
    the module's beside it."""
    from kubeflow_tpu.parallel import MeshSpec, build_mesh
    from kubeflow_tpu.train import TrainConfig, Trainer, fit

    _, tokens, labels = seeded
    mesh = build_mesh(MeshSpec(), jax.devices()[:1])
    trainer = Trainer(
        TransformerLM(CFG, mesh=mesh),
        TrainConfig(batch_size=B, optimizer="adamw", label_smoothing=0.0,
                    fsdp_params=False, train_metrics="loss",
                    loss_in_model=True, learning_rate=1e-3, warmup_steps=1),
        mesh, example_input_shape=(B, S), example_input_dtype=jnp.int32,
        input_key="tokens", label_key="labels",
    )
    batch = {"tokens": tokens, "labels": labels}
    result = fit(trainer, iter([batch] * 2), 2, log_every=1, handle_signals=False)
    assert len(result.history) == 2
    for record in result.history:
        assert record["loss"] == pytest.approx(
            record["main_loss"] + 0.3 * record["mtp_loss"], rel=1e-5
        )
        assert record["moe_tokens_held"] > 0
    evaluated = trainer.make_eval_step()(result.state, batch)
    assert set(evaluated) == {"loss", "mtp_loss"}
    want, counted = _losses(CFG, result.state.params, tokens, labels)
    assert float(evaluated["loss"]) == pytest.approx(float(counted["main_loss"]), rel=1e-6)
    assert float(evaluated["loss"]) < float(want)


@pytest.mark.parametrize("change, numbers", [
    (dict(mtp_layers=2), "2 multi-token module"),
    (dict(residual_streams=4), "4 residual streams"),
    (dict(layer_pattern="*E*", attention_kinds=(), attention_pattern=(),
          dense_layers=0), "layer_pattern of '\\*E\\*'"),
    (dict(mtp_layers=-1), "-1 multi-token module"),
])
def test_what_is_not_built_is_refused_with_its_numbers(change, numbers):
    with pytest.raises(ValueError, match=numbers):
        _attention_kinds(dataclasses.replace(CFG, **change))


def test_the_pipelined_model_refuses_the_module():
    cfg = TransformerConfig(
        vocab_size=V, d_model=32, n_layers=2, n_heads=2, head_dim=16, d_ff=32,
        mtp_layers=1,
    )
    model = PipelinedTransformerLM(cfg, n_stages=2, num_microbatches=2)
    with pytest.raises(ValueError, match=r"multi-token module \(1\)"):
        model.init(jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))


# -- `attend` by the values' width ---------------------------------------------


def test_the_form_follows_the_three_widths():
    assert latent_form(128, 64, 128) == "two_part"   # xing's, kimi's
    assert latent_form(192, 64, 256) == "joined"     # GLM-4.7-Flash's
    assert latent_form(192, 64, 128) == "dense"
    assert latent_form(16, 8, 16) == "two_part" and latent_form(24, 8, 32) == "joined"


@pytest.mark.parametrize("own, rope, wide, impl, kernels", [
    (16, 8, 16, "flash", ("mla",)),
    (24, 8, 32, "flash", ("flash_fwd",)),
    (16, 8, 20, "auto", ()),
], ids=["two_part", "joined", "dense"])
def test_attends_three_forms_agree_with_the_dense_two_part_scores(
    own, rope, wide, impl, kernels
):
    """Forward, dq, dk, dv and the ONE rope key's gradient (the kernels
    interpreted); the joined form runs the one-part calls at own + rope."""
    from kubeflow_tpu.testing.hlo import jaxpr_kernel_names

    h, s = 2, 64
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    q, k = (jax.random.normal(key, (1, s, h, own)) for key in keys[:2])
    v = jax.random.normal(keys[2], (1, s, h, wide))
    q_rope = jax.random.normal(keys[3], (1, s, h, rope))
    k_rope = jax.random.normal(keys[4], (1, s, rope))
    weigh = jax.random.normal(keys[5], (1, s, h, wide))

    def run(f):
        def loss(q, k, v, q_rope, k_rope):
            out = f(q, k, v, q_rope=q_rope, k_rope=k_rope)
            return jnp.sum(out * weigh), out
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)

    form = functools.partial(attend, mesh=None, impl=impl)
    (_, want), want_grads = run(functools.partial(dense_attention, causal=True))(
        q, k, v, q_rope, k_rope
    )
    (_, got), got_grads = jax.jit(run(form))(q, k, v, q_rope, k_rope)
    names = set(jaxpr_kernel_names(
        jax.jit(run(form)).trace(q, k, v, q_rope, k_rope).jaxpr.jaxpr
    ))
    assert bool(names) == bool(kernels), names
    for part in kernels:
        assert any(part in n for n in names), (part, names)
    if form == "joined":
        assert not any("mla" in n for n in names), names
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert got_grads[4].shape == k_rope.shape
    for name, a, b in zip(("dq", "dk", "dv", "dq_rope", "dk_rope"),
                          got_grads, want_grads):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5, err_msg=name)


def test_a_width_no_kernel_has_says_so_where_the_kernels_would_have_run(
    monkeypatch
):
    q = k = jnp.zeros((1, 8, 2, 16))
    v = jnp.zeros((1, 8, 2, 20))
    pair = dict(q_rope=jnp.zeros((1, 8, 2, 8)), k_rope=jnp.zeros((1, 8, 8)))
    with pytest.raises(ValueError, match="16 \\+ 8 dims over values 20 wide"):
        attend(q, k, v, mesh=None, impl="flash", **pair)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # the CPU: dense anyway, nothing to say
        attend(q, k, v, mesh=None, impl="auto", **pair)
    monkeypatch.setattr(attention_ops, "kernels_compiled", lambda: True)
    with pytest.warns(RuntimeWarning, match="16 \\+ 8 dims over values 20"):
        attend(q, k, v, mesh=None, impl="auto", **pair)
