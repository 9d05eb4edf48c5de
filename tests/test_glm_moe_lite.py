"""The GLM-4.7-Flash-style decoder WITH its multi-token module (latent
attention whose values are as wide as a head's two parts together, a leading
dense layer, gated top-k experts beside a shared one, an untied head; the
module's block over `[embedding | hidden]` and the shared head a second
time) against the plain reference `benchmarks/reference/glm_moe_lite.py`,
at a tiny size on the CPU that keeps every ratio: 2 heads of 24 + 8 over v
of 32, ranks 12 / 8, 8 experts two a token, 1 dense + 2 expert layers + the
module."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks.drivers import train_mtp
from benchmarks.reference import glm_moe_lite as ref
from kubeflow_tpu.models.transformer import ExpertLayer, TransformerLM

NUMBERS = train_mtp.model_numbers({
    "hidden_size": 32, "intermediate_size": 48, "num_hidden_layers": 3,
    "num_attention_heads": 2, "num_key_value_heads": 2, "q_lora_rank": 12,
    "kv_lora_rank": 8, "qk_nope_head_dim": 24, "qk_rope_head_dim": 8,
    "v_head_dim": 32, "vocab_size": 64, "rms_norm_eps": 1e-5,
    "rope_theta": 1000000, "rope_scaling": None, "partial_rotary_factor": 1,
    "first_k_dense_replace": 1, "n_routed_experts": 8, "n_shared_experts": 1,
    "num_experts_per_tok": 2, "moe_intermediate_size": 16,
    "routed_scaling_factor": 1.8, "norm_topk_prob": True,
    "topk_method": "noaux_tc", "tie_word_embeddings": False,
    "num_nextn_predict_layers": 1, "mtp_weight": 0.3, "experts_routed": 8,
    "experts_first": 0,
})
B, S = 2, 32


def _config(numbers=NUMBERS, **how):
    how = {"dtype": jnp.float32, "attention_impl": "dense", "remat_policy": "none",
           **how}
    return train_mtp.transformer_config(numbers, **how)


def _seeded_leaves(key, numbers):
    """`ref.init_params` under one jit: drawn leaf by leaf it compiles a
    program a shape."""
    return jax.jit(lambda k: ref.init_params(k, numbers))(key)


def _held(numbers, key, first, count):
    """The configuration's numbers and the seeded leaves of a share that
    holds experts first .. first + count - 1 (the draw `follow` makes)."""
    cut = dict(numbers, n_routed_experts=count, num_experts=count,
               experts_first=first)
    return cut, _seeded_leaves(key, cut)


@pytest.fixture(scope="module")
def seeded():
    key = jax.random.PRNGKey(3)
    tokens = jax.random.randint(
        jax.random.PRNGKey(4), (B, S + 1), 0, NUMBERS["vocab_size"]
    )
    return key, _seeded_leaves(key, NUMBERS), tokens[:, :-1], tokens[:, 1:]


def _program_losses(cfg):
    """(L, (main_loss, mtp_loss)) as the step reads them: the model's own
    scalar, and the two it counts apart."""
    model = TransformerLM(cfg)

    def losses(params, tokens, labels):
        loss, counted = model.apply(
            {"params": params}, tokens, labels=labels, mutable=["counters"]
        )
        counted = counted["counters"]
        return loss, (counted["main_loss"], counted["mtp_loss"])

    return losses


@pytest.fixture(scope="module")
def wanted(seeded):
    """The reference's three losses and gradient of L, routed and forced,
    for a share of experts 0-3."""
    key, _, tokens, labels = seeded
    out = {}
    for forced in (False, True):
        numbers, flat = _held(dict(NUMBERS, router_force_balance=forced), key, 0, 4)
        want, grads = jax.jit(jax.value_and_grad(
            lambda p, n=numbers: ref.losses(p, tokens, labels, n)[0]
        ))(flat)
        three = jax.jit(lambda p, n=numbers: ref.losses(p, tokens, labels, n))(flat)
        assert float(want) == pytest.approx(float(three[0]))
        out[forced] = numbers, flat, three, grads
    return out


# -- program against reference ------------------------------------------------


@pytest.mark.parametrize("policy, forced", [
    ("none", False), ("none", True), ("full", True), ("mlp", True),
    ("flash", True),
])
def test_the_three_losses_and_every_gradient_leaf_match_the_reference(
    seeded, wanted, policy, forced
):
    _, _, tokens, labels = seeded
    numbers, flat, (want, want_main, want_mtp), ref_grads = wanted[forced]
    params = train_mtp.to_program_tree(flat)
    losses = _program_losses(_config(numbers, remat_policy=policy))
    (loss, (main, mtp)), grads = jax.jit(
        jax.value_and_grad(losses, has_aux=True)
    )(params, tokens, labels)
    np.testing.assert_allclose(main, want_main, rtol=2e-6)
    np.testing.assert_allclose(mtp, want_mtp, rtol=2e-6)
    np.testing.assert_allclose(loss, want, rtol=2e-6)
    np.testing.assert_allclose(loss, main + 0.3 * mtp, rtol=1e-6)
    assert abs(float(mtp) - float(main)) > 1e-3  # two targets, two numbers
    for name, got in train_mtp.from_program_tree(grads, list(flat)).items():
        np.testing.assert_allclose(
            got, ref_grads[name], atol=2e-6, rtol=1e-3, err_msg=name
        )
    # the correction gets no gradient; every other leaf of the module its own
    assert not np.any(grads["mtp"]["block"]["moe"]["router_bias"])
    for name in ("enorm", "hnorm", "head_norm"):
        assert np.any(grads["mtp"][name]["scale"]), name
    half = numbers["hidden_size"]
    assert np.any(grads["mtp"]["eh_proj"]["kernel"][:half])
    assert np.any(grads["mtp"]["eh_proj"]["kernel"][half:])
    assert set(grads["mtp"]["block"]) == {
        "attn", "ln_attn", "ln_mlp", "moe"}
    assert set(grads) == {
        "embedding", "layer_0", "layer_1", "layer_2", "ln_final", "lm_head",
        "mtp"}


def test_the_flash_calls_at_the_joined_width_give_the_dense_losses(seeded, wanted):
    """`attention_impl="flash"` (interpreted here): the one-part kernels at
    a head of 8 + 24 = 32 lanes, v's width, and none with two parts."""
    from kubeflow_tpu.testing.hlo import jaxpr_kernel_names

    _, _, tokens, labels = seeded
    numbers, flat, (want, want_main, want_mtp), _ = wanted[True]
    params = train_mtp.to_program_tree(flat)
    losses = _program_losses(_config(numbers, attention_impl="flash"))
    step = jax.jit(jax.value_and_grad(losses, has_aux=True))
    names = set(jaxpr_kernel_names(
        step.trace(params, tokens, labels).jaxpr.jaxpr
    ))
    assert any(n.startswith("flash_fwd") for n in names), names
    assert not any("mla" in n for n in names), names
    (loss, (main, mtp)), _ = step(params, tokens, labels)
    np.testing.assert_allclose(
        [loss, main, mtp], [want, want_main, want_mtp], rtol=2e-5
    )


def test_three_adamw_steps_match_the_reference(seeded):
    key = seeded[0]
    numbers, flat = _held(dict(NUMBERS, router_force_balance=True), key, 0, 4)
    opt = {"learning_rate": 1e-2, "warmup_steps": 2, "schedule_steps": 100,
           "weight_decay": 1e-2}
    batches = [
        dict(zip(("tokens", "labels"), (t[:, :-1], t[:, 1:])))
        for t in jax.random.randint(
            jax.random.PRNGKey(5), (3, B, S + 1), 0, NUMBERS["vocab_size"]
        )
    ]
    want = ref.follow(key, numbers, opt, batches, rows_per_block=1)
    params = train_mtp.to_program_tree(flat)
    tx = optax.adamw(
        lambda count: opt["learning_rate"] * count / opt["warmup_steps"],
        weight_decay=opt["weight_decay"],
    )
    state, got, first = tx.init(params), [], None
    loss_fn = jax.jit(jax.value_and_grad(
        _program_losses(_config(numbers)), has_aux=True
    ))
    for batch in batches:
        (loss, (main, mtp)), grads = loss_fn(
            params, batch["tokens"], batch["labels"]
        )
        first = grads if first is None else first
        updates, state = tx.update(grads, state, params)
        params = optax.apply_updates(params, updates)
        got.append((float(loss), float(main), float(mtp)))
    for i, name in enumerate(train_mtp.LOSSES):
        assert [g[i] for g in got] == pytest.approx(want[name], rel=1e-5), name
    norm = lambda x: float(jnp.sqrt(jnp.sum(jnp.square(x))))
    now = train_mtp.from_program_tree(params, list(flat))
    grad = train_mtp.from_program_tree(first, list(flat))
    for name in flat:
        assert norm(grad[name]) == pytest.approx(
            want["first_grad_norm"][name], rel=2e-3, abs=1e-7), name
        assert norm(now[name] - flat[name]) == pytest.approx(
            want["change_norm"][name], rel=2e-3, abs=1e-7), name


@pytest.mark.parametrize("where, index", [("layer.1.", 1), (ref.MTP, 3)],
                         ids=["a_main_layer", "the_modules_block"])
def test_the_eight_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(
    seeded, where, index
):
    """One expert on each of eight chips: what each adds, with the shared
    expert (which every chip computes alike) counted once, is the uncut
    reference's layer; the module's block folds its own index."""
    _, flat, _, _ = seeded
    numbers = dict(NUMBERS, router_force_balance=index == 3)
    p = ref.sub_params(flat, where)
    h = jax.random.normal(jax.random.PRNGKey(9), (B, S, NUMBERS["hidden_size"]))
    whole = ref.expert_layer(h, p, numbers, index)
    shared = ref._swiglu(h, p["shared_gate"], p["shared_up"], p["shared_down"], None)
    tree = train_mtp.to_program_tree({where + k: v for k, v in p.items()})
    moe = (tree["mtp"]["block"] if where == ref.MTP else tree["layer_1"])["moe"]

    @jax.jit
    def routed_parts(moe, h):
        parts = []
        for first in range(8):
            cfg = dataclasses.replace(_config(numbers), experts_held=(first, 1))
            share = dict(moe, **{
                leaf: moe[leaf][first:first + 1]
                for leaf in ("w_gate", "w_up", "w_down")
            })
            out, _ = ExpertLayer(cfg, layer=index).apply({"params": share}, h, None)
            parts.append(out - shared)
        return sum(parts)

    np.testing.assert_allclose(
        routed_parts(moe, h) + shared, whole, atol=2e-5, rtol=2e-5
    )
    assert float(jnp.max(jnp.abs(whole - shared))) > 1e-2  # the routed part
