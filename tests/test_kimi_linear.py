"""The Kimi-Linear-style decoder (a gated delta-rule mixer in most layers,
un-rotated latent attention with a directly projected q in the others, one
stack of `Block`s; a leading dense layer, then gated top-k experts beside a
shared one; an untied head) against the plain reference
`benchmarks/reference/kimi_linear.py`, at a tiny size on the CPU that keeps
every ratio: 2 heads of 16 (+ 8 un-rotated) over v of 16, a K/V rank of 8,
convolutions of 4 taps, chunks of 16, 8 experts two a token, layers KDA
(dense), KDA, MLA."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks.drivers import train_kda
from benchmarks.reference import kimi_linear as ref
from kubeflow_tpu.models.transformer import (
    Attention, AttentionKind, ExpertLayer, TransformerLM,
)
from kubeflow_tpu.testing.hlo import jaxpr_kernel_names
from kubeflow_tpu.train.trainer import softmax_cross_entropy

NUMBERS = train_kda.model_numbers({
    "hidden_size": 32, "intermediate_size": 48, "num_hidden_layers": 3,
    "num_attention_heads": 2, "num_key_value_heads": 2, "q_lora_rank": None,
    "kv_lora_rank": 8, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "vocab_size": 64, "rms_norm_eps": 1e-5,
    "mla_use_nope": True,
    "linear_attn_config": {
        "full_attn_layers": [3], "kda_layers": [1, 2], "head_dim": 16,
        "num_heads": 2, "short_conv_kernel_size": 4,
    },
    "first_k_dense_replace": 1, "num_experts": 8, "num_shared_experts": 1,
    "num_experts_per_token": 2, "moe_intermediate_size": 16,
    "routed_scaling_factor": 2.446, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "tie_word_embeddings": False,
    "num_nextn_predict_layers": 0, "experts_routed": 8, "experts_first": 0,
})
B, S = 2, 32


def _config(numbers=NUMBERS, **how):
    how = {"dtype": jnp.float32, "attention_impl": "dense", "remat_policy": "none",
           **how}
    return dataclasses.replace(
        train_kda.transformer_config(numbers, **how), ssm_chunk=16
    )


def _held(numbers, key, first, count):
    """The numbers and the seeded leaves of a share that holds experts
    first .. first + count - 1 (the draw `follow` makes)."""
    cut = dict(numbers, num_experts=count, experts_first=first)
    return cut, jax.jit(lambda k: ref.init_params(k, cut))(key)


@pytest.fixture(scope="module")
def seeded():
    key = jax.random.PRNGKey(3)
    tokens = jax.random.randint(
        jax.random.PRNGKey(4), (B, S + 1), 0, NUMBERS["vocab_size"]
    )
    return key, tokens[:, :-1], tokens[:, 1:]


def _program_loss(cfg):
    model = TransformerLM(cfg)
    return lambda params, tokens, labels: softmax_cross_entropy(
        model.apply({"params": params}, tokens), labels
    )


def test_logits_loss_and_every_gradient_leaf_match_the_reference(seeded):
    key, tokens, labels = seeded
    numbers, flat = _held(dict(NUMBERS, router_force_balance=True), key, 0, 4)
    params = train_kda.to_program_tree(flat)
    model = TransformerLM(_config(numbers))

    def program(p, t, l):
        logits = model.apply({"params": p}, t)
        return softmax_cross_entropy(logits, l), logits

    def reference(p, t, l):
        logits = ref.logits(p, t, numbers)
        log_z = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, l[..., None], axis=-1)[..., 0]
        return jnp.sum(log_z - picked), logits

    (loss, got), grads = jax.jit(jax.value_and_grad(program, has_aux=True))(
        params, tokens, labels
    )
    (ref_loss, want), ref_grads = jax.jit(
        jax.value_and_grad(reference, has_aux=True)
    )(flat, tokens, labels)
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5)
    n_tok = tokens.size
    np.testing.assert_allclose(loss, ref_loss / n_tok, rtol=1e-6)
    for name, got in train_kda.from_program_tree(grads, list(flat)).items():
        np.testing.assert_allclose(
            got, ref_grads[name] / n_tok, atol=2e-6, rtol=1e-3, err_msg=name
        )
    # one stack of blocks: a delta mixer or attention by the layer's row
    assert set(grads["layer_0"]) == {"kda", "ln_attn", "ln_mlp", "mlp"}
    assert set(grads["layer_1"]) == {"kda", "ln_attn", "ln_mlp", "moe"}
    assert set(grads["layer_2"]) == {"attn", "ln_attn", "ln_mlp", "moe"}
    assert set(grads["layer_2"]["attn"]) == {
        "wq", "wkv_a", "kv_norm", "wkv_b", "wo"}
    for leaf in ("A_log", "dt_bias", "conv_q", "norm_scale", "wb"):
        assert np.any(grads["layer_1"]["kda"][leaf]), leaf
    assert not np.any(grads["layer_1"]["moe"]["router_bias"])


def test_three_adamw_steps_match_the_reference(seeded):
    key = seeded[0]
    numbers, flat = _held(NUMBERS, key, 0, 4)
    opt = {"learning_rate": 1e-2, "warmup_steps": 2, "schedule_steps": 100,
           "weight_decay": 1e-2}
    batches = [
        dict(zip(("tokens", "labels"), (t[:, :-1], t[:, 1:])))
        for t in jax.random.randint(
            jax.random.PRNGKey(5), (3, B, S + 1), 0, NUMBERS["vocab_size"]
        )
    ]
    want = ref.follow(key, numbers, opt, batches, rows_per_block=1)
    params = train_kda.to_program_tree(flat)
    tx = optax.adamw(
        lambda count: opt["learning_rate"] * count / opt["warmup_steps"],
        weight_decay=opt["weight_decay"],
    )
    state, losses, first = tx.init(params), [], None
    loss_fn = jax.jit(jax.value_and_grad(_program_loss(_config(numbers))))
    for batch in batches:
        loss, grads = loss_fn(params, batch["tokens"], batch["labels"])
        first = grads if first is None else first
        updates, state = tx.update(grads, state, params)
        params = optax.apply_updates(params, updates)
        losses.append(float(loss))
    assert losses == pytest.approx(want["loss"], rel=1e-5)
    norm = lambda x: float(jnp.sqrt(jnp.sum(jnp.square(x))))
    now = train_kda.from_program_tree(params, list(flat))
    grad = train_kda.from_program_tree(first, list(flat))
    for name in flat:
        assert norm(grad[name]) == pytest.approx(
            want["first_grad_norm"][name], rel=2e-3, abs=1e-7), name
        assert norm(now[name] - flat[name]) == pytest.approx(
            want["change_norm"][name], rel=2e-3, abs=1e-7), name


def _unboxed(module, *args):
    return jax.tree_util.tree_map(
        lambda v: v.value if hasattr(v, "value") else v,
        module.init(jax.random.PRNGKey(7), *args)["params"],
        is_leaf=lambda v: hasattr(v, "value"),
    )


def test_the_unrotated_direct_q_latent_layer_matches_the_references():
    """`q_latent` 0 projects q by parts from the input and a row with a
    `rope_fraction` of 0 turns neither 64-wide part."""
    cfg = _config()
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model))
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    layer = Attention(cfg, kind=cfg.attention_kinds[1])
    params = _unboxed(layer, x, positions)
    assert set(params) == {"wq", "wkv_a", "kv_norm", "wkv_b", "wo"}
    got = layer.apply({"params": params}, x, positions)
    with jax.default_matmul_precision("highest"):
        want = ref.mla_layer(x, {
            "wq": params["wq"], "wkv_a": params["wkv_a"]["kernel"],
            "kv_norm": params["kv_norm"]["scale"], "wkv_b": params["wkv_b"],
            "wo": params["wo"]["kernel"],
        }, NUMBERS)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    # positions do not enter: no part is turned
    again = layer.apply({"params": params}, x, positions + 5)
    np.testing.assert_array_equal(got, again)


def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(seeded):
    """Experts 0-3 on one chip and 4-7 on another: what each adds, with the
    shared expert (which every chip computes alike) counted once, is the
    uncut reference's layer."""
    key = seeded[0]
    _, flat = _held(NUMBERS, key, 0, 8)
    p = ref._xing.layer_params(flat, 1)
    h = jax.random.normal(jax.random.PRNGKey(9), (B, S, NUMBERS["hidden_size"]))
    whole = ref._xing.expert_layer(h, p, ref._experts_cfg(NUMBERS), 1)
    shared = ref._xing._swiglu(
        h, p["shared_gate"], p["shared_up"], p["shared_down"], None
    )
    parts = []
    for first in (0, 4):
        cfg = dataclasses.replace(_config(), experts_held=(first, 4))
        share = dict(train_kda.to_program_tree({
            f"layer.1.{k}": v for k, v in p.items()
            if not k.startswith(("kda_", "ln_"))
        })["layer_1"]["moe"])
        for leaf in ("w_gate", "w_up", "w_down"):
            share[leaf] = share[leaf][first:first + 4]
        out, _ = ExpertLayer(cfg, layer=1).apply({"params": share}, h, None)
        parts.append(out - shared)
    np.testing.assert_allclose(
        parts[0] + parts[1] + shared, whole, atol=2e-5, rtol=2e-5
    )


def test_flash_policy_runs_the_kernels_once_and_gives_the_same_gradient(seeded):
    """Under `flash` with the kernels (interpreted here) the backward holds
    `kda_bwd` and no second `kda_fwd`; loss and gradient are the plain
    chunked form's."""
    from kubeflow_tpu.ops import kda

    key, tokens, labels = seeded
    numbers, flat = _held(
        dict(NUMBERS, num_hidden_layers=2, router_force_balance=True,
             linear_attn_config=dict(
                 NUMBERS["linear_attn_config"], kda_layers=[1],
                 full_attn_layers=[2])),
        key, 0, 4,
    )
    params = train_kda.to_program_tree(flat)
    want = jax.jit(jax.value_and_grad(_program_loss(_config(numbers))))(
        params, tokens, labels
    )
    patch = pytest.MonkeyPatch()
    try:
        cfg = _config(numbers, remat_policy="flash")
        loss = _program_loss(cfg)
        real = kda.kda_scan
        patch.setattr(
            "kubeflow_tpu.models.transformer.kda_scan",
            lambda *a, **kw: real(*a, **kw, interpret=True),
        )
        names = jaxpr_kernel_names(
            jax.make_jaxpr(jax.grad(loss))(params, tokens, labels).jaxpr
        )
        got = jax.jit(jax.value_and_grad(loss))(params, tokens, labels)
    finally:
        patch.undo()
    assert names.count("kda_fwd") == 1 and names.count("kda_bwd") == 1
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(want[1])):
        np.testing.assert_allclose(a, b, atol=2e-6, rtol=2e-3)


@pytest.mark.parametrize("change, message", [
    (dict(attention_kinds=(AttentionKind(2, mixer="rnn"),),
          attention_pattern=(0, 0, 0)), "mixer 'rnn'"),
    (dict(attention_kinds=(AttentionKind(2, window=4, mixer="delta"),),
          attention_pattern=(0, 0, 0)), "a window of 4"),
    (dict(ssm_chunk=24), "chunks of 24"),
    (dict(kv_latent=0), "takes a K/V rank"),
    (dict(attention_kinds=(
        AttentionKind(2, mixer="delta"), AttentionKind(4, rope_fraction=0.0),
    )), "equal heads in every attention row"),
])
def test_a_configuration_that_cannot_be_built_is_refused(change, message):
    cfg = dataclasses.replace(_config(), **change)
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match=message):
        TransformerLM(cfg).init(jax.random.PRNGKey(0), tokens)


def test_the_mixers_counters_are_means_over_its_layers():
    cfg = _config(dict(NUMBERS, router_force_balance=True))
    model = TransformerLM(cfg)
    tokens = jnp.zeros((1, S), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    _, mutated = model.apply(variables, tokens, mutable=["counters"])
    total = lambda name: sum(
        float(v[name]) for v in jax.tree_util.tree_leaves(
            mutated["counters"], is_leaf=lambda v: isinstance(v, dict) and name in v
        ) if isinstance(v, dict) and name in v
    )
    assert 0.5 < total("kda_decay_mean") < 1.0
    assert 0.2 < total("kda_beta_mean") < 0.8


# -- the convolutions, `silu` and a head's norms as kernels ---------------------

WIDE = dict(
    NUMBERS, num_hidden_layers=2, router_force_balance=True,
    qk_nope_head_dim=128, v_head_dim=128,
    linear_attn_config=dict(
        NUMBERS["linear_attn_config"], head_dim=128, kda_layers=[1],
        full_attn_layers=[2],
    ),
)
WIDE_SHAPE = (2, 128)  # heads of one lane tile, a sequence of one row block


def _wide_loss_and_gradient(policy, kernels: bool, dtype=jnp.bfloat16):
    """Loss and gradient of a delta layer and a latent one at heads of 128
    lanes, the convolutions and the gated norm as XLA's passes or as
    `shortconv_*` and `gatenorm_*` (which the CPU is told compile, and
    interprets)."""
    from kubeflow_tpu.ops import gatenorm, shortconv

    key = jax.random.PRNGKey(5)
    numbers, flat = _held(WIDE, key, 0, 4)
    params = train_kda.to_program_tree(flat)
    tokens = jax.random.randint(
        jax.random.PRNGKey(6), (WIDE_SHAPE[0], WIDE_SHAPE[1] + 1), 0, 64
    )
    loss = _program_loss(_config(numbers, remat_policy=policy, dtype=dtype))
    patch = pytest.MonkeyPatch()
    try:
        for ops in (shortconv, gatenorm) if kernels else ():
            patch.setattr(ops, "kernels_apply", functools.partial(
                ops.kernels_apply, compiled=True
            ))
        # The CPU has no bfloat16 x bfloat16 = float32 product of the plain
        # delta rule's shapes: the rule itself in float32, either way.
        from kubeflow_tpu.ops import kda

        wide = lambda u: u.astype(jnp.float32)
        patch.setattr(
            "kubeflow_tpu.models.transformer.kda_scan",
            lambda q, k, v, *a, **kw: kda.kda_scan(
                wide(q), wide(k), wide(v), *a, **kw
            ).astype(q.dtype),
        )
        args = (params, tokens[:, :-1], tokens[:, 1:])
        names = jaxpr_kernel_names(jax.make_jaxpr(jax.grad(loss))(*args).jaxpr)
        return names, jax.jit(jax.value_and_grad(loss))(*args)
    finally:
        patch.undo()


@functools.cache
def _wide_plain(policy):
    return _wide_loss_and_gradient(policy, kernels=False)


@functools.cache
def _wide_kernels(policy):
    return _wide_loss_and_gradient(policy, kernels=True)


@pytest.mark.parametrize("policy", ["none", "full", "mlp", "flash"])
def test_the_convolutions_as_kernels_give_the_plain_paths_loss_and_gradient(
    policy,
):
    """Under every remat policy: three `shortconv_fwd` (q, k, v) and three
    `shortconv_bwd` a delta layer, the forward's three run again where a
    checkpoint keeps nothing of them ("full", and "flash" with no limit
    known: the CPU's plan admits no name); the loss and every gradient
    leaf are the plain expression's under the same policy within
    bfloat16's rounding."""
    plain_names, want = _wide_plain(policy)
    assert not [n for n in plain_names if n.startswith("shortconv")]
    names, got = _wide_kernels(policy)
    again = 3 if policy in ("full", "flash") else 0
    assert names.count("shortconv_fwd") == 3 + again
    assert names.count("shortconv_bwd") == 3
    np.testing.assert_allclose(got[0], want[0], rtol=2e-3)
    for a, b in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(want[1])):
        gap = np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-6)
        # the decay's leaves carry their own noise (PERF.md §4)
        assert gap < 5e-2, gap


@pytest.mark.parametrize("policy", ["none", "full", "mlp", "flash"])
def test_the_gated_norm_is_one_kernel_each_way_a_delta_layer(policy):
    """The same programs (held to the plain path's loss and gradients
    above): one `gatenorm_fwd` and one `gatenorm_bwd` the delta layer, the
    forward's run again only where a checkpoint keeps nothing of it."""
    assert not [n for n in _wide_plain(policy)[0] if n.startswith("gatenorm")]
    names, _ = _wide_kernels(policy)
    assert names.count("gatenorm_fwd") == 1 + (policy in ("full", "flash"))
    assert names.count("gatenorm_bwd") == 1


def test_float32_streams_take_the_plain_convolutions_whatever_the_backend():
    """What `shortconv.kernels_apply` and `gatenorm.kernels_apply` refuse
    runs the plain expression: the traced gradient is the one traced where
    no kernel compiles."""
    told, _ = _wide_loss_and_gradient("none", kernels=True, dtype=jnp.float32)
    plain, _ = _wide_loss_and_gradient("none", kernels=False, dtype=jnp.float32)
    assert told == plain
    assert not {"shortconv_fwd", "gatenorm_fwd"} & set(told)
