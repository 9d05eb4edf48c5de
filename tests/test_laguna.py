"""The Laguna-style decoder (global and window attention layers in one
stack, a sigmoid gate a query head, yarn on the global layers, a leading
dense layer, then gated top-k experts beside a shared one; an untied head)
against the plain reference `benchmarks/reference/laguna.py`, at a tiny
size on the CPU: 4 and 6 query heads over 2 K/V heads of 16, window 8 at
S = 32, 8 experts three a token, a leading dense layer then one period."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks.drivers import train_window
from benchmarks.lib import flops_window
from benchmarks.reference import laguna as ref
from kubeflow_tpu.models.transformer import (
    AttentionKind, Block, TransformerConfig, TransformerLM,
)
from kubeflow_tpu.testing.hlo import jaxpr_kernel_names
from kubeflow_tpu.train.trainer import softmax_cross_entropy

NUMBERS = {
    "hidden_size": 32, "intermediate_size": 48, "num_hidden_layers": 5,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 64, "rms_norm_eps": 1e-6, "num_experts": 8,
    "experts_routed": 8, "experts_first": 0, "num_experts_per_tok": 3,
    "moe_intermediate_size": 16, "shared_expert_intermediate_size": 24,
    "norm_topk_prob": True, "moe_routed_scaling_factor": 2.5,
    "mlp_only_layers": [0], "tie_word_embeddings": False, "gating": "per-head",
    "sliding_window": 8,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 8,
            "original_max_position_embeddings": 16, "beta_slow": 1,
            "beta_fast": 4, "attention_factor": 1.2,
            "partial_rotary_factor": 0.5,
        },
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000,
            "partial_rotary_factor": 1,
        },
    },
    "layer_types": ["full_attention"] + ["sliding_attention"] * 3
    + ["full_attention"],
    "mlp_layer_types": ["dense"] + ["sparse"] * 4,
    "num_attention_heads_per_layer": [4, 6, 6, 6, 4],
}
B, S = 2, 32


def _config(numbers=NUMBERS, **how):
    how = {"dtype": jnp.float32, "attention_impl": "dense", "remat_policy": "none",
           **how}
    return train_window.transformer_config(numbers, **how)


def _held(numbers, key, first, count):
    """The configuration's numbers and the seeded leaves of a share that
    holds experts first .. first + count - 1 (the draw `follow` makes)."""
    cut = dict(numbers, num_experts=count, experts_first=first)
    return cut, ref.init_params(key, cut)


@pytest.fixture(scope="module")
def seeded():
    key = jax.random.PRNGKey(3)
    flat = ref.init_params(key, NUMBERS)
    tokens = jax.random.randint(
        jax.random.PRNGKey(4), (B, S + 1), 0, NUMBERS["vocab_size"]
    )
    return key, flat, tokens[:, :-1], tokens[:, 1:]


def _program_loss(cfg):
    model = TransformerLM(cfg)
    return lambda params, tokens, labels: softmax_cross_entropy(
        model.apply({"params": params}, tokens), labels
    )


# -- program against reference ------------------------------------------------


@pytest.mark.parametrize("held", [(0, 8), (0, 4)], ids=["all", "half"])
@pytest.mark.parametrize("forced", [False, True], ids=["routed", "forced"])
def test_logits_loss_and_every_gradient_leaf_match_the_reference(
    seeded, held, forced
):
    key, _, tokens, labels = seeded
    numbers, flat = _held(
        dict(NUMBERS, router_force_balance=forced), key, *held
    )
    params = train_window.to_program_tree(flat)
    got = TransformerLM(_config(numbers)).apply({"params": params}, tokens)
    np.testing.assert_allclose(
        got, ref.logits(flat, tokens, numbers), atol=5e-5, rtol=5e-5
    )
    loss, grads = jax.value_and_grad(_program_loss(_config(numbers)))(
        params, tokens, labels
    )
    ref_loss, ref_grads = jax.value_and_grad(ref.summed_loss)(
        flat, tokens, labels, numbers
    )
    n_tok = tokens.size
    np.testing.assert_allclose(loss, ref_loss / n_tok, rtol=1e-6)
    for name, got in train_window.from_program_tree(grads, list(flat)).items():
        np.testing.assert_allclose(
            got, ref_grads[name] / n_tok, atol=2e-6, rtol=5e-4, err_msg=name
        )
    # the correction gets no gradient; the gate, the head and the dense
    # layer their own
    assert not np.any(grads["layer_1"]["moe"]["router_bias"])
    assert np.any(grads["layer_1"]["attn"]["wg"]) and np.any(grads["lm_head"])
    assert set(grads["layer_0"]) == {"attn", "ln_attn", "ln_mlp", "mlp"}
    assert set(grads["layer_1"]) == {"attn", "ln_attn", "ln_mlp", "moe"}


@pytest.mark.parametrize("held", [(0, 8), (0, 4)], ids=["all", "half"])
def test_three_adamw_steps_match_the_reference(seeded, held):
    key = seeded[0]
    numbers, flat = _held(NUMBERS, key, *held)
    opt = {"learning_rate": 1e-2, "warmup_steps": 2, "schedule_steps": 100,
           "weight_decay": 1e-2}
    batches = [
        dict(zip(("tokens", "labels"), (t[:, :-1], t[:, 1:])))
        for t in jax.random.randint(
            jax.random.PRNGKey(5), (3, B, S + 1), 0, NUMBERS["vocab_size"]
        )
    ]
    want = ref.follow(key, numbers, opt, batches, rows_per_block=1)
    params = train_window.to_program_tree(flat)
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
    now = train_window.from_program_tree(params, list(flat))
    grad = train_window.from_program_tree(first, list(flat))
    for name in flat:
        assert norm(grad[name]) == pytest.approx(
            want["first_grad_norm"][name], rel=2e-3, abs=1e-7
        ), name
        assert norm(now[name] - flat[name]) == pytest.approx(
            want["change_norm"][name], rel=2e-3, abs=1e-7
        ), name


@pytest.mark.parametrize("remat", ["full", "mlp", "flash"])
def test_the_kernels_and_remat_policies_give_the_dense_gradients(seeded, remat):
    """The band kernels (interpreted), the rope kernel's tables and the
    rows' movers in the model's step, against dense attention."""
    _, flat, tokens, labels = seeded
    params = train_window.to_program_tree(flat)
    want = jax.grad(_program_loss(_config()))(params, tokens, labels)
    cfg = _config(attention_impl="flash", remat_policy=remat)
    got = jax.grad(_program_loss(cfg))(params, tokens, labels)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=5e-6, rtol=5e-4)


def test_the_step_traces_both_kinds_of_flash_call_and_names_them(seeded):
    _, flat, tokens, labels = seeded
    params = train_window.to_program_tree(flat)
    cfg = _config(attention_impl="flash", remat_policy="flash")
    jaxpr = jax.make_jaxpr(jax.grad(_program_loss(cfg)))(params, tokens, labels)
    names = jaxpr_kernel_names(jaxpr.jaxpr)
    count = lambda name: sum(n == name for n in names)
    # two global and three window layers; `remat: flash` keeps each forward
    # kernel out of the backward
    assert count("flash_fwd_compact") == 2 and count("flash_bwd_fused") == 2
    assert count("flash_fwd_window") == 3 and count("flash_bwd_window_fused") == 3
    # the scopes a device trace's `op_name`s carry
    text = jax.jit(_program_loss(cfg)).lower(params, tokens, labels).as_text(
        debug_info=True
    )
    for scope in ("attend.full", "attend.window", "attn.gate", "moe.route"):
        assert scope in text, scope


def test_the_gate_counter_is_the_mean_over_heads_positions_and_layers(seeded):
    _, flat, tokens, _ = seeded
    params = train_window.to_program_tree(flat)
    _, out = TransformerLM(_config()).apply(
        {"params": params}, tokens, mutable=["counters"]
    )
    sown = [out["counters"][f"layer_{i}"]["attn"]["attn_gate_mean"] for i in range(5)]
    x = flat["embedding"][tokens]
    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    first = jnp.mean(jax.nn.sigmoid(h @ flat["layer.0.wg"]))
    np.testing.assert_allclose(sown[0] * 5, first, rtol=1e-5)
    assert 0.3 < float(sum(sown)) < 0.7  # a mean of sigmoids of a plain draw


# -- the shares of a deployment ------------------------------------------------


def test_the_expert_shares_add_up_to_the_whole_layer(seeded):
    """Four shares of two experts: the routed parts, with attention, the
    shared expert and the residual counted once, are the uncut reference's
    layer."""
    _, flat, _, _ = seeded
    i = 2
    names = [n for n in flat if n.startswith(f"layer.{i}.")]
    tree = train_window.to_program_tree({n: flat[n] for n in names})[f"layer_{i}"]
    theirs = ref.layer_params(flat, i)
    x = jax.random.normal(jax.random.PRNGKey(6), (B, S, 32))
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    want = ref.layer(x, theirs, NUMBERS, i)
    kinds, pattern = train_window.attention_kinds(NUMBERS)

    def share(first, count, scale=1.0):
        held = dict(tree, moe={
            k: v[first:first + count] * scale if k.startswith("w_") else v
            for k, v in tree["moe"].items()
        })
        cfg = dataclasses.replace(_config(), experts_held=(first, count))
        return Block(cfg, layer=i, attention=kinds[pattern[i]]).apply(
            {"params": held}, x, positions
        )[0]

    once = share(0, 2, scale=0.0)  # the residual, attention, the shared expert
    parts = [share(first, 2) - once for first in (0, 2, 4, 6)]
    np.testing.assert_allclose(sum(parts) + once, want, atol=5e-5, rtol=5e-5)
    assert all(float(jnp.abs(p).max()) > 1e-3 for p in parts)
    # ... and the reference given a share leaves the same part out.
    cut = dict(NUMBERS, num_experts=2, experts_first=4)
    theirs_cut = dict(theirs, **{
        k: theirs[k][4:6] for k in ("w_gate", "w_up", "w_down")
    })
    np.testing.assert_allclose(
        share(4, 2), ref.layer(x, theirs_cut, cut, i), atol=5e-5, rtol=5e-5
    )


# -- what cannot be built, and what stays as it was --------------------------


@pytest.mark.parametrize("change, message", [
    (dict(attention_kinds=(AttentionKind(5),), attention_pattern=(0,) * 5),
     "5 query heads are not a multiple of the 2 K/V heads"),
    (dict(attention_kinds=(AttentionKind(4, window=0),),
          attention_pattern=(0,) * 5), "a window of 0 key"),
    (dict(attention_pattern=(0, 1, 1)), "names 3 layers of 2 kind"),
    (dict(attention_pattern=(0, 1, 1, 1, 2)), "names 5 layers of 2 kind"),
    (dict(dense_layers=6), "6 leading dense layer"),
    (dict(dense_d_ff=0), "1 leading dense layer"),
    (dict(layer_pattern="*****"), "not for a layer_pattern"),
])
def test_a_configuration_that_cannot_be_built_is_refused_by_its_numbers(
    change, message
):
    cfg = dataclasses.replace(_config(), **change)
    with pytest.raises(ValueError, match=message):
        TransformerLM(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, S), jnp.int32))


def test_the_accepted_families_keep_their_parameter_trees():
    """No kind, no gate, no leading dense layer: the names and shapes the
    three accepted families' checkpoints and drivers read."""
    tokens = jnp.zeros((1, 16), jnp.int32)
    tree = lambda cfg: jax.eval_shape(
        lambda: TransformerLM(cfg).init(jax.random.PRNGKey(0), tokens)
    )["params"]
    dense = tree(TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, head_dim=8, d_ff=64,
    ))
    assert set(dense["layer_0"]) == {"attn", "ln_attn", "ln_mlp", "mlp"}
    assert set(dense["layer_0"]["attn"]) == {"wq", "wk", "wv", "wo"}
    sparse = tree(TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=8, d_ff=16, num_experts=4, experts_held=(0, 2),
    ))
    assert set(sparse["layer_0"]) == {"attn", "ln_attn", "ln_mlp", "moe"}
    assert "wg" not in sparse["layer_0"]["attn"]
    assert sparse["layer_0"]["moe"]["w_gate"].value.shape == (2, 32, 16)


# -- what the benchmark counts -----------------------------------------------


def test_the_models_flops_count_a_window_layer_by_its_band():
    """`lib/flops_window` against a count by hand at the cell's sizes."""
    import json
    import pathlib

    cfg = json.loads((
        pathlib.Path(__file__).parents[1]
        / "benchmarks/configs/laguna-s-2.1-ep32.json"
    ).read_text())
    s, w = 8192, 512
    pairs = sum(min(i + 1, w) for i in range(s))
    assert flops_window.band_pairs(s, w) == pairs == 4_063_488
    assert flops_window.band_pairs(s, None) == s * (s + 1) // 2
    parts = flops_window.flops_by_part(cfg, s, 0.3125)
    # three sliding layers of 72 heads of 128: QK^T and PV, 2 FLOP a pair
    # and lane, forward and twice backward
    assert parts["window_attention"] == 3 * 3 * 2 * 2 * pairs * 72 * 128 / s
    assert parts["global_attention"] == 2 * 6 * s * 48 * 128
    by_triangle = 3 * 6 * s * 72 * 128
    assert 8.2 < by_triangle / parts["window_attention"] < 8.4
    assert parts["routed_experts"] == 4 * 6 * 3 * 3072 * 1024 * 0.3125
    total = flops_window.window_flops_per_token(cfg, s, 0.3125)
    assert total == sum(parts.values()) and 3.6e9 < total < 3.7e9
    # the parameters held: 811.0 M
    held = sum(
        int(np.prod(shape)) for shape, _ in ref.param_specs(cfg).values()
    )
    assert round(held / 1e6, 1) == 811.0
