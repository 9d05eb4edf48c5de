"""The ZAYA1-style decoder (CCA attention over grouped K/V heads, the
dropless expert layer over the experts held, the router's carried state)
against the plain reference `benchmarks/reference/zaya.py`, at a tiny size
on the CPU: 2 layers, 4 experts, 4 query over 2 K/V heads of 8."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks.drivers import train_moe
from benchmarks.reference import zaya
from kubeflow_tpu.models.transformer import Block, TransformerLM
from kubeflow_tpu.ops import flash as flash_kernels, moe
from kubeflow_tpu.ops.attention import dense_attention
from kubeflow_tpu.ops.flash import flash_attention
from kubeflow_tpu.parallel import MeshSpec, build_mesh
from kubeflow_tpu.train.trainer import softmax_cross_entropy

NUMBERS = {
    "hidden_size": 32, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "moe_intermediate_size": 32,
    "num_experts": 4, "experts_routed": 4, "experts_first": 0,
    "router_hidden_size": 16, "vocab_size": 64, "rope_theta": 5e6,
    "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-5,
    "cca_time0": 2, "cca_time1": 2,
}
B, S = 2, 32


def _config(numbers=NUMBERS, **how):
    how = {"dtype": jnp.float32, "attention_impl": "dense", "remat_policy": "none",
           **how}
    return train_moe.transformer_config(numbers, **how)


@pytest.fixture(scope="module")
def seeded():
    key = jax.random.PRNGKey(3)
    flat = zaya.init_params(key, NUMBERS)
    tokens = jax.random.randint(
        jax.random.PRNGKey(4), (B, S + 1), 0, NUMBERS["vocab_size"]
    )
    return key, flat, tokens[:, :-1], tokens[:, 1:]


def _program_loss(cfg, mesh=None):
    model = TransformerLM(cfg, mesh=mesh)
    return lambda params, tokens, labels: softmax_cross_entropy(
        model.apply({"params": params}, tokens), labels
    )


def test_logits_loss_and_every_gradient_leaf_match_the_reference(seeded):
    _, flat, tokens, labels = seeded
    params, stacked = train_moe.to_program_tree(flat), zaya.stack_layers(flat, NUMBERS)
    got = TransformerLM(_config()).apply({"params": params}, tokens)
    want = zaya.logits(stacked, tokens, NUMBERS)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    loss, grads = jax.value_and_grad(_program_loss(_config()))(params, tokens, labels)
    ref_loss, ref_grads = jax.value_and_grad(zaya.summed_loss)(
        stacked, tokens, labels, NUMBERS
    )
    n_tok = tokens.size
    np.testing.assert_allclose(loss, ref_loss / n_tok, rtol=1e-6)
    ref_flat = zaya.by_layer(ref_grads, NUMBERS)
    for name, got in train_moe.from_program_tree(grads, list(flat)).items():
        np.testing.assert_allclose(
            got, ref_flat[name] / n_tok, atol=2e-6, rtol=2e-4, err_msg=name
        )


@pytest.mark.parametrize("impl,remat", [("flash", "none"), ("flash", "full"),
                                        ("flash", "mlp"), ("dense", "flash")])
def test_the_kernel_paths_and_remat_policies_give_the_dense_logits(
    seeded, impl, remat
):
    _, flat, tokens, labels = seeded
    params = train_moe.to_program_tree(flat)
    want = jax.grad(_program_loss(_config()))(params, tokens, labels)
    cfg = _config(attention_impl=impl, remat_policy=remat)
    got = jax.grad(_program_loss(cfg))(params, tokens, labels)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-6, rtol=2e-4)


def test_three_adamw_steps_match_the_reference(seeded):
    key, _, _, _ = seeded
    opt = {"learning_rate": 1e-2, "warmup_steps": 2, "schedule_steps": 100,
           "weight_decay": 1e-2}
    batches = [
        dict(zip(("tokens", "labels"), (t[:, :-1], t[:, 1:])))
        for t in jax.random.randint(
            jax.random.PRNGKey(5), (3, B, S + 1), 0, NUMBERS["vocab_size"]
        )
    ]
    want = zaya.follow(key, NUMBERS, opt, batches, rows_per_block=1)
    whole = zaya.follow(key, NUMBERS, opt, batches)
    assert whole["loss"] == pytest.approx(want["loss"], rel=1e-6)
    assert whole["change_norm"] == pytest.approx(want["change_norm"], rel=1e-4)

    flat = zaya.init_params(key, NUMBERS)
    params = train_moe.to_program_tree(flat)
    tx = optax.adamw(
        lambda count: opt["learning_rate"] * count / opt["warmup_steps"],
        weight_decay=opt["weight_decay"],
    )
    state, losses = tx.init(params), []
    loss_fn = jax.jit(jax.value_and_grad(_program_loss(_config())))
    first = None
    for batch in batches:
        loss, grads = loss_fn(params, batch["tokens"], batch["labels"])
        first = grads if first is None else first
        updates, state = tx.update(grads, state, params)
        params = optax.apply_updates(params, updates)
        losses.append(float(loss))
    assert losses == pytest.approx(want["loss"], rel=1e-5)
    norm = lambda x: float(jnp.sqrt(jnp.sum(jnp.square(x))))
    now = train_moe.from_program_tree(params, list(flat))
    grad = train_moe.from_program_tree(first, list(flat))
    for name in flat:
        assert norm(grad[name]) == pytest.approx(
            want["first_grad_norm"][name], rel=2e-3, abs=1e-7
        ), name
        assert norm(now[name] - flat[name]) == pytest.approx(
            want["change_norm"][name], rel=2e-3
        ), name


def _one_layer(flat, i=0):
    """(the program's layer tree, the reference's layer dict) of layer i."""
    names = [n for n in flat if n.startswith(f"layers.{i}.")]
    tree = train_moe.to_program_tree({n: flat[n] for n in names})[f"layer_{i}"]
    return tree, {n.split(".", 2)[2]: flat[n] for n in names}


def test_the_shares_of_the_experts_add_up_to_the_whole_layer(seeded):
    """Experts 0-1 and 2-3 as two shares: the two partial expert outputs,
    with the attention and the residual counted once, are the uncut
    reference's layer."""
    _, flat, _, _ = seeded
    tree, ref = _one_layer(flat)
    x = jax.random.normal(jax.random.PRNGKey(6), (B, S, 32))
    state = jax.random.normal(jax.random.PRNGKey(7), (B, S, 16))
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    (want, want_state), _ = zaya._layer((x, state), ref, NUMBERS, None)

    def share(first, count, scale=1.0):
        held = dict(tree, moe={
            k: v[first:first + count] * scale if k.startswith("w_") else v
            for k, v in tree["moe"].items()
        })
        cfg = dataclasses.replace(_config(), experts_held=(first, count))
        return Block(cfg).apply({"params": held}, x, positions, state)

    (a, state_a), (b, state_b) = share(0, 2), share(2, 2)
    once, _ = share(0, 2, scale=0.0)  # the residual and the attention alone
    np.testing.assert_allclose(a + b - once, want, atol=2e-5, rtol=2e-5)
    assert float(jnp.abs(a - once).max()) > 1e-3 < float(jnp.abs(b - once).max())
    np.testing.assert_allclose(state_a, want_state, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(state_b, want_state, atol=1e-5, rtol=1e-5)


def test_all_tokens_on_one_expert_lose_none(seeded):
    """No capacity: with every token routed to expert 0 the layer is that
    expert applied to all of them, and the counters say so."""
    _, flat, tokens, _ = seeded
    flat = {
        k: jnp.zeros_like(v) if k.endswith("router_out") else v
        for k, v in flat.items()
    }
    got, counted = TransformerLM(_config()).apply(
        {"params": train_moe.to_program_tree(flat)}, tokens,
        mutable=["counters"],
    )
    want = zaya.logits(zaya.stack_layers(flat, NUMBERS), tokens, NUMBERS)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    layer = counted["counters"]["layer_1"]["moe"]
    assert layer["moe_tokens_held"] == layer["moe_load_max"] == B * S
    assert layer["moe_load_mean"] == B * S / 4


def test_expert_mlp_matches_a_dense_loop_at_any_imbalance():
    n, d, f, e = 96, 32, 64, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    x, gate = jax.random.normal(ks[0], (n, d)), jax.random.uniform(ks[1], (n,))
    w = (jax.random.normal(ks[2], (e, d, f)) / 6,
         jax.random.normal(ks[3], (e, d, f)) / 6,
         jax.random.normal(ks[4], (e, f, d)) / 8)
    target = jax.random.normal(ks[5], (n, d))

    def dense(x, gate, w_gate, w_up, w_down, expert, lo):
        out = jnp.zeros_like(x)
        for i in range(w_gate.shape[0]):
            y = (jax.nn.silu(x @ w_gate[i]) * (x @ w_up[i])) @ w_down[i]
            out += jnp.where((expert == lo + i)[:, None], y, 0)
        return out * gate[:, None]

    cases = {
        "uneven": (jax.random.randint(ks[6], (n,), 0, e), 0, e),
        "one expert": (jnp.full((n,), 2), 0, e),
        "an empty share": (jnp.full((n,), 0), 2, 2),
        "a share": (jax.random.randint(ks[6], (n,), 0, e), 2, 2),
    }
    for name, (expert, lo, held) in cases.items():
        mine = tuple(m[lo:lo + held] for m in w)
        ours = lambda *a: moe.expert_mlp(a[0], expert, a[1], a[2:], lo, block_rows=16)
        theirs = lambda *a: dense(*a, expert, lo)
        np.testing.assert_allclose(
            ours(x, gate, *mine), theirs(x, gate, *mine), atol=1e-5, err_msg=name
        )
        got = jax.grad(lambda *a: (ours(*a) * target).sum(), range(5))(x, gate, *mine)
        want = jax.grad(lambda *a: (theirs(*a) * target).sum(), range(5))(x, gate, *mine)
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, atol=3e-5, rtol=3e-5, err_msg=name)


def _choices(flat, tokens):
    _, seen = TransformerLM(_config()).apply(
        {"params": train_moe.to_program_tree(flat)}, tokens,
        mutable=["intermediates"],
    )
    return [seen["intermediates"][f"layer_{i}"]["moe"]["expert"] for i in (0, 1)]


def test_the_carried_state_changes_the_second_layers_routing(seeded):
    _, flat, tokens, _ = seeded
    cut = dict(flat)
    cut["layers.1.router_carry"] = jnp.zeros_like(flat["layers.1.router_carry"])
    with_carry, without = _choices(flat, tokens), _choices(cut, tokens)
    assert (with_carry[0] == without[0]).all()
    assert (with_carry[1] != without[1]).any()
    for f, got in ((flat, with_carry), (cut, without)):
        want = zaya.routing(zaya.stack_layers(f, NUMBERS), tokens, NUMBERS)
        np.testing.assert_array_equal(jnp.stack(got), want)


def test_a_forced_selection_is_even_whatever_the_weights_and_matches_the_reference(
    seeded,
):
    """`router_force_balance`: the experts are drawn by position and layer,
    not by the router, whose probability of the drawn expert stays the
    gate, and the reference draws the same."""
    _, flat, tokens, labels = seeded
    numbers = {**NUMBERS, "router_force_balance": True}
    other = zaya.init_params(jax.random.PRNGKey(8), NUMBERS)
    cfg = _config(numbers)
    assert cfg.router_force_balance
    _, seen = TransformerLM(cfg).apply(
        {"params": train_moe.to_program_tree(flat)}, tokens,
        mutable=["intermediates", "counters"],
    )
    choices = jnp.stack([
        seen["intermediates"][f"layer_{i}"]["moe"]["expert"] for i in (0, 1)
    ])
    for f in (flat, other):  # the same choices whatever the weights are
        np.testing.assert_array_equal(
            choices, zaya.routing(zaya.stack_layers(f, NUMBERS), tokens, numbers)
        )
    assert (choices[:, 0] == choices[:, 1]).all()  # every row alike
    assert (choices[0] != choices[1]).any()  # and every layer its own draw
    assert (choices != zaya.routing(
        zaya.stack_layers(flat, NUMBERS), tokens, NUMBERS
    )).any()
    assert set(np.unique(choices)) == set(range(NUMBERS["experts_routed"]))
    params, stacked = train_moe.to_program_tree(flat), zaya.stack_layers(flat, NUMBERS)
    loss, grads = jax.value_and_grad(_program_loss(cfg))(params, tokens, labels)
    ref_loss, ref_grads = jax.value_and_grad(zaya.summed_loss)(
        stacked, tokens, labels, numbers
    )
    n_tok = tokens.size
    np.testing.assert_allclose(loss, ref_loss / n_tok, rtol=1e-6)
    ref_flat = zaya.by_layer(ref_grads, NUMBERS)
    for name, got in train_moe.from_program_tree(grads, list(flat)).items():
        np.testing.assert_allclose(
            got, ref_flat[name] / n_tok, atol=2e-6, rtol=2e-4, err_msg=name
        )
    # The router still learns, through the gate.
    assert float(jnp.abs(ref_flat["layers.0.router_out"]).max()) > 0


@pytest.mark.parametrize("seq,bq,bk,two_pass", [
    (256, 128, 128, False),  # compact grid, fused backward
    (384, 128, 128, True),   # compact grid, two-pass backward
    (256, 128, 64, False),   # rectangular grid
    (200, 128, 128, False),  # padded inside the wrapper
])
def test_grouped_head_flash_matches_dense(monkeypatch, seq, bq, bk, two_pass):
    """4 query heads over 2 K/V heads through the kernels' index maps
    (interpreted), forward and backward, against dense attention over
    repeated K and V."""
    if two_pass:  # what the backward runs past the fused kernel's VMEM
        monkeypatch.setattr(flash_kernels, "_FUSED_VMEM_BUDGET", 0)
    ks = jax.random.split(jax.random.PRNGKey(seq), 4)
    q = jax.random.normal(ks[0], (2, seq, 4, 8))
    k = jax.random.normal(ks[1], (2, seq, 2, 8))
    v = jax.random.normal(ks[2], (2, seq, 2, 8))
    target = jax.random.normal(ks[3], q.shape)
    flash = lambda q, k, v: flash_attention(q, k, v, block_q=bq, block_k=bk)
    dense = lambda q, k, v: dense_attention(
        q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2), causal=True
    )
    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), atol=2e-5, rtol=2e-5)
    got = jax.grad(lambda *a: (flash(*a) * target).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (dense(*a) * target).sum(), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("spec", [{"ep": 2}, {"dp": 2, "ep": 2}, {"ep": 2, "tp": 2}])
def test_an_ep_mesh_gives_the_one_device_result(seeded, devices, spec):
    _, flat, tokens, labels = seeded
    params = train_moe.to_program_tree(flat)
    want = jax.value_and_grad(_program_loss(_config()))(params, tokens, labels)
    n = int(np.prod(list(spec.values())))
    mesh = build_mesh(MeshSpec(**spec), devices[:n])
    cfg = _config(attention_impl="flash")
    got = jax.jit(jax.value_and_grad(_program_loss(cfg, mesh)))(params, tokens, labels)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(got[1]), jax.tree_util.tree_leaves(want[1])):
        np.testing.assert_allclose(a, b, atol=2e-6, rtol=2e-4)
