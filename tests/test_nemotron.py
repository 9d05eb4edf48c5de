"""The Nemotron-H-style decoder (a stack of single sublayers: Mamba-2
mixers, latent top-k expert layers beside a shared expert, attention
without rotation; an untied head) against the plain reference
`benchmarks/reference/nemotron_h.py`, at a tiny size on the CPU: the
pattern `ME*E`, 4 state-space heads of 8 in 2 groups, 16 experts three a
token, 4 query heads over 1 K/V head."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks.drivers import train_hybrid
from benchmarks.reference import nemotron_h as ref
from kubeflow_tpu.models.transformer import (
    Sublayer, TransformerConfig, TransformerLM, forced_experts,
)
from kubeflow_tpu.ops import moe, ssd
from kubeflow_tpu.parallel import MeshSpec, build_mesh
from kubeflow_tpu.testing.hlo import jaxpr_kernel_names
from kubeflow_tpu.train.trainer import softmax_cross_entropy

NUMBERS = {
    "hidden_size": 32, "num_hidden_layers": 4, "hybrid_override_pattern": "ME*E",
    "num_attention_heads": 4, "num_key_value_heads": 1, "head_dim": 8,
    "mamba_num_heads": 4, "mamba_head_dim": 8, "ssm_state_size": 8,
    "n_groups": 2, "conv_kernel": 4, "chunk_size": 8,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "n_routed_experts": 16, "experts_routed": 16, "experts_first": 0,
    "num_experts_per_tok": 3, "routed_scaling_factor": 5, "norm_topk_prob": True,
    "moe_latent_size": 16, "moe_intermediate_size": 24,
    "moe_shared_expert_intermediate_size": 40, "vocab_size": 64,
    "layer_norm_epsilon": 1e-5,
}
B, S = 2, 29  # not a multiple of the chunk
KINDS = {"M": 0, "E": 1, "*": 2}


def _config(numbers=NUMBERS, **how):
    how = {"dtype": jnp.float32, "attention_impl": "dense", "remat_policy": "none",
           **how}
    return train_hybrid.transformer_config(numbers, **how)


@pytest.fixture(scope="module")
def seeded():
    key = jax.random.PRNGKey(3)
    flat = ref.init_params(key, NUMBERS)
    tokens = jax.random.randint(
        jax.random.PRNGKey(4), (B, S + 1), 0, NUMBERS["vocab_size"]
    )
    return key, flat, tokens[:, :-1], tokens[:, 1:]


def _program_loss(cfg, mesh=None):
    model = TransformerLM(cfg, mesh=mesh)
    return lambda params, tokens, labels: softmax_cross_entropy(
        model.apply({"params": params}, tokens), labels
    )


def _one_layer(flat, i):
    """(the program's layer tree, the reference's layer dict) of layer i."""
    names = [n for n in flat if n.startswith(f"layer.{i}.")]
    tree = train_hybrid.to_program_tree({n: flat[n] for n in names})[f"layer_{i}"]
    return tree, ref.layer_params(flat, i)


# -- program against reference ------------------------------------------------


@pytest.mark.parametrize("kind", list(KINDS))
def test_each_layer_kind_matches_the_reference(seeded, kind):
    _, flat, _, _ = seeded
    i = KINDS[kind]
    tree, theirs = _one_layer(flat, i)
    x = jax.random.normal(jax.random.PRNGKey(6), (B, S, 32))
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    layer = Sublayer(_config(), layer=i, kind=kind)

    def ours(x, tree):
        return layer.apply({"params": tree}, x, positions)[0]

    want = ref.sublayer(x, theirs, NUMBERS, i, kind)
    np.testing.assert_allclose(ours(x, tree), want, atol=2e-5, rtol=2e-5)
    target = jax.random.normal(jax.random.PRNGKey(7), x.shape)
    got = jax.grad(lambda x, t: (ours(x, t) * target).sum(), (0, 1))(x, tree)
    want = jax.grad(
        lambda x, p: (ref.sublayer(x, p, NUMBERS, i, kind) * target).sum(), (0, 1)
    )(x, theirs)
    np.testing.assert_allclose(got[0], want[0], atol=2e-5, rtol=2e-4)
    names = [n for n in flat if n.startswith(f"layer.{i}.")]
    mine = train_hybrid.from_program_tree({f"layer_{i}": got[1]}, names)
    for name in names:
        np.testing.assert_allclose(
            mine[name], want[1][name.split(".", 2)[2]], atol=2e-5, rtol=2e-4,
            err_msg=name,
        )


def test_logits_loss_and_every_gradient_leaf_match_the_reference(seeded):
    _, flat, tokens, labels = seeded
    params = train_hybrid.to_program_tree(flat)
    got = TransformerLM(_config()).apply({"params": params}, tokens)
    np.testing.assert_allclose(
        got, ref.logits(flat, tokens, NUMBERS), atol=5e-5, rtol=5e-5
    )
    loss, grads = jax.value_and_grad(_program_loss(_config()))(params, tokens, labels)
    ref_loss, ref_grads = jax.value_and_grad(ref.summed_loss)(
        flat, tokens, labels, NUMBERS
    )
    n_tok = tokens.size
    np.testing.assert_allclose(loss, ref_loss / n_tok, rtol=1e-6)
    for name, got in train_hybrid.from_program_tree(grads, list(flat)).items():
        np.testing.assert_allclose(
            got, ref_grads[name] / n_tok, atol=2e-6, rtol=5e-4, err_msg=name
        )
    # the correction gets no gradient; the untied head its own
    assert not np.any(grads["layer_1"]["moe"]["router_bias"])
    assert np.any(grads["lm_head"]) and np.any(grads["embedding"])


@pytest.mark.parametrize("remat", ["full", "mlp", "flash"])
def test_the_remat_policies_give_the_same_gradients(seeded, remat):
    _, flat, tokens, labels = seeded
    params = train_hybrid.to_program_tree(flat)
    want = jax.grad(_program_loss(_config()))(params, tokens, labels)
    got = jax.grad(_program_loss(_config(remat_policy=remat)))(params, tokens, labels)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-6, rtol=2e-4)


def test_three_adamw_steps_match_the_reference(seeded):
    key, flat, _, _ = seeded
    opt = {"learning_rate": 1e-2, "warmup_steps": 2, "schedule_steps": 100,
           "weight_decay": 1e-2}
    batches = [
        dict(zip(("tokens", "labels"), (t[:, :-1], t[:, 1:])))
        for t in jax.random.randint(
            jax.random.PRNGKey(5), (3, B, S + 1), 0, NUMBERS["vocab_size"]
        )
    ]
    want = ref.follow(key, NUMBERS, opt, batches, rows_per_block=1)
    params = train_hybrid.to_program_tree(flat)
    tx = optax.adamw(
        lambda count: opt["learning_rate"] * count / opt["warmup_steps"],
        weight_decay=opt["weight_decay"],
    )
    state, losses, first = tx.init(params), [], None
    loss_fn = jax.jit(jax.value_and_grad(_program_loss(_config())))
    for batch in batches:
        loss, grads = loss_fn(params, batch["tokens"], batch["labels"])
        first = grads if first is None else first
        updates, state = tx.update(grads, state, params)
        params = optax.apply_updates(params, updates)
        losses.append(float(loss))
    assert losses == pytest.approx(want["loss"], rel=1e-5)
    norm = lambda x: float(jnp.sqrt(jnp.sum(jnp.square(x))))
    now = train_hybrid.from_program_tree(params, list(flat))
    grad = train_hybrid.from_program_tree(first, list(flat))
    for name in flat:
        assert norm(grad[name]) == pytest.approx(
            want["first_grad_norm"][name], rel=2e-3, abs=1e-7
        ), name
        assert norm(now[name] - flat[name]) == pytest.approx(
            want["change_norm"][name], rel=2e-3, abs=1e-7
        ), name


@pytest.mark.parametrize("spec", [{"ep": 2}, {"dp": 2, "ep": 2}])
def test_an_ep_mesh_gives_the_one_device_result(seeded, devices, spec):
    """k rows a token through `expert_mlp_on_mesh`'s `psum` path: every
    `ep` shard adds its own experts' part."""
    _, flat, tokens, labels = seeded
    params = train_hybrid.to_program_tree(flat)
    want = jax.value_and_grad(_program_loss(_config()))(params, tokens, labels)
    n = int(np.prod(list(spec.values())))
    mesh = build_mesh(MeshSpec(**spec), devices[:n])
    got = jax.jit(jax.value_and_grad(_program_loss(_config(), mesh)))(
        params, tokens, labels
    )
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(got[1]), jax.tree_util.tree_leaves(want[1])):
        np.testing.assert_allclose(a, b, atol=2e-6, rtol=2e-4)


def test_the_old_stacks_keep_their_parameter_paths():
    """A configuration without a pattern builds the blocks it built: the
    same tree of names, a tied head, rope in the step."""
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, head_dim=8, d_ff=64,
    )
    tokens = jnp.zeros((1, 16), jnp.int32)
    params = jax.eval_shape(
        lambda: TransformerLM(cfg).init(jax.random.PRNGKey(0), tokens)
    )["params"]
    assert set(params) == {"embedding", "layer_0", "layer_1", "ln_final"}
    assert set(params["layer_0"]) == {"attn", "ln_attn", "ln_mlp", "mlp"}
    with pytest.raises(ValueError, match="names 3 layers"):
        TransformerLM(dataclasses.replace(cfg, layer_pattern="M*E")).init(
            jax.random.PRNGKey(0), tokens
        )
    with pytest.raises(ValueError, match="unknown layer kind"):
        TransformerLM(dataclasses.replace(cfg, layer_pattern="*x")).init(
            jax.random.PRNGKey(0), tokens
        )


# -- the scan ------------------------------------------------------------------


def _sequential(x, dt, a, b, c, groups):
    """The recurrence itself, a position at a time."""
    bsz, s, h, p = x.shape
    per = h // groups
    bh, ch = jnp.repeat(b, per, axis=2), jnp.repeat(c, per, axis=2)

    def step(state, inp):
        xt, dtt, bt, ct = inp
        state = (
            jnp.exp(dtt * a)[..., None, None] * state
            + dtt[..., None, None] * xt[..., None] * bt[..., None, :]
        )
        return state, jnp.einsum("bhpn,bhn->bhp", state, ct)

    _, y = jax.lax.scan(
        step, jnp.zeros((bsz, h, p, b.shape[-1])),
        tuple(jnp.moveaxis(u, 1, 0) for u in (x, dt, bh, ch)),
    )
    return jnp.moveaxis(y, 0, 1)


@pytest.mark.parametrize("seq_len", [32, 29])
@pytest.mark.parametrize("form", ["plain", "kernels"])
@pytest.mark.parametrize("against", ["sequential", "masked"])
def test_the_chunked_scan_matches_the_recurrence_and_the_masked_form(
    seq_len, form, against
):
    """Forward and every gradient, at S a multiple of the chunk and not:
    the plain chunked form (the CPU's) and the kernels under the
    interpreter, against the position-by-position recurrence and against
    the reference's masked form over the whole sequence."""
    bsz, h, g, p, n, chunk = 2, 4, 2, 16, 8, 8
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(k[0], (bsz, seq_len, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (bsz, seq_len, h))) * 0.5
    a = -jnp.exp(jax.random.normal(k[2], (h,)) * 0.5)
    b = jax.random.normal(k[3], (bsz, seq_len, g, n))
    c = jax.random.normal(k[4], (bsz, seq_len, g, n))
    w = jax.random.normal(k[5], x.shape)
    fold = lambda u: u.reshape(*u.shape[:2], -1)

    def ours(x, dt, a, b, c):
        y = ssd.ssd_scan(
            fold(x), dt, a, fold(b), fold(c), groups=g, chunk=chunk,
            interpret=True if form == "kernels" else None,
        )
        return jnp.sum(y.reshape(x.shape) * w)

    def theirs(x, dt, a, b, c):
        if against == "sequential":
            return jnp.sum(_sequential(x, dt, a, b, c, g) * w)
        return jnp.sum(ref.scan_masked(x, dt, a, b, c, block=16) * w)

    with jax.default_matmul_precision("highest"):
        args, every = (x, dt, a, b, c), (0, 1, 2, 3, 4)
        got, got_grads = jax.value_and_grad(ours, every)(*args)
        want, want_grads = jax.value_and_grad(theirs, every)(*args)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for name, mine, ref_grad in zip("x dt a b c".split(), got_grads, want_grads):
        np.testing.assert_allclose(
            mine, ref_grad, atol=1e-4, rtol=2e-4, err_msg=name
        )


def test_the_scans_kernels_on_a_mesh_give_the_one_device_result(devices):
    """Under `shard_map`: the batch over `dp`, whole groups over `tp`."""
    bsz, seq_len, h, g, p, n = 2, 24, 4, 2, 8, 8
    k = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(k[0], (bsz, seq_len, h * p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (bsz, seq_len, h)))
    a = -jnp.exp(jax.random.normal(k[2], (h,)))
    b = jax.random.normal(k[3], (bsz, seq_len, g * n))
    c = jax.random.normal(k[4], (bsz, seq_len, g * n))
    mesh = build_mesh(MeshSpec(dp=2, tp=2), devices[:4])
    scan = lambda mesh: lambda *args: jnp.sum(ssd.ssd_scan(
        *args, groups=g, chunk=8, mesh=mesh, interpret=True
    ) ** 2)
    every = (0, 1, 2, 3, 4)
    want = jax.value_and_grad(scan(None), every)(x, dt, a, b, c)
    got = jax.jit(jax.value_and_grad(scan(mesh), every))(x, dt, a, b, c)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for mine, theirs in zip(got[1], want[1]):
        np.testing.assert_allclose(mine, theirs, atol=1e-5, rtol=1e-4)
    with pytest.raises(ValueError, match="groups over tp"):
        ssd.ssd_scan(x, dt, a, b, c, groups=g, chunk=8, interpret=True,
                     mesh=build_mesh(MeshSpec(tp=4), devices[:4]))


def test_the_scan_saves_what_the_flash_policy_names():
    """Under `save_only_these_names` the scan's output and chunk states
    are saved and the backward holds no second `ssd_fwd`."""
    g, chunk = 2, 8
    x = jnp.ones((1, 16, 32)); dt = jnp.full((1, 16, 4), 0.1)
    a = -jnp.ones((4,)); b = jnp.ones((1, 16, 16))

    def loss(x):
        return jnp.sum(ssd.ssd_scan(
            jnp.tanh(x), dt, a, b, b, groups=g, chunk=chunk, interpret=True
        ) ** 2)

    policy = jax.checkpoint_policies.save_only_these_names(
        ssd.CHECKPOINT_OUT_NAME, ssd.CHECKPOINT_STATES_NAME
    )
    saved = jax.make_jaxpr(jax.grad(jax.checkpoint(loss, policy=policy)))(x)
    again = jax.make_jaxpr(jax.grad(jax.checkpoint(loss)))(x)
    assert sorted(jaxpr_kernel_names(saved.jaxpr)) == ["ssd_bwd", "ssd_fwd"]
    assert sorted(jaxpr_kernel_names(again.jaxpr)) == ["ssd_bwd", "ssd_fwd", "ssd_fwd"]


def test_the_scans_schedule_at_the_cells_shape():
    plan = ssd.ssd_schedule(
        8192, heads=64, head_dim=64, groups=4, state=128, chunk=128
    )
    assert plan["chunks"] == 64 and plan["grid"] == (1, 4, 64)
    assert plan["heads_a_block"] == 16 and plan["heads_a_lane_tile"] == 2
    # y, and the state entering each chunk, in bfloat16
    assert plan["saved_bytes_a_call"] == 2 * (8192 * 4096 + 64 * 128 * 4096)
    assert plan["state_scratch_bytes"] == 128 * 1024 * 4
    assert ssd.ssd_schedule(
        8000, heads=64, head_dim=64, groups=4, state=128, chunk=128
    )["padded_seq_len"] == 8064


# -- the expert layer ----------------------------------------------------------


@pytest.mark.parametrize("k", [1, 3])
@pytest.mark.parametrize("act", ["relu2", "swiglu"])
def test_top_k_dispatch_matches_a_dense_loop_over_experts(k, act):
    n, d, f, e = 96, 32, 64, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    x, gate = jax.random.normal(ks[0], (n, d)), jax.random.uniform(ks[1], (n, k))
    mats = 2 if act == "relu2" else 3
    w = tuple(
        jax.random.normal(ks[2 + i], (e, f, d) if i == mats - 1 else (e, d, f)) / 6
        for i in range(mats)
    )
    target = jax.random.normal(ks[5], (n, d))
    expert = jax.lax.top_k(jax.random.normal(ks[6], (n, e)), k)[1]

    def one(x, w_e):
        if act == "relu2":
            return jnp.square(jax.nn.relu(x @ w_e[0])) @ w_e[1]
        return (jax.nn.silu(x @ w_e[0]) * (x @ w_e[1])) @ w_e[2]

    def dense(x, gate, *w, lo):
        out = jnp.zeros_like(x)
        for i in range(w[0].shape[0]):
            mine = jnp.sum(jnp.where(expert == lo + i, gate, 0.0), axis=-1)
            out += mine[:, None] * one(x, [m[i] for m in w])
        return out

    for lo, held in ((0, e), (2, 3)):
        mine = tuple(m[lo:lo + held] for m in w)
        ours = lambda x, gate, *w: moe.expert_mlp(
            x, expert, gate, w, lo, block_rows=16
        )
        np.testing.assert_allclose(
            ours(x, gate, *mine), dense(x, gate, *mine, lo=lo), atol=2e-5
        )
        every = tuple(range(2 + mats))
        got = jax.grad(lambda *a: (ours(*a) * target).sum(), every)(x, gate, *mine)
        want = jax.grad(
            lambda *a: (dense(*a, lo=lo) * target).sum(), every
        )(x, gate, *mine)
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=2e-4)


def test_the_plan_gives_a_token_k_rows_and_one_at_k_1():
    expert = jnp.array([[0, 3], [3, 1], [2, 0], [5, 4]], jnp.int32)
    plan = moe.plan_dispatch(expert, 0, 4, block_rows=2)
    row_token = np.asarray(plan["row_token"])
    rows = row_token.shape[0]
    assert rows == 2 * moe.row_tiles(4, 2, 4, 2) and int(plan["n_tiles"][0]) == 4
    # rows in the order of the experts, a run of whole tiles each, an
    # expert's in the order of its tokens; N marks a row of padding
    assert row_token[:8].tolist() == [0, 2, 1, 4, 2, 4, 0, 1]
    assert (row_token[8:] == 4).all()
    assert np.asarray(plan["tile_expert"])[:4].tolist() == [0, 1, 2, 3]
    # a token's rows side by side, in the order of its experts
    token_rows, count = np.asarray(plan["token_rows"]), np.asarray(plan["token_count"])
    assert count.tolist() == [2, 2, 2, 0]
    assert token_rows.tolist() == [[0, 2, 1, rows], [6, 7, 4, rows]]
    gate = jnp.arange(8, dtype=jnp.float32).reshape(4, 2) + 1
    np.testing.assert_array_equal(
        moe.slot_weights(gate, plan), [[1, 4, 6, 0], [2, 3, 5, 0]]
    )
    one = moe.plan_dispatch(expert[:, 0], 0, 4, block_rows=2)
    flat = moe.plan_dispatch(expert[:, :1], 0, 4, block_rows=2)
    for ours, theirs in (("dst", "token_rows"), ("src", "row_token"),
                         ("tile_expert",) * 2, ("n_tiles",) * 2):
        np.testing.assert_array_equal(
            one[ours], np.asarray(flat[theirs]).reshape(one[ours].shape)
        )
    dst, src = np.asarray(one["dst"]), np.asarray(one["src"])
    held = np.asarray(expert[:, 0]) < 4
    assert (dst[~held] == src.shape[0]).all()
    assert (src[dst[held]] == np.flatnonzero(held)).all()


def test_forced_experts_at_k_1_is_the_argmax_it_was():
    key = jax.random.fold_in(jax.random.PRNGKey(42), 5)
    scores = jax.random.normal(key, (64, 16), jnp.float32)
    np.testing.assert_array_equal(
        forced_experts(5, 64, 16), jnp.argmax(scores, axis=-1)
    )
    three = forced_experts(5, 64, 16, 3)
    assert three.shape == (64, 3)
    np.testing.assert_array_equal(three[:, 0], forced_experts(5, 64, 16))
    np.testing.assert_array_equal(three, ref.forced_experts(5, 64, 16, 3))
    assert all(len(set(row)) == 3 for row in np.asarray(three))


def test_the_counters_count_rows(seeded):
    _, flat, tokens, _ = seeded
    cfg = dataclasses.replace(_config(), experts_held=(4, 8))
    params = train_hybrid.to_program_tree(flat)
    for name in ("layer_1", "layer_3"):
        params[name]["moe"] = {
            k: v[4:12] if k.startswith("w_") else v
            for k, v in params[name]["moe"].items()
        }
    _, out = TransformerLM(cfg).apply(
        {"params": params}, tokens, mutable=["counters", "intermediates"]
    )
    for name in ("layer_1", "layer_3"):
        counted = out["counters"][name]["moe"]
        expert = np.asarray(out["intermediates"][name]["moe"]["expert"])
        assert expert.shape == (B, S, 3)
        held = ((expert >= 4) & (expert < 12)).sum()
        assert counted["moe_tokens_held"] == held > B * S  # rows, not tokens
        assert counted["moe_load_mean"] == held / 8


# -- the shares of a deployment ------------------------------------------------


def test_the_expert_shares_add_up_to_the_whole_layer(seeded):
    """Four shares of four experts: the routed parts, with the shared
    expert, the latent's projections and the residual counted once, are
    the uncut reference's layer."""
    _, flat, _, _ = seeded
    tree, theirs = _one_layer(flat, 1)
    x = jax.random.normal(jax.random.PRNGKey(6), (B, S, 32))
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    want = ref.sublayer(x, theirs, NUMBERS, 1, "E")

    def share(first, count, scale=1.0):
        held = dict(tree, moe={
            k: v[first:first + count] * scale if k.startswith("w_") else v
            for k, v in tree["moe"].items()
        })
        cfg = dataclasses.replace(_config(), experts_held=(first, count))
        return Sublayer(cfg, layer=1, kind="E").apply(
            {"params": held}, x, positions
        )[0]

    once = share(0, 4, scale=0.0)  # the residual and the shared expert alone
    parts = [share(first, 4) - once for first in (0, 4, 8, 12)]
    # The out-projection is linear, so the shares' latent sums add under it.
    np.testing.assert_allclose(sum(parts) + once, want, atol=5e-5, rtol=5e-5)
    assert all(float(jnp.abs(p).max()) > 1e-3 for p in parts)
    # ... and the reference given a share leaves the same part out.
    cut = dict(NUMBERS, n_routed_experts=4, experts_first=8)
    theirs_cut = dict(theirs, w_in=theirs["w_in"][8:12], w_down=theirs["w_down"][8:12])
    np.testing.assert_allclose(
        share(8, 4), ref.sublayer(x, theirs_cut, cut, 1, "E"), atol=5e-5, rtol=5e-5
    )


def _columns(total: int, parts: int, part: int):
    size = total // parts
    return slice(part * size, (part + 1) * size)


def test_the_head_shares_of_the_mixer_add_up_to_the_whole_mixer(seeded):
    """`tp` 2 by whole groups: each share holds half the heads and their
    groups (its columns of the in-projection, the convolution, the gated
    norm, its rows of the out-projection); the two out-projections'
    partial sums, the residual counted once, are the uncut mixer."""
    _, flat, _, _ = seeded
    tree, theirs = _one_layer(flat, 0)
    x = jax.random.normal(jax.random.PRNGKey(6), (B, S, 32))
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    want = ref.sublayer(x, theirs, NUMBERS, 0, "M")
    h, p, g, n = 4, 8, 2, 8
    d_in, gn = h * p, g * n

    def share(part):
        z = np.arange(0, d_in)[_columns(d_in, 2, part)]
        xs = d_in + z
        bs = 2 * d_in + np.arange(gn)[_columns(gn, 2, part)]
        cs = bs + gn
        dts = 2 * d_in + 2 * gn + np.arange(h)[_columns(h, 2, part)]
        cols = np.concatenate([z, xs, bs, cs, dts])
        conv = np.concatenate([xs, bs, cs]) - d_in
        heads = _columns(h, 2, part)
        s = tree["ssm"]
        held = dict(tree, ssm={
            "in_proj": {"kernel": s["in_proj"]["kernel"][:, cols]},
            "conv_kernel": s["conv_kernel"][:, conv],
            "conv_bias": s["conv_bias"][conv],
            "dt_bias": s["dt_bias"][heads], "A_log": s["A_log"][heads],
            "D": s["D"][heads], "norm_scale": s["norm_scale"][z],
            "out_proj": {"kernel": s["out_proj"]["kernel"][z]},
        })
        cfg = dataclasses.replace(_config(), ssm_heads=h // 2, ssm_groups=g // 2)
        return Sublayer(cfg, layer=0, kind="M").apply(
            {"params": held}, x, positions
        )[0] - x

    np.testing.assert_allclose(
        x + share(0) + share(1), want, atol=2e-5, rtol=2e-5
    )
    assert float(jnp.abs(share(0)).max()) > 1e-3 < float(jnp.abs(share(1)).max())


def test_the_head_shares_of_attention_add_up_to_the_whole_layer(seeded):
    """`tp` 2 over 4 query heads and, here, 2 K/V heads: a share holds one
    K/V head and the two query heads that read it."""
    _, flat, _, _ = seeded
    numbers = dict(NUMBERS, num_key_value_heads=2)
    flat = ref.init_params(jax.random.PRNGKey(3), numbers)
    tree, theirs = _one_layer(flat, 2)
    x = jax.random.normal(jax.random.PRNGKey(6), (B, S, 32))
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    want = ref.sublayer(x, theirs, numbers, 2, "*")

    def share(part):
        q, kv = _columns(4, 2, part), _columns(2, 2, part)
        a = tree["attn"]
        held = dict(tree, attn={
            "wq": {"kernel": a["wq"]["kernel"][:, q]},
            "wk": {"kernel": a["wk"]["kernel"][:, kv]},
            "wv": {"kernel": a["wv"]["kernel"][:, kv]},
            "wo": {"kernel": a["wo"]["kernel"][q]},
        })
        cfg = dataclasses.replace(_config(numbers), n_heads=2, n_kv_heads=1)
        return Sublayer(cfg, layer=2, kind="*").apply(
            {"params": held}, x, positions
        )[0] - x

    np.testing.assert_allclose(
        x + share(0) + share(1), want, atol=2e-5, rtol=2e-5
    )


# -- the convolution, its bias and `silu` as kernels ------------------------------


def _wide_mixers(policy, kernels: bool, dtype=jnp.bfloat16):
    """Loss and gradient of two state-space mixers whose xBC is four lane
    tiles wide (8 heads of 32 in 2 groups of one lane tile, a state of 64)
    over sequences of one row block, the convolution and the gated norm as
    XLA's passes or as `shortconv_*` and `gatenorm_*` (which the CPU is
    told compile, and interprets)."""
    from kubeflow_tpu.ops import gatenorm, shortconv

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, layer_pattern="MM", n_heads=4,
        head_dim=8, d_ff=16, ssm_heads=8, ssm_head_dim=32, ssm_state=64,
        ssm_groups=2, ssm_chunk=16, tie_embeddings=False, dtype=dtype,
        attention_impl="dense", remat_policy=policy,
    )
    model = TransformerLM(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 129), 0, 64)
    params = model.init(jax.random.PRNGKey(7), tokens[:, :-1])["params"]
    loss = _program_loss(cfg)
    patch = pytest.MonkeyPatch()
    try:
        for ops in (shortconv, gatenorm) if kernels else ():
            patch.setattr(ops, "kernels_apply", functools.partial(
                ops.kernels_apply, compiled=True
            ))
        args = (params, tokens[:, :-1], tokens[:, 1:])
        names = jaxpr_kernel_names(jax.make_jaxpr(jax.grad(loss))(*args).jaxpr)
        return names, jax.jit(jax.value_and_grad(loss))(*args)
    finally:
        patch.undo()


@functools.cache
def _wide_plain(policy):
    return _wide_mixers(policy, kernels=False)


@functools.cache
def _wide_kernels(policy):
    return _wide_mixers(policy, kernels=True)


@pytest.mark.parametrize("policy", ["none", "full", "mlp", "flash"])
def test_the_convolution_as_kernels_gives_the_plain_paths_loss_and_gradient(
    policy,
):
    """Under every remat policy: one `shortconv_fwd` and one
    `shortconv_bwd` a mixer, the forward's run again where a checkpoint
    keeps nothing of it ("full", and "flash" with no limit known: the
    CPU's plan admits no name); the loss and every gradient leaf, the
    taps' and the bias's among them, are the plain expression's under the
    same policy (XLA's own rounding moves with what it fuses: 4 % at one
    leaf between "none" and "full")."""
    plain_names, want = _wide_plain(policy)
    assert not [n for n in plain_names if n.startswith("shortconv")]
    names, got = _wide_kernels(policy)
    again = 2 if policy in ("full", "flash") else 0
    assert names.count("shortconv_fwd") == 2 + again
    assert names.count("shortconv_bwd") == 2
    np.testing.assert_allclose(got[0], want[0], rtol=2e-3)
    for a, b in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(want[1])):
        gap = np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-6)
        assert gap < 1e-2, gap


@pytest.mark.parametrize("policy", ["none", "full", "mlp", "flash"])
def test_the_gated_norm_is_one_kernel_each_way_a_mixer(policy):
    """The same programs (held to the plain path's loss and gradients
    above, `D`'s and the norm's scale's among them): one `gatenorm_fwd`
    and one `gatenorm_bwd` a mixer, the forward's run again only where a
    checkpoint keeps nothing of it."""
    assert not [n for n in _wide_plain(policy)[0] if n.startswith("gatenorm")]
    names, _ = _wide_kernels(policy)
    again = 2 if policy in ("full", "flash") else 0
    assert names.count("gatenorm_fwd") == 2 + again
    assert names.count("gatenorm_bwd") == 2


def test_float32_mixers_take_the_plain_convolution_whatever_the_backend():
    told, _ = _wide_mixers("none", kernels=True, dtype=jnp.float32)
    plain, _ = _wide_mixers("none", kernels=False, dtype=jnp.float32)
    assert told == plain
    assert not {"shortconv_fwd", "gatenorm_fwd"} & set(told)
