"""The Xing4.0-style decoder (latent attention with two-part scores and one
rope key for all heads, four residual streams mixed by Sinkhorn-normalised
maps round every sublayer, a leading dense layer, then gated top-k experts
beside a shared one; an untied head) against the plain reference
`benchmarks/reference/xing.py`, at a tiny size on the CPU that keeps every
ratio: 2 heads of 16 + 8 over v of 16, ranks 12 / 8, n = 4, 8 experts two a
token, 1 dense + 2 expert layers."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks.drivers import train_mla
from benchmarks.reference import xing as ref
from kubeflow_tpu.models.transformer import (
    Block, StreamMaps, TransformerConfig, TransformerLM,
)
from kubeflow_tpu.ops import streams as streams_ops
from kubeflow_tpu.testing.hlo import jaxpr_kernel_names
from kubeflow_tpu.train.trainer import softmax_cross_entropy

NUMBERS = train_mla.model_numbers({
    "hidden_size": 32, "intermediate_size": 48, "num_hidden_layers": 3,
    "num_attention_heads": 2, "num_key_value_heads": 2, "q_lora_rank": 12,
    "kv_lora_rank": 8, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "vocab_size": 64, "rms_norm_eps": 1e-6,
    "rope_theta": 10000,
    "rope_scaling": {
        "beta_fast": 4, "beta_slow": 1, "factor": 8, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 16,
        "type": "yarn",
    },
    "first_k_dense_replace": 1, "n_routed_experts": 8, "n_shared_experts": 1,
    "num_experts_per_tok": 2, "moe_intermediate_size": 16,
    "routed_scaling_factor": 2, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "tie_word_embeddings": False, "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "num_nextn_predict_layers": 0,
    "experts_routed": 8, "experts_first": 0,
})
B, S = 2, 32
# One dense and one expert layer: what the tests that compile a step more
# than once run (the interpreted kernels' programs are long).
SMALL = dict(NUMBERS, num_hidden_layers=2)
# ... and, where a test is not about the maps' convergence, 3 iterations:
# the 20 unrolled are half of what a step takes to compile here.
FEW = dict(SMALL, hc_sinkhorn_iters=3)


def _config(numbers=NUMBERS, **how):
    how = {"dtype": jnp.float32, "attention_impl": "dense", "remat_policy": "none",
           **how}
    return train_mla.transformer_config(numbers, **how)


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


def _program_loss(cfg):
    model = TransformerLM(cfg)
    return lambda params, tokens, labels: softmax_cross_entropy(
        model.apply({"params": params}, tokens), labels
    )


# -- program against reference ------------------------------------------------


@pytest.mark.parametrize(
    "base, forced", [(NUMBERS, False), (FEW, True)], ids=["routed", "forced"]
)
def test_logits_loss_and_every_gradient_leaf_match_the_reference(
    seeded, base, forced
):
    key, _, tokens, labels = seeded
    numbers, flat = _held(dict(base, router_force_balance=forced), key, 0, 4)
    params = train_mla.to_program_tree(flat)
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
    for name, got in train_mla.from_program_tree(grads, list(flat)).items():
        np.testing.assert_allclose(
            got, ref_grads[name] / n_tok, atol=2e-6, rtol=1e-3, err_msg=name
        )
    # the correction gets no gradient; the maps, the latents' norms and the
    # rope key's projection their own
    assert not np.any(grads["layer_1"]["moe"]["router_bias"])
    last = f"layer_{numbers['num_hidden_layers'] - 1}"
    for leaf in ("phi", "b", "a"):
        assert np.any(grads["layer_0"]["hc_attn"][leaf]), leaf
        assert np.any(grads[last]["hc_mlp"][leaf]), leaf
    assert np.any(grads["layer_0"]["attn"]["kv_norm"]["scale"])
    assert np.any(grads["layer_0"]["attn"]["wkv_a"]["kernel"][:, -8:])
    assert set(grads["layer_0"]) == {
        "attn", "hc_attn", "hc_mlp", "ln_attn", "ln_mlp", "mlp"}
    assert set(grads["layer_1"]) == {
        "attn", "hc_attn", "hc_mlp", "ln_attn", "ln_mlp", "moe"}


def test_three_adamw_steps_match_the_reference(seeded):
    key = seeded[0]
    numbers, flat = _held(FEW, key, 0, 4)
    opt = {"learning_rate": 1e-2, "warmup_steps": 2, "schedule_steps": 100,
           "weight_decay": 1e-2}
    batches = [
        dict(zip(("tokens", "labels"), (t[:, :-1], t[:, 1:])))
        for t in jax.random.randint(
            jax.random.PRNGKey(5), (3, B, S + 1), 0, NUMBERS["vocab_size"]
        )
    ]
    want = ref.follow(key, numbers, opt, batches, rows_per_block=1)
    params = train_mla.to_program_tree(flat)
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
    now = train_mla.from_program_tree(params, list(flat))
    grad = train_mla.from_program_tree(first, list(flat))
    for name in flat:
        assert norm(grad[name]) == pytest.approx(
            want["first_grad_norm"][name], rel=2e-3, abs=1e-7), name
        assert norm(now[name] - flat[name]) == pytest.approx(
            want["change_norm"][name], rel=2e-3, abs=1e-7), name


def test_the_two_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(
    seeded
):
    """Experts 0-3 on one chip and 4-7 on another: what each adds, with the
    shared expert (which every chip computes alike) counted once, is the
    uncut reference's layer."""
    key, flat, _, _ = seeded
    p = ref.layer_params(flat, 1)
    h = jax.random.normal(jax.random.PRNGKey(9), (B, S, NUMBERS["hidden_size"]))
    whole = ref.expert_layer(h, p, NUMBERS, 1)
    shared = ref._swiglu(h, p["shared_gate"], p["shared_up"], p["shared_down"], None)
    parts = []
    for first in (0, 4):
        cfg = dataclasses.replace(_config(), experts_held=(first, 4))
        share = {k: v for k, v in train_mla.to_program_tree(
            {f"layer.1.{k}": v for k, v in p.items()}
        )["layer_1"]["moe"].items()}
        for leaf in ("w_gate", "w_up", "w_down"):
            share[leaf] = share[leaf][first:first + 4]
        from kubeflow_tpu.models.transformer import ExpertLayer

        out, _ = ExpertLayer(cfg, layer=1).apply({"params": share}, h, None)
        parts.append(out - shared)
    np.testing.assert_allclose(
        parts[0] + parts[1] + shared, whole, atol=2e-5, rtol=2e-5
    )


# -- the streams ---------------------------------------------------------------


def _maps(cfg, streams, seed=0, **leaves):
    module = StreamMaps(cfg)
    params = jax.tree_util.tree_map(
        lambda x: x.value if hasattr(x, "value") else x,
        module.init(jax.random.PRNGKey(seed), streams)["params"],
        is_leaf=lambda x: hasattr(x, "value"),
    )
    params.update(leaves)
    return module.apply({"params": params}, streams, mutable=["counters"])


@pytest.mark.parametrize(
    "a_res, rows_to", [(0.25, 1e-4), (1.0, 1e-2), (40.0, None)],
    ids=["mild", "seeded", "clamped"],
)
def test_the_stream_map_is_doubly_stochastic_and_the_clamp_holds(a_res, rows_to):
    """Columns sum to one after every iteration (theirs is the last
    normalisation); rows to 1e-4 after 20 where the products are mild, to
    1e-2 at the seed's spread (the iteration converges geometrically, at a
    rate the entries' ratio sets: `hc_sinkhorn_err` reads it in a run);
    with `a_res` times 40 the products pass the clamp, and without it exp
    would overflow and the rows be nan."""
    cfg = _config()
    n = cfg.residual_streams
    streams = jax.random.normal(jax.random.PRNGKey(1), (B, S, n * cfg.d_model))
    (h, same, ho, hr), mutated = _maps(
        cfg, streams, a=jnp.array([1.0, 1.0, a_res], jnp.float32)
    )
    assert same is streams and h.shape == (B, S, cfg.d_model)
    # h = sum_i Hp[i] X[i] over d > n lanes: Hp is what solves it a token
    one = np.asarray(streams).reshape(B, S, n, cfg.d_model)
    hp = np.stack([
        [np.linalg.lstsq(one[b, s].T, np.asarray(h)[b, s], rcond=None)[0]
         for s in range(S)] for b in range(B)
    ]).transpose(0, 2, 1)
    assert hp.shape == (B, n, S) and ho.shape == hp.shape
    assert hr.shape == (B, n, n, S)
    assert np.all(np.isfinite(hr)) and np.all(hr >= 0)
    np.testing.assert_allclose(hr.sum(axis=1), 1.0, atol=1e-4)
    if rows_to is not None:
        np.testing.assert_allclose(hr.sum(axis=2), 1.0, atol=rows_to)
    assert np.all((hp > 0) & (hp < 1)) and np.all((ho > 0) & (ho < 2))
    counters = mutated["counters"]
    assert float(counters["hc_kernel_sublayers"]) == 0  # XLA's code here
    err = float(counters["hc_sinkhorn_err"]) * 2 * cfg.n_layers
    sums = np.concatenate([hr.sum(axis=2), hr.sum(axis=1)], axis=1)
    assert err == pytest.approx(np.abs(sums - 1).max(), rel=1e-4)
    diag = float(counters["hc_res_diag_mean"]) * 2 * cfg.n_layers
    assert diag == pytest.approx(
        np.mean(np.trace(np.asarray(hr), axis1=1, axis2=2)) / n, rel=1e-5
    )
    if a_res == 1.0:  # the seed leans to the identity and is not it
        assert 0.3 < diag < 0.8


@pytest.mark.parametrize("dense", [True, False], ids=["dense", "experts"])
def test_identity_maps_on_equal_streams_are_todays_block_a_stream(dense):
    """Hp summing to one, Ho = 1, Hr = I: h = x and every stream leaves as
    x + F(norm(x))."""
    cfg = _config()
    plain = dataclasses.replace(cfg, residual_streams=0)
    n, d = cfg.residual_streams, cfg.d_model
    x = jax.random.normal(jax.random.PRNGKey(2), (B, S, d))
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    unbox = lambda tree: jax.tree_util.tree_map(
        lambda v: v.value if hasattr(v, "value") else v, tree,
        is_leaf=lambda v: hasattr(v, "value"),
    )
    block = Block(plain, dense=dense, layer=1)
    params = unbox(block.init(jax.random.PRNGKey(7), x, positions)["params"])
    want, _ = block.apply({"params": params}, x, positions)
    # sigmoid(b_pre) = 1/n a stream, 2 sigmoid(0) = 1, and a diagonal of
    # +30 under zeros off it: e^30 against 1 is the identity to 1e-13
    maps = {
        "phi": jnp.zeros((n * d, n * n + 2 * n)),
        "b": jnp.concatenate([
            jnp.full((n,), -np.log(n - 1.0)), jnp.zeros(n),
            30.0 * jnp.eye(n).reshape(-1),
        ]).astype(jnp.float32),
        "a": jnp.ones(3),
    }
    streams = Block(cfg, dense=dense, layer=1)
    got, _ = streams.apply(
        {"params": {**params, "hc_attn": maps, "hc_mlp": maps}},
        jnp.tile(x, n), positions,
    )
    for i in range(n):
        np.testing.assert_allclose(
            got[..., i * d:(i + 1) * d], want, atol=2e-5, rtol=2e-5
        )


# -- one loss and one gradient under every policy ---------------------------------


@pytest.fixture(scope="module", params=["xla", "kernels"])
def small(seeded, request):
    """The two-layer cut's seeded leaves, a batch, and its loss and
    gradient under dense attention with nothing formed again: in float32,
    where the streams' mixes are XLA's code, and in bfloat16 at a hidden
    size of 128 and a sequence of 128 with `ops/streams.kernels_apply`
    told the kernels compile (the CPU interprets them), where they are
    the row-block kernels and their two rules."""
    key, _, tokens, labels = seeded
    kernels = request.param == "kernels"
    numbers = dict(FEW, hidden_size=128) if kernels else FEW
    if kernels:  # a sequence of whole 128-row blocks
        tokens = jax.random.randint(
            jax.random.PRNGKey(5), (B, 128 + 1), 0, NUMBERS["vocab_size"]
        )
        tokens, labels = tokens[:, :-1], tokens[:, 1:]
    numbers, flat = _held(numbers, key, 0, 4)
    params = train_mla.to_program_tree(flat)
    how = dict(dtype=jnp.bfloat16) if kernels else {}

    def step(**more):
        patch = pytest.MonkeyPatch()
        if kernels:
            patch.setattr(streams_ops, "kernels_apply", functools.partial(
                streams_ops.kernels_apply, compiled=True
            ))
        try:
            loss = _program_loss(_config(numbers, **how, **more))
            names = jaxpr_kernel_names(
                jax.make_jaxpr(jax.grad(loss))(params, tokens, labels).jaxpr
            )
            assert kernels == any(n.startswith("hc_") for n in names), names
            return jax.jit(jax.value_and_grad(loss))(params, tokens, labels)
        finally:
            patch.undo()

    return step, step(), kernels


@pytest.mark.parametrize("policy", ["full", "mlp", "flash"])
def test_every_remat_policy_gives_one_loss_and_one_gradient(small, policy):
    """The kernels' two rules share one write of dX (`mix_out`'s hands dX'
    on, `mix_in`'s applies Hr): sound under `jax.checkpoint` whatever is
    formed again, since a checkpoint runs the same two rules on the same
    values."""
    step, want, kernels = small
    # `flash` differs from `full` only where the kernels name their results
    impl = "flash" if policy == "flash" else "dense"
    if kernels and impl == "flash":  # bfloat16: against the same kernels
        want = step(attention_impl=impl)
    got = step(remat_policy=policy, attention_impl=impl)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(want[1])):
        np.testing.assert_allclose(a, b, atol=2e-6, rtol=2e-3)


def test_the_kernels_are_the_two_part_calls(seeded):
    key, _, tokens, labels = seeded
    numbers, flat = _held(SMALL, key, 0, 4)
    flash = _program_loss(_config(numbers, attention_impl="flash"))
    names = jaxpr_kernel_names(jax.make_jaxpr(jax.grad(flash))(
        train_mla.to_program_tree(flat), tokens, labels
    ).jaxpr)
    assert {"flash_fwd_mla", "flash_bwd_mla_fused", "flash_delta"} <= set(names)
    assert not [n for n in names if n.startswith("flash_") and "mla" not in n
                and n != "flash_delta"]


# -- what cannot be built is refused with its numbers ---------------------------


@pytest.mark.parametrize("change, message", [
    (dict(q_latent=-1), "latent attention with ranks -1 / 8"),
    (dict(rope_head_dim=7), "a rope part of 7"),
    (dict(n_kv_heads=1), "equal heads"),
    (dict(cca=True), "no CCA"),
    (dict(hc_iters=0), "mixed by 0 iterations"),
    (dict(residual_streams=-1), "-1 residual streams"),
])
def test_a_configuration_that_cannot_be_built_is_refused(change, message):
    cfg = dataclasses.replace(_config(), **change)
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match=message):
        TransformerLM(cfg).init(jax.random.PRNGKey(0), tokens)


# -- the counters reach fit()'s records ---------------------------------------------


def test_the_streams_counters_reach_fits_records():
    from kubeflow_tpu.parallel import MeshSpec, build_mesh
    from kubeflow_tpu.train import TrainConfig, Trainer, fit

    cfg = _config(dict(
        FEW, router_force_balance=True, n_routed_experts=4, num_experts=4
    ))
    mesh = build_mesh(MeshSpec(), jax.devices()[:1])
    trainer = Trainer(
        TransformerLM(cfg, mesh=mesh),
        TrainConfig(batch_size=B, optimizer="adamw", train_metrics="loss",
                    fsdp_params=False, label_smoothing=0.0),
        mesh, example_input_shape=(B, S), example_input_dtype=jnp.int32,
        input_key="tokens", label_key="labels",
    )
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 0, 64)

    def batches():
        while True:
            yield {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}

    result = fit(trainer, batches(), 2, log_every=1, handle_signals=False)
    for record in result.history:
        assert 0 < record["hc_sinkhorn_err"] < 0.5  # three iterations
        assert 0.3 < record["hc_res_diag_mean"] < 0.8
        assert record["hc_kernel_sublayers"] == 0  # the CPU: XLA's code
        assert record["moe_tokens_held"] > 0


def test_the_maps_product_in_one_pass_is_the_full_precision_product():
    """bfloat16 streams times float32 `phi`: `_exact_product` against the
    float32 product at `highest`, forward and both gradients (x's lands in
    bfloat16, so it is held to bfloat16's rounding)."""
    from kubeflow_tpu.ops.streams import exact_product as _exact_product

    b, s, k, c = 2, 64, 512, 24
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(keys[0], (b, s, k)).astype(jnp.bfloat16)
    phi = jax.random.normal(keys[1], (k, c)) * k ** -0.5
    w = jax.random.normal(keys[2], (b, c, s))
    plain = lambda x, phi: jnp.einsum(
        "kc,bsk->bcs", phi, x.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    np.testing.assert_allclose(
        _exact_product(x, phi), plain(x, phi), atol=2e-6, rtol=2e-6
    )
    weigh = lambda f: lambda x, phi: jnp.sum(f(x, phi) * w)
    (dx, dphi), (dx_, dphi_) = (
        jax.grad(weigh(f), argnums=(0, 1))(x, phi) for f in (_exact_product, plain)
    )
    np.testing.assert_allclose(dphi, dphi_, atol=2e-5, rtol=2e-5)
    assert dx.dtype == jnp.bfloat16
    as32 = lambda u: np.asarray(u.astype(jnp.float32))
    np.testing.assert_allclose(as32(dx), as32(dx_), atol=2 ** -6, rtol=2 ** -6)
