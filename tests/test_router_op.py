"""The router's op (`ops/router.py`): its product against a float64 one at
a tolerance a cheaper product fails, the kernel pair (interpreted here)
against the op's plain form, today's `take_along_axis`, which runs
wherever the pair does not, which of the two `route_weights` takes where,
and what `router_schedule()` says of the cells' shapes."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh

from kubeflow_tpu.models.transformer import forced_experts
from kubeflow_tpu.ops import router
from kubeflow_tpu.ops.streams import split3
from kubeflow_tpu.testing.hlo import jaxpr_kernel_names

F32, BF16 = jnp.float32, jnp.bfloat16
HIGHEST = lax.Precision.HIGHEST
# (tokens, d, N, k, scoring, routed_scaling) of the five cells' one-matmul
# routers: qwen3-next, nemotron, laguna, kimi, xing.
CELLS = {
    "qwen3-next": (16384, 2048, 512, 10, "softmax", 1.0),
    "nemotron": (8192, 4096, 512, 22, "sigmoid", 5.0),
    "laguna": (8192, 3072, 256, 10, "sigmoid", 2.5),
    "kimi": (8192, 2304, 256, 8, "sigmoid", 2.446),
    "xing": (8192, 3584, 64, 4, "sigmoid", 2.0),
}


def _operands(d, n, batch=2, seq=128, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(keys[0], (batch, seq, d), F32)
    w = jax.random.normal(keys[1], (d, n), F32) * d ** -0.5
    dlogits = jax.random.normal(keys[2], (batch, seq, n), F32)
    return x, w, dlogits


def _f64(a):
    return np.asarray(a.astype(F32), np.float64)


# -- the product ---------------------------------------------------------------

# Products of a bfloat16 x that carry fewer of W's bits, built from W's
# bfloat16 pieces: one pass (W rounded to bfloat16) and what `bf16_3x`
# keeps of a float32 operand, its first two pieces (`Precision.HIGH`
# itself is the CPU's plain float32, as `HIGHEST` is).
def _pieces(x, w, count):
    parts = jnp.split(split3(w, axis=1), 3, axis=1)[:count]
    return sum(jnp.dot(x, p, preferred_element_type=F32) for p in parts)


PRODUCTS = {
    "exact_dot": (router.exact_dot, True),
    "one pass": (functools.partial(_pieces, count=1), False),
    "bf16_3x": (functools.partial(_pieces, count=2), False),
}


@pytest.mark.parametrize("product", sorted(PRODUCTS))
@pytest.mark.parametrize("d", [512, 1024])
def test_the_product_holds_float32_and_a_cheaper_one_does_not(product, d):
    """Within float32's unit times sqrt(d), at the largest product, of the
    float64 product of the same bfloat16 x and float32 W."""
    x, w, _ = _operands(d, 256)
    x = x.astype(BF16)
    fn, holds = PRODUCTS[product]
    want = _f64(x).reshape(-1, d) @ np.asarray(w, np.float64)
    got = np.asarray(fn(x, w), np.float64).reshape(want.shape)
    tolerance = 2.0 ** -24 * d ** 0.5 * np.abs(want).max()
    assert bool(np.abs(got - want).max() <= tolerance) is holds


@pytest.mark.parametrize("form", ["exact_dot", "one pass", "bf16_3x"])
def test_the_weights_gradient_holds_float32_and_a_cheaper_one_does_not(form):
    d, n = 512, 256
    x, w, dlogits = _operands(d, n)
    x = x.astype(BF16)
    want = _f64(x).reshape(-1, d).T @ np.asarray(dlogits, np.float64).reshape(-1, n)
    if form == "exact_dot":
        got = jax.vjp(router.exact_dot, x, w)[1](dlogits)[1]
    else:
        got = _pieces(
            x.reshape(-1, d).T, dlogits.reshape(-1, n),
            1 if form == "one pass" else 2,
        )
    tolerance = 2.0 ** -24 * (x.shape[0] * x.shape[1]) ** 0.5 * np.abs(want).max()
    assert bool(
        np.abs(np.asarray(got, np.float64) - want).max() <= tolerance
    ) is (form == "exact_dot")


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_product_is_highest_on_both_sides(dtype):
    """The widened x against W at `HIGHEST`, forward and both gradients:
    the model's expression before the op, to the bit; what the chip runs
    of it follows x's dtype (`router_schedule`)."""
    x, w, dlogits = _operands(256, 128)
    x = x.astype(dtype)
    today = lambda x, w: jnp.dot(x.astype(F32), w, precision=HIGHEST)
    assert str(jax.make_jaxpr(router.exact_dot)(x, w)) == str(
        jax.make_jaxpr(today)(x, w)
    )
    backward = lambda fn: str(jax.make_jaxpr(
        lambda x, w: jax.vjp(fn, x, w)[1](dlogits)
    )(x, w))
    assert backward(router.exact_dot) == backward(today)
    # the forward's, x's gradient, W's: each `HIGHEST` on both sides
    assert backward(today).count("dot_general") == 3
    assert backward(today).count("Precision.HIGHEST") == 6
    np.testing.assert_array_equal(router.exact_dot(x, w), today(x, w))
    got = jax.vjp(router.exact_dot, x, w)[1](dlogits)
    want = jax.vjp(today, x, w)[1](dlogits)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)
    assert router.router_schedule(8192, 256, 128, 8, dtype)[
        "product_passes"
    ] == {
        "forward": 3 if dtype == "bfloat16" else 6,
        "weight_gradient": 3 if dtype == "bfloat16" else 6,
        "input_gradient": 6,
    }


# -- the weights ---------------------------------------------------------------


def _ids(how, logits, scoring, k, layer=1):
    batch, seq, n = logits.shape
    if how == "forced":
        return jnp.broadcast_to(forced_experts(layer, seq, n, k), (batch, seq, k))
    return lax.top_k(router.SCORES[scoring](logits), k)[1].astype(jnp.int32)


def _today(x, w, expert, scoring, scaling):
    """The router as the model wrote it before the op."""
    probs = router.SCORES[scoring](
        jnp.dot(x.astype(F32), w, precision=HIGHEST)
    )
    chosen = jnp.take_along_axis(probs, expert, axis=-1)
    return scaling * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)


def _op(x, w, expert, scoring, scaling):
    return router.route_weights(
        router.exact_dot(x, w), expert, scoring=scoring, scaling=scaling,
        interpret=True, name="moe_route",
    )


@pytest.mark.parametrize("ids", ["forced", "top_k"])
@pytest.mark.parametrize("scaled", [False, True])
@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_weights_and_every_gradient_match_the_plain_form(
    cell, scoring, scaled, ids
):
    _, _, n, k, _, scaling = CELLS[cell]
    scaling = (scaling if scaling != 1.0 else 2.5) if scaled else 1.0
    x, w, _ = _operands(64, n, seed=n + k)
    x = x.astype(BF16)
    expert = _ids(ids, router.exact_dot(x, w), scoring, k)
    assert all(  # k distinct a token
        len(set(row)) == k for row in np.asarray(expert).reshape(-1, k)[::17]
    )
    cotangent = jax.random.normal(jax.random.PRNGKey(7), expert.shape, F32)
    run = lambda fn: jax.jit(lambda x, w: jax.vjp(
        functools.partial(fn, expert=expert, scoring=scoring, scaling=scaling),
        x, w,
    ))(x, w)
    got, got_vjp = run(_op)
    want, want_vjp = run(_today)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-7)
    (dx, dw), (dx_want, dw_want) = got_vjp(cotangent), want_vjp(cotangent)
    assert dx.dtype == BF16 and dw.dtype == F32
    np.testing.assert_allclose(  # a unit of bfloat16 at the largest value
        dx.astype(F32), dx_want.astype(F32),
        atol=2.0 ** -7 * float(jnp.max(jnp.abs(dx_want.astype(F32)))),
    )
    np.testing.assert_allclose(
        dw, dw_want, atol=2e-5 * float(jnp.max(jnp.abs(dw_want)))
    )


@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
@pytest.mark.parametrize("batch, seq", [(1, 128), (2, 384), (1, 1024)])
def test_the_logits_cotangent_is_the_plain_forms(scoring, batch, seq):
    """Blocks of 128, 384 (three of 128) and 512 rows, each sequence alone."""
    n, k = 256, 6
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(0), (batch, seq, n), F32)
    expert = _ids("top_k", logits + 1.0, scoring, k)
    cotangent = jax.random.normal(jax.random.PRNGKey(1), expert.shape, F32)
    forms = {
        "kernels": lambda l: router.route_weights(
            l, expert, scoring=scoring, scaling=2.5, interpret=True
        ),
        "plain": lambda l: router.route_weights_plain(l, expert, scoring, 2.5),
    }
    got, want = (
        jax.jit(lambda l, fn=fn: jax.vjp(fn, l)[1](cotangent))(logits)[0]
        for fn in forms.values()
    )
    np.testing.assert_allclose(
        got, want, atol=4e-6 * float(jnp.max(jnp.abs(want)))
    )
    # dense, and zero wherever a sigmoid's expert was not chosen
    if scoring == "sigmoid":
        chosen = jnp.any(
            expert[..., None] == jnp.arange(n, dtype=jnp.int32), axis=-2
        )
        assert not np.asarray(jnp.where(chosen, 0.0, got)).any()


# -- which form runs -----------------------------------------------------------

FALLBACKS = {
    "64 experts (xing)": dict(n=64),
    "192 experts": dict(n=192),
    "one expert a token": dict(k=1),
    "a sequence of 100 rows": dict(seq=100),
    "bfloat16 logits": dict(dtype=BF16),
    "a mesh of two devices": dict(devices=2),
    "a mesh of four": dict(devices=4),
    "a backend that interprets": dict(compiled=None),
}


@pytest.mark.parametrize("case", [None, *sorted(FALLBACKS)])
def test_where_the_kernels_apply(case, monkeypatch):
    how = dict(
        dtype=F32, seq=256, n=256, k=4, devices=1, compiled=True,
    ) | (FALLBACKS[case] if case else {})
    mesh = Mesh(np.array(jax.devices()[: how["devices"]]), ("x",))
    logits = jax.ShapeDtypeStruct((2, how["seq"], how["n"]), how["dtype"])
    expert = jax.ShapeDtypeStruct((2, how["seq"], how["k"]), jnp.int32)
    assert router.kernels_apply(
        logits, expert, mesh, compiled=how["compiled"]
    ) is (case is None)
    assert router.kernels_apply(logits, expert, None) is False
    # ... and `route_weights` takes the form it says: the pair, or the
    # plain form's very equations and no kernel.
    monkeypatch.setattr(router, "kernels_apply", functools.partial(
        router.kernels_apply, compiled=how["compiled"]
    ))
    traced = lambda fn, **more: jax.make_jaxpr(
        functools.partial(fn, **more)
    )(logits, expert)
    entry = traced(router.route_weights, scoring="sigmoid", scaling=2.0, mesh=mesh)
    if case is None:
        assert jaxpr_kernel_names(entry.jaxpr) == ["route_weights_fwd"]
    else:
        assert jaxpr_kernel_names(entry.jaxpr) == []
        assert str(entry) == str(traced(
            router.route_weights_plain, scoring="sigmoid", scaling=2.0
        ))
    told = traced(
        router.route_weights, scoring="sigmoid", mesh=mesh, interpret=True
    )
    fits = not set(FALLBACKS.get(case, {})) - {"devices", "compiled"}
    # shapes the pair takes: told to interpret, it runs whatever the
    # backend and the mesh; others stay plain
    assert jaxpr_kernel_names(told.jaxpr) == ["route_weights_fwd"] * fits


def test_a_vector_of_ids_stays_plain():
    logits = jax.ShapeDtypeStruct((2, 256, 256), F32)
    expert = jax.ShapeDtypeStruct((2, 256), jnp.int32)
    assert not router.kernels_apply(logits, expert, None, compiled=True)


@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
def test_the_pair_is_one_call_each_way_and_a_kept_name_runs_none_again(scoring):
    """`route_weights_fwd` forward and `route_weights_bwd` backward, neither
    `moe_*` (`moe_time_pct.train` sums those); under a checkpoint whose
    policy keeps the name the backward forms no weights again, and a
    sigmoid's reads no logits."""
    x, w, _ = _operands(64, 256)
    x = x.astype(BF16)
    expert = _ids("forced", router.exact_dot(x, w), scoring, 6)

    def weights(x, w):
        logits = jax.ad_checkpoint.checkpoint_name(
            router.exact_dot(x, w), "moe_route"
        )
        return router.route_weights(
            logits, expert, scoring=scoring, interpret=True, name="moe_route"
        )

    forward = jax.make_jaxpr(weights)(x, w)
    assert jaxpr_kernel_names(forward.jaxpr) == ["route_weights_fwd"]
    kept = jax.checkpoint(
        weights, policy=jax.checkpoint_policies.save_only_these_names("moe_route")
    )
    backward = jax.make_jaxpr(jax.grad(lambda x, w: jnp.sum(kept(x, w) ** 2), 1))(x, w)
    assert sorted(jaxpr_kernel_names(backward.jaxpr)) == [
        "route_weights_bwd", "route_weights_fwd",
    ]
    text = str(backward)
    assert "gather" not in text and "scatter" not in text
    # the forward's product and W's gradient: neither formed again
    assert text.count("dot_general") == 2


# -- what it costs -------------------------------------------------------------


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_router_schedule_at_the_cells_shapes(cell):
    tokens, d, n, k, _, _ = CELLS[cell]
    on_chip = router.router_schedule(tokens, d, n, k, BF16, compiled=True)
    here = router.router_schedule(tokens, d, n, k, BF16)
    wide = router.router_schedule(tokens, d, n, k, F32, compiled=True)
    assert on_chip["product_passes"] == {
        "forward": 3, "weight_gradient": 3, "input_gradient": 6,
    }
    assert set(wide["product_passes"].values()) == {6}
    kernels = cell != "xing"  # 64 experts are no lane tile: today's take
    assert on_chip["form"] == ("kernels" if kernels else "plain")
    singly = 0 if kernels else tokens * k
    assert on_chip["gathered_elements"] == on_chip["scattered_elements"] == singly
    # the CPU interprets, and a mesh of several devices partitions no call
    mesh = Mesh(np.array(jax.devices()[:2]), ("x",))
    split = router.router_schedule(tokens, d, n, k, BF16, mesh=mesh, compiled=True)
    for plain in (here, split):
        assert plain["form"] == "plain"
        assert plain["gathered_elements"] == plain["scattered_elements"] == tokens * k


# -- inside a model ------------------------------------------------------------


def _model_loss_and_gradient(scoring: str, policy: str, kernels: bool):
    """An expert layer of 128 experts (eight of them held), four a token,
    over [2, 128] tokens in bfloat16: (kernel names of the gradient,
    (loss, gradient))."""
    from kubeflow_tpu.models.transformer import TransformerConfig, TransformerLM

    cfg = TransformerConfig(
        vocab_size=64, d_model=128, n_layers=1, n_heads=2, head_dim=64,
        d_ff=32, num_experts=128, experts_held=(0, 8), experts_per_token=4,
        router=scoring,
        routed_scaling=2.5, router_force_balance=True, remat_policy=policy,
        attention_impl="dense", dtype=BF16,
    )
    model = TransformerLM(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 129), 0, 64)
    params = model.init(jax.random.PRNGKey(4), tokens[:, :-1])

    def loss(params):
        logits = model.apply(params, tokens[:, :-1]).astype(F32)
        picked = jnp.take_along_axis(
            jax.nn.log_softmax(logits), tokens[:, 1:, None], axis=-1
        )
        return -jnp.mean(picked)

    patch = pytest.MonkeyPatch()
    try:
        if kernels:
            patch.setattr(router, "kernels_apply", functools.partial(
                router.kernels_apply, compiled=True
            ))
        names = jaxpr_kernel_names(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
        return names, jax.jit(jax.value_and_grad(loss))(params)
    finally:
        patch.undo()


@pytest.mark.parametrize("scoring, policy", [
    ("softmax", "none"), ("sigmoid", "full"),
])
def test_a_model_routes_by_the_pair_as_by_the_plain_form(scoring, policy):
    """The pair once each way (the forward's again where a
    checkpoint keeps nothing), the loss and every gradient leaf the plain
    form's under the same policy within two units of bfloat16 at the
    leaf's largest value."""
    plain_names, (want, want_grads) = _model_loss_and_gradient(
        scoring, policy, kernels=False
    )
    assert not [n for n in plain_names if n.startswith("route_weights")]
    names, (got, grads) = _model_loss_and_gradient(scoring, policy, kernels=True)
    routed = sorted(n for n in names if n.startswith("route_weights"))
    assert routed == (
        ["route_weights_bwd"]
        + ["route_weights_fwd"] * (2 if policy == "full" else 1)
    )
    np.testing.assert_allclose(got, want, rtol=1e-5)
    flat = jax.tree_util.tree_leaves_with_path
    for (path, a), (_, b) in zip(flat(grads), flat(want_grads)):
        scale = float(jnp.max(jnp.abs(b.astype(F32))))
        np.testing.assert_allclose(
            a.astype(F32), b.astype(F32), atol=2.0 ** -6 * scale + 1e-9,
            err_msg=jax.tree_util.keystr(path),
        )
