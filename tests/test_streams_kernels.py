"""The residual streams' row-block kernels (`kubeflow_tpu/ops/streams.py`)
against XLA's code, interpreted on the CPU: every pass alone, a whole
round's values and every gradient, the two rules under every remat policy
in a model, which form runs where, and the counter that says so."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models.transformer import Block, TransformerConfig, TransformerLM
from kubeflow_tpu.ops import streams as so
from kubeflow_tpu.testing.hlo import jaxpr_kernel_names

BF16, F32 = jnp.bfloat16, jnp.float32


def _spec(n, d, iters=3):
    return so.Maps(n, d, iters, 30.0, 1e-6, 1e-6)


def _leaves(spec, b, s, seed=0):
    """Streams, phi, a, b and a sublayer's matrix, seeded; the maps' bias
    leans to the identity as the model's seed does."""
    n, d = spec.n, spec.d
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (b, s, n * d)).astype(BF16)
    phi = jax.random.normal(ks[1], (n * d, spec.maps)) * (n * d) ** -0.5
    a = jnp.array([1.0, 0.8, 1.2], F32)
    bias = jnp.concatenate(
        [jnp.zeros(2 * n), 2.0 * jnp.eye(n).reshape(-1)]
    ) + 0.1 * jax.random.normal(ks[2], (spec.maps,))
    w = jax.random.normal(ks[3], (d, d)) * d ** -0.5
    return x, phi, a, bias.astype(F32), w


def _maps_xla(x, phi, a, bias, spec):
    """Today's code: the norm's scalar, the raw product, the three maps."""
    x32 = x.astype(F32)
    inv_rms = jax.lax.rsqrt(jnp.mean(x32 * x32, -1) + spec.norm_eps)
    t = so.exact_product(x, phi)
    return t, inv_rms, so.maps_of_products(t * inv_rms[:, None, :], a, bias, spec)


def _round_xla(x, phi, a, bias, w, spec):
    h, x, ho, hr = so.mixed_in(x, phi, a, bias, spec)
    y = jnp.tanh(h @ w.astype(x.dtype))
    return so.mixed_out(x, y, hr, ho, spec)


def _round_kernels(x, phi, a, bias, w, spec):
    h, link, ho, hr = so.mix_in(x, phi, a, bias, spec, interpret=True)
    y = jnp.tanh(h @ w.astype(x.dtype))
    return so.mix_out(link, y, hr, ho, spec, interpret=True)


def _close(got, want, rel):
    """Held to `rel` of the largest value: the kernels sum in another
    order and round dX once where XLA's code rounds its parts."""
    got, want = (np.asarray(u.astype(F32)) for u in (got, want))
    assert got.shape == want.shape
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, atol=rel * np.abs(want).max(), rtol=0)


# (n, d, B, S): four streams; two; three blocks of 128 rows a sequence
# (no multiple of 256); two sequences of two blocks.
ROUNDS = {
    "n4": (4, 128, 2, 128), "n2": (2, 256, 2, 128),
    "three-blocks": (4, 128, 1, 384), "blocks": (4, 128, 2, 256),
}


@pytest.fixture(scope="module", params=sorted(ROUNDS))
def round_(request):
    n, d, b, s = ROUNDS[request.param]
    spec = _spec(n, d)
    leaves = _leaves(spec, b, s)
    weights = jax.random.normal(jax.random.PRNGKey(9), (b, s, n * d))
    loss = lambda f: lambda *args: jnp.sum(f(*args, spec).astype(F32) * weights)
    run = lambda f: jax.jit(jax.value_and_grad(loss(f), argnums=(0, 1, 2, 3, 4)))(
        *leaves
    )
    values = [jax.jit(functools.partial(f, spec=spec))(*leaves)
              for f in (_round_xla, _round_kernels)]
    return values, run(_round_xla), run(_round_kernels)


def test_a_rounds_values_are_xlas(round_):
    (want, got), _, _ = round_
    assert got.dtype == BF16
    _close(got, want, 2 ** -7)


@pytest.mark.parametrize("leaf", ["x", "phi", "a", "b", "w"])
def test_a_rounds_gradient_is_xlas(round_, leaf):
    """X's through both mixes, the product and the norm, summed in
    float32 and rounded once (XLA's code rounds the product's part to
    bfloat16 first); phi's, a's and b's through the maps' backward from
    the kernels' dot products; w's through dy and dh."""
    _, (_, want), (_, got) = round_
    at = ["x", "phi", "a", "b", "w"].index(leaf)
    assert got[at].dtype == want[at].dtype
    _close(got[at], want[at], 2 ** -6 if leaf == "x" else 5e-3)


def _pass_operands(n=4, d=128, b=2, s=128):
    spec = _spec(n, d)
    x, phi, a, bias, _ = _leaves(spec, b, s, seed=1)
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    dxo = jax.random.normal(ks[0], x.shape).astype(BF16)
    y = jax.random.normal(ks[1], (b, s, d)).astype(BF16)
    dh = jax.random.normal(ks[2], (b, s, d)).astype(BF16)
    return spec, x, phi, a, bias, dxo, y, dh


def _streams32(x, spec):
    return [x[..., j * spec.d:(j + 1) * spec.d].astype(F32) for j in range(spec.n)]


def _pass_pre_fwd():
    """`hc_pre_fwd`: the raw product, the norm's scalar and h."""
    spec, x, phi, a, bias, *_ = _pass_operands()
    (h, link, ho, hr), (_, _, _, _, t, inv_rms) = so._mix_in_fwd(
        x, phi, a, bias, spec, True
    )
    t_, inv_, (hp_, ho_, hr_) = _maps_xla(x, phi, a, bias, spec)
    h_ = sum(hp_[:, i, :, None] * xi for i, xi in enumerate(_streams32(x, spec)))
    assert link is x
    return (t, inv_rms, h, ho, hr), (t_, inv_, h_.astype(BF16), ho_, hr_)


def _pass_post_bwd():
    """`hc_post_bwd`: dy and the dot products that are Hr's and Ho's
    cotangents, against XLA's own backward of the mix out."""
    spec, x, phi, a, bias, dxo, y, _ = _pass_operands()
    _, _, (_, ho, hr) = _maps_xla(x, phi, a, bias, spec)
    link, dy, dhr, dho = so._mix_out_bwd(spec, True, (x, y, hr, ho), dxo)
    assert link is dxo  # handed on whole: `mix_in`'s rule applies Hr
    _, vjp = jax.vjp(lambda y, hr, ho: so.mixed_out(x, y, hr, ho, spec), y, hr, ho)
    return (dy, dhr, dho), vjp(dxo)


def _pass_post_fwd():
    """`hc_post_fwd`: the streams mixed and the sublayer's output added."""
    spec, x, phi, a, bias, _, y, _ = _pass_operands()
    _, _, (_, ho, hr) = _maps_xla(x, phi, a, bias, spec)
    mixed, _ = so._mix_out_fwd(x, y, hr, ho, spec, True)
    return (mixed,), (so.mixed_out(x, y, hr, ho, spec),)


def _pass_pre_bwd():
    """`hc_pre_bwd` through `mix_in`'s rule: dX from dX', dh and the
    maps' cotangents against the sum of its four parts in float32, and
    phi's, a's and b's gradients, Hp's share of which the pass finds."""
    spec, x, phi, a, bias, dxo, _, dh = _pass_operands()
    n = spec.n
    residuals = so._mix_in_fwd(x, phi, a, bias, spec, True)[1]
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    dho = jax.random.normal(ks[0], (x.shape[0], n, x.shape[1]))
    dhr = jax.random.normal(ks[1], (x.shape[0], n, n, x.shape[1]))
    dx, dphi, da, dbias = so._mix_in_bwd(
        spec, True, residuals, (dh, dxo, dho, dhr)
    )

    def maps_and_h(x, phi, a, bias):
        _, _, (hp, ho, hr) = _maps_xla(x, phi, a, bias, spec)
        h = sum(hp[:, i, :, None] * xi for i, xi in enumerate(_streams32(x, spec)))
        return h, ho, hr

    (_, _, hr), vjp = jax.vjp(maps_and_h, x.astype(F32), phi, a, bias)
    dx_, dphi_, da_, dbias_ = vjp((dh.astype(F32), dho, dhr))
    through_hr = jnp.concatenate([
        sum(hr[:, i, j, :, None] * di for i, di in enumerate(_streams32(dxo, spec)))
        for j in range(n)
    ], -1)
    return (dx, dphi, da, dbias), ((dx_ + through_hr).astype(BF16), dphi_, da_, dbias_)


PASSES = {
    "hc_pre_fwd": _pass_pre_fwd, "hc_post_fwd": _pass_post_fwd,
    "hc_post_bwd": _pass_post_bwd, "hc_pre_bwd": _pass_pre_bwd,
}


@pytest.mark.parametrize("name", sorted(PASSES))
def test_a_pass_alone_is_xlas(name):
    got, want = PASSES[name]()
    for u, v in zip(got, want, strict=True):
        _close(u, v, 2 ** -7 if u.dtype == BF16 else 2e-3)


# -- the names and the scopes ----------------------------------------------------


def test_every_call_is_named_hc_and_lies_under_an_hc_scope():
    """Forward and in the hand-written backward: `hc_time_pct.train` and
    `hc_roofline.train` read frames that start `hc.` or `hc_`."""
    spec = _spec(4, 128)
    leaves = _leaves(spec, 1, 128)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *args: jnp.sum(_round_kernels(*args, spec).astype(F32)),
        argnums=(0, 1, 2, 3, 4),
    ))(*leaves).jaxpr
    names = jaxpr_kernel_names(jaxpr)
    assert sorted(names) == ["hc_post_bwd", "hc_post_fwd", "hc_pre_bwd", "hc_pre_fwd"]

    def calls(jaxpr, above=""):
        """(kernel's name, the stacks of the equations it lies under)."""
        for eqn in jaxpr.eqns:
            stack = f"{above}/{eqn.source_info.name_stack}"
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"], stack
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub, stack)

    found = dict(calls(jaxpr))
    assert sorted(found) == sorted(names)
    for name, stack in found.items():
        # each pass is a jitted function: the scope lies on its call
        assert ("hc.post" if "post" in name else "hc.pre") in stack, (name, stack)


# -- which form runs ---------------------------------------------------------------


def _streams(dtype=BF16, d=128, n=4, tokens=(2, 128)):
    return jax.ShapeDtypeStruct((*tokens, n * d), dtype), _spec(n, d)


class _Mesh:
    def __init__(self, size):
        self.size = size


@pytest.mark.parametrize("change, runs", [
    (dict(), True),
    (dict(dtype=F32), False),          # float32 streams
    (dict(d=96), False),               # d no whole lane tiles
    (dict(tokens=(4, 48)), False),     # a sequence of no whole blocks
    (dict(n=6), False),                # phi's three pieces pass 128 lanes
    (dict(mesh=_Mesh(1)), True),
    (dict(mesh=_Mesh(4)), False),      # a Pallas call does not partition itself
    (dict(compiled=False), False),     # the CPU
], ids=["cell-like", "float32", "d96", "48-tokens", "n6", "mesh-of-one",
        "mesh-of-four", "not-compiled"])
def test_the_kernels_run_where_they_apply(change, runs, monkeypatch):
    change = dict(change)
    mesh, compiled = change.pop("mesh", None), change.pop("compiled", True)
    streams, spec = _streams(**change)
    assert so.kernels_apply(streams, spec, mesh, compiled=compiled) is runs
    # What the program asks by itself is the backend: the CPU interprets.
    assert so.kernels_apply(streams, spec, mesh) is False
    # ... and the round's two entries take the form it says, both the
    # same: the kernels, or the plain form's very equations and no kernel.
    monkeypatch.setattr(
        so, "kernels_apply", functools.partial(so.kernels_apply, compiled=compiled)
    )
    shaped = lambda *shape: jax.ShapeDtypeStruct(shape, F32)
    (b, s, _), n = streams.shape, spec.n
    into = streams, shaped(n * spec.d, spec.maps), shaped(3), shaped(spec.maps)
    out = (
        streams, jax.ShapeDtypeStruct((b, s, spec.d), streams.dtype),
        shaped(b, n, n, s), shaped(b, n, s),
    )
    for entry, plain, args, name in (
        (so.mix_in, so.mixed_in, into, "hc_pre_fwd"),
        (so.mix_out, so.mixed_out, out, "hc_post_fwd"),
    ):
        traced = jax.make_jaxpr(lambda *a: entry(*a, spec, mesh))(*args)
        assert jaxpr_kernel_names(traced.jaxpr) == ([name] if runs else [])
        if not runs:
            assert str(traced) == str(
                jax.make_jaxpr(lambda *a: plain(*a, spec))(*args)
            )
        if not set(change):  # the cell's shapes: told to interpret, it does
            told = jax.make_jaxpr(
                lambda *a: entry(*a, spec, mesh, interpret=True)
            )(*args)
            assert jaxpr_kernel_names(told.jaxpr) == [name]


def _block_config(**how):
    return TransformerConfig(**{
        **dict(
            vocab_size=64, d_model=128, n_layers=1, n_heads=2, head_dim=16,
            d_ff=64, residual_streams=4, hc_iters=2, attention_impl="dense",
            remat_policy="none", dtype=BF16, tie_embeddings=False,
        ), **how,
    })


def _counters(cfg, monkeypatch, compiled):
    if compiled:
        monkeypatch.setattr(
            so, "kernels_apply",
            functools.partial(so.kernels_apply, compiled=True),
        )
    model = TransformerLM(cfg)
    tokens = jnp.arange(256, dtype=jnp.int32).reshape(2, 128) % 64
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    unboxed = jax.tree_util.tree_map(
        lambda v: v.value if hasattr(v, "value") else v, params,
        is_leaf=lambda v: hasattr(v, "value"),
    )
    _, mutated = model.apply({"params": unboxed}, tokens, mutable=["counters"])
    return sum(
        float(leaf) for path, leaf in
        jax.tree_util.tree_flatten_with_path(mutated["counters"])[0]
        if "hc_kernel_sublayers" in jax.tree_util.keystr(path)
    )


@pytest.mark.parametrize("how, compiled, sublayers", [
    (dict(), True, 2),
    (dict(n_layers=3), True, 6),
    (dict(), False, 0),                    # the CPU by itself
    (dict(dtype=F32), True, 0),            # float32 streams -> XLA's code
    (dict(d_model=96, head_dim=16), True, 0),  # d not of 128 lanes
], ids=["kernels", "three-layers", "cpu", "float32", "d96"])
def test_the_counter_says_how_many_sublayers_ran_as_kernels(
    monkeypatch, how, compiled, sublayers
):
    assert _counters(_block_config(**how), monkeypatch, compiled) == sublayers


def test_a_block_as_kernels_is_the_block_in_xla(monkeypatch):
    """One layer of four streams, forward and every parameter's gradient,
    the kernels' rules against JAX's own derivative of XLA's code."""
    cfg = _block_config()
    block = Block(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 4 * 128)).astype(BF16)
    positions = jnp.broadcast_to(jnp.arange(128), (2, 128))
    params = jax.tree_util.tree_map(
        lambda v: v.value if hasattr(v, "value") else v,
        block.init(jax.random.PRNGKey(7), x, positions)["params"],
        is_leaf=lambda v: hasattr(v, "value"),
    )
    loss = lambda params, x: jnp.sum(
        block.apply({"params": params}, x, positions)[0].astype(F32) ** 2
    )
    want = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(params, x)
    monkeypatch.setattr(
        so, "kernels_apply", functools.partial(so.kernels_apply, compiled=True)
    )
    got = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(params, x)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-3)
    for (path, u), v in zip(
        jax.tree_util.tree_flatten_with_path(got[1])[0],
        jax.tree_util.tree_leaves(want[1]), strict=True,
    ):
        _close(u, v, 3e-2)
