"""The short-convolution kernel pair (`ops/shortconv.py`, interpreted here)
against the op's plain form, `short_conv_plain`, which runs wherever the
pair does not; and which of the two `short_conv` takes where."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from kubeflow_tpu.ops import shortconv
from kubeflow_tpu.testing.hlo import jaxpr_kernel_names

F32, BF16 = jnp.float32, jnp.bfloat16
SCALE, EPS = 0.3, 1e-6


plain = functools.partial(shortconv.short_conv_plain, scale=SCALE, eps=EPS)


def kernels(u, w, bias=None, *, sum_dtype=F32, head_dim=0):
    return shortconv.short_conv(
        u, w, bias, sum_dtype=sum_dtype, head_dim=head_dim, scale=SCALE,
        eps=EPS, interpret=True,
    )


def _drawn(shape, taps=4, bias=False, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    width = shape[-1]
    return (
        jax.random.normal(keys[0], shape, F32).astype(BF16),
        0.5 * jax.random.normal(keys[1], (taps, width), F32),
        0.1 * jax.random.normal(keys[2], (width,), F32) if bias else None,
        jax.random.normal(keys[3], shape, F32).astype(BF16),
    )


@pytest.fixture
def blocks_of_128_rows(monkeypatch):
    """Several row blocks in a short sequence."""
    monkeypatch.setattr(shortconv, "_ROWS", 128)
    jax.clear_caches()
    yield
    jax.clear_caches()


CASES = {
    "the delta mixer's q and k: the sum rounded, a head's norm": dict(
        how=dict(sum_dtype=BF16, head_dim=128), width=256, bias=False,
    ),
    "the delta mixer's v: the sum rounded, no norm": dict(
        how=dict(sum_dtype=BF16), width=256, bias=False,
    ),
    "the state-space mixer's xBC: a bias, the sum in float32": dict(
        how=dict(), width=384, bias=True,
    ),
    "heads of two lane tiles, a bias and three taps": dict(
        how=dict(head_dim=256), width=256, bias=True, taps=3,
    ),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_forward_and_every_gradient_match_the_plain_expression(
    case, blocks_of_128_rows
):
    """Batch 2 with different sequences over three row blocks: a halo
    taken from the other sequence, or not masked in a sequence's first
    block, moves the first rows of a block by whole units."""
    spec = CASES[case]
    u, w, bias, dy = _drawn(
        (2, 384, spec["width"]), spec.get("taps", 4), spec["bias"]
    )
    got, got_vjp = jax.vjp(functools.partial(kernels, **spec["how"]), u, w, bias)
    want, want_vjp = jax.vjp(functools.partial(plain, **spec["how"]), u, w, bias)
    # one unit of bfloat16 at the largest value
    np.testing.assert_allclose(
        got.astype(F32), want.astype(F32), atol=2.0 ** -8 * float(
            jnp.max(jnp.abs(want.astype(F32)))
        ),
    )
    for name, a, b in zip(("u", "taps", "bias"), got_vjp(dy), want_vjp(dy)):
        if b is None:
            assert a is None
            continue
        a, b = a.astype(F32), b.astype(F32)
        # XLA rounds the sum's cotangent to bfloat16 where the sum was
        # rounded; the kernel keeps it in float32.
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 5e-3, name
    assert got_vjp(dy)[0].dtype == BF16


def test_the_first_rows_of_a_sequence_see_zeros_not_the_other_sequence(
    blocks_of_128_rows,
):
    """Each sequence alone gives what it gives in the batch, forward and
    backward: nothing crosses from one to the next."""
    u, w, _, dy = _drawn((2, 256, 128))
    how = dict(sum_dtype=BF16, head_dim=128)
    both, vjp = jax.vjp(lambda u: kernels(u, w, **how), u)
    (du,) = vjp(dy)
    for b in range(2):
        alone, vjp = jax.vjp(lambda u: kernels(u, w, **how), u[b:b + 1])
        np.testing.assert_array_equal(alone[0], both[b])
        np.testing.assert_array_equal(vjp(dy[b:b + 1])[0][0], du[b])


@pytest.mark.parametrize("row", [127, 255, 383])
def test_a_cotangent_in_a_blocks_last_row_reaches_the_rows_before_it(
    row, blocks_of_128_rows
):
    """`du_t = sum_j w_j dm_(t+j)`: the last row of a block has its
    gradient's `taps` - 1 rows in the SAME block, the first rows of the
    next block send theirs back across the boundary (the rows after)."""
    u, w, bias, _ = _drawn((1, 384, 128), bias=True)
    dy = jnp.zeros(u.shape, BF16).at[0, row].set(1.0)
    if row + 1 < u.shape[1]:
        dy = dy.at[0, row + 1].set(-0.5)
    _, got = jax.vjp(kernels, u, w, bias)
    _, want = jax.vjp(plain, u, w, bias)
    for a, b in zip(got(dy), want(dy)):
        np.testing.assert_allclose(
            a.astype(F32), b.astype(F32), atol=1e-2, rtol=1e-2
        )
    du = got(dy)[0].astype(F32)[0]
    assert float(jnp.abs(du[row - 3:row + 1]).min()) > 0
    assert not du[: row - 3].any() and not du[row + 2:].any()


def test_a_long_block_walks_its_rows_in_chunks():
    """The default block at a sequence of 512 rows: one block of eight
    loop steps, and the rows after it masked."""
    u, w, bias, dy = _drawn((1, 512, 128), bias=True, seed=3)
    got, got_vjp = jax.vjp(kernels, u, w, bias)
    want, want_vjp = jax.vjp(plain, u, w, bias)
    np.testing.assert_allclose(got.astype(F32), want.astype(F32), atol=2e-2)
    for a, b in zip(got_vjp(dy), want_vjp(dy)):
        a, b = a.astype(F32), b.astype(F32)
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 5e-3


FALLBACKS = {
    "float32": dict(dtype=F32),
    "a sequence that is not whole blocks": dict(seq=192),
    "a width that is not whole lane tiles": dict(width=192),
    "heads that are not whole lane tiles": dict(head_dim=64),
    "more taps than the table holds": dict(taps=7),
    "a mesh of several devices": dict(devices=2),
    "a mesh of four": dict(devices=4),
    "a backend that interprets": dict(compiled=None),
}


@pytest.mark.parametrize("case", [None, *sorted(FALLBACKS)])
def test_where_the_kernels_apply(case, monkeypatch):
    how = dict(
        dtype=BF16, seq=256, width=256, head_dim=128, taps=4, devices=1,
        compiled=True,
    ) | (FALLBACKS[case] if case else {})
    mesh = Mesh(np.array(jax.devices()[: how["devices"]]), ("x",))
    u = jax.ShapeDtypeStruct((2, how["seq"], how["width"]), how["dtype"])
    assert shortconv.kernels_apply(
        u, how["taps"], how["head_dim"], mesh, compiled=how["compiled"]
    ) is (case is None)
    assert shortconv.kernels_apply(u, how["taps"], how["head_dim"], None) is False
    # ... and `short_conv` takes the form it says: the pair, or the plain
    # form's very equations and no kernel.
    monkeypatch.setattr(shortconv, "kernels_apply", functools.partial(
        shortconv.kernels_apply, compiled=how["compiled"]
    ))
    w = jax.ShapeDtypeStruct((how["taps"], how["width"]), F32)
    args = dict(sum_dtype=BF16, head_dim=how["head_dim"], scale=SCALE, eps=EPS)
    traced = lambda fn, **more: jax.make_jaxpr(
        functools.partial(fn, **args, **more)
    )(u, w)
    entry = traced(shortconv.short_conv, mesh=mesh)
    if case is None:
        assert jaxpr_kernel_names(entry.jaxpr) == ["shortconv_fwd"]
    else:
        assert jaxpr_kernel_names(entry.jaxpr) == []
        assert str(entry) == str(traced(shortconv.short_conv_plain))
    if not set(FALLBACKS.get(case, {})) - {"devices", "compiled"}:
        # shapes the pair takes: told to interpret, it runs whatever the
        # backend and the mesh
        told = traced(shortconv.short_conv, mesh=mesh, interpret=True)
        assert jaxpr_kernel_names(told.jaxpr) == ["shortconv_fwd"]


def test_the_pair_is_one_call_each_way_under_its_names(blocks_of_128_rows):
    u, w, bias, dy = _drawn((1, 256, 128), bias=True)
    forward = jax.make_jaxpr(kernels)(u, w, bias)
    assert jaxpr_kernel_names(forward.jaxpr) == ["shortconv_fwd"]
    backward = jax.make_jaxpr(
        lambda u, w, bias: jax.vjp(kernels, u, w, bias)[1](dy)
    )(u, w, bias)
    assert sorted(jaxpr_kernel_names(backward.jaxpr)) == [
        "shortconv_bwd", "shortconv_fwd",
    ]
    wide = [
        v.aval for eqn in backward.jaxpr.eqns for v in eqn.outvars
        if v.aval.dtype == F32 and v.aval.shape[-2:] == u.shape[-2:]
    ]
    assert not wide  # no float32 [tokens, W] array outside the kernels
