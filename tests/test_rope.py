"""Rotary embeddings over [B, S, H·D] (ISSUE 31): the plain folded form
against the [B, S, H, D] formula it replaced, the Pallas kernel (under
the interpreter) against the folded form, and which of the two `rope`
picks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.ops.rope import rope, rope_folded, rope_tables
from kubeflow_tpu.testing.hlo import pallas_kernel_names

THETA = 10000.0


def _rope_4d(x, positions, theta, fraction=1.0):
    """The formula `models/transformer.rope` had: split a head in two
    halves, turn the pairs, concatenate."""
    turned = int(x.shape[-1] * fraction)
    if turned != x.shape[-1]:
        return jnp.concatenate(
            [_rope_4d(x[..., :turned], positions, theta), x[..., turned:]],
            axis=-1,
        )
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions[..., None].astype(jnp.float32) * freqs
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _x(b, s, h, d, dtype, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), (b, s, h, d), dtype)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    return x, positions


def _weighted(y):
    y = y.astype(jnp.float32)
    return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape) * 0.37))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("fraction", [1.0, 0.5])
@pytest.mark.parametrize("d", [32, 128])
def test_folded_form_is_the_4d_formula_bit_for_bit(d, fraction, dtype):
    """Operation by operation the same products and sums, so eagerly the
    folded form and its gradient equal the [B, S, H, D] formula's."""
    b, s, h = 2, 24, 3
    x, positions = _x(b, s, h, d, dtype)
    fold = lambda u: u.reshape(b, s, h * d)
    old = lambda x: _rope_4d(x, positions, THETA, fraction)
    new = lambda x: rope(
        fold(x), positions, THETA, fraction, head_dim=d
    ).reshape(x.shape)
    np.testing.assert_array_equal(new(x), old(x))
    np.testing.assert_array_equal(
        jax.grad(lambda x: _weighted(new(x)))(x),
        jax.grad(lambda x: _weighted(old(x)))(x),
    )


@pytest.mark.parametrize(
    "h,fraction,dtype",
    [
        (4, 1.0, jnp.bfloat16),  # equal heads
        (2, 0.5, jnp.bfloat16),  # zaya's K: two heads, half of each turns
        (3, 1.0, jnp.float32),
        (3, 0.5, jnp.float32),
    ],
)
def test_kernel_matches_the_folded_form_forward_and_backward(
    h, fraction, dtype
):
    """The kernel turns each head's 128 lanes by a lane rotation, the
    folded form by rolls of the whole axis: the same numbers, to the
    compiler's contraction of a*b + c (in bfloat16 that is the last bit
    of a few results in a million), and its VJP, the same kernel by the
    negated angle, is the folded form's gradient."""
    b, s, d = 2, 512, 128
    x, positions = _x(b, s, h, d, dtype, seed=1)
    x = x.reshape(b, s, h * d)
    plain = lambda x: rope(x, positions, THETA, fraction, head_dim=d)
    kernel = lambda x: rope(
        x, positions, THETA, fraction, head_dim=d, interpret=True
    )
    assert pallas_kernel_names(plain, x) == []
    assert pallas_kernel_names(kernel, x) == ["rope_turn_fwd"]
    assert pallas_kernel_names(
        jax.grad(lambda x: _weighted(kernel(x))), x
    ) == ["rope_turn_fwd", "rope_turn_bwd"]
    tol = dict(atol=2e-6, rtol=2e-6)
    if dtype == jnp.bfloat16:
        tol = dict(atol=2 ** -8, rtol=2 ** -7)
    as32 = lambda u: np.asarray(u.astype(jnp.float32))
    np.testing.assert_allclose(as32(kernel(x)), as32(plain(x)), **tol)
    np.testing.assert_allclose(
        as32(jax.grad(lambda x: _weighted(kernel(x)))(x)),
        as32(jax.grad(lambda x: _weighted(plain(x)))(x)),
        **tol,
    )


@pytest.mark.parametrize(
    "s,d,kernel",
    [
        (512, 128, True),
        (512, 256, True),  # two lane tiles a head
        (512, 64, False),  # a head is not whole lanes
        (250, 128, False),  # no 8-aligned block divides the sequence
    ],
)
def test_kernel_engages_from_the_shapes(s, d, kernel):
    x, positions = _x(1, s, 2, d, jnp.float32, seed=2)
    x = x.reshape(1, s, 2 * d)
    forced = lambda x: rope(x, positions, THETA, head_dim=d, interpret=True)
    assert (pallas_kernel_names(forced, x) == ["rope_turn_fwd"]) == kernel
    want = _rope_4d(x.reshape(1, s, 2, d), positions, THETA).reshape(x.shape)
    np.testing.assert_allclose(forced(x), want, atol=2e-6, rtol=2e-6)


def test_kernel_runs_in_shard_map_over_batch_and_heads(devices):
    """A Pallas call does not partition itself: on a mesh the kernel sees
    its shard's batch rows and heads, and the tables their batch rows."""
    from kubeflow_tpu.parallel import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(dp=2, tp=2), devices[:4])
    b, s, h, d = 2, 256, 4, 128
    x, positions = _x(b, s, h, d, jnp.float32, seed=3)
    x = x.reshape(b, s, h * d)
    on_mesh = jax.jit(
        lambda x: rope(
            x, positions, THETA, head_dim=d, mesh=mesh, interpret=True
        )
    )
    plain = jax.jit(lambda x: rope(x, positions, THETA, head_dim=d))
    np.testing.assert_allclose(on_mesh(x), plain(x), atol=2e-6, rtol=2e-6)
    # Three heads do not divide tp = 2: the plain form, partitioned by jit.
    x3 = x[..., : 3 * d]
    odd = lambda x: rope(
        x, positions, THETA, head_dim=d, mesh=mesh, interpret=True
    )
    assert pallas_kernel_names(odd, x3) == []


def test_tables_are_one_head_wide_and_keep_the_lanes_that_stay():
    positions = jnp.arange(6).reshape(1, 6)
    cos, sin = rope_tables(positions, THETA, 16, fraction=0.5)
    assert cos.shape == sin.shape == (1, 6, 16)
    np.testing.assert_array_equal(cos[..., 8:], 1.0)
    np.testing.assert_array_equal(sin[..., 8:], 0.0)
    np.testing.assert_array_equal(sin[..., :4], -sin[..., 4:8])
    x = jnp.arange(1 * 6 * 32, dtype=jnp.float32).reshape(1, 6, 32)
    out = rope_folded(x, cos, sin, 4)
    np.testing.assert_array_equal(out[..., 8:16], x[..., 8:16])
    np.testing.assert_array_equal(out[..., 24:], x[..., 24:])
