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
    "s,h,d,kernel",
    [
        (512, 2, 128, True),
        (512, 2, 256, True),  # two lane tiles a head
        (512, 4, 64, True),   # latent attention's rope part: two heads a tile
        (512, 3, 64, False),  # ... but not where the heads leave half a tile
        (512, 1, 64, False),  # the head-less rope key [B, S, 64]
        (512, 2, 48, False),  # a head is no share of a tile
        (250, 2, 128, False),  # no 8-aligned block divides the sequence
    ],
)
def test_kernel_engages_from_the_shapes(s, h, d, kernel):
    x, positions = _x(1, s, h, d, jnp.float32, seed=2)
    x = x.reshape(1, s, h * d)
    forced = lambda x: rope(x, positions, THETA, head_dim=d, interpret=True)
    assert (pallas_kernel_names(forced, x) == ["rope_turn_fwd"]) == kernel
    want = _rope_4d(x.reshape(1, s, h, d), positions, THETA).reshape(x.shape)
    np.testing.assert_allclose(forced(x), want, atol=2e-6, rtol=2e-6)
    if kernel:  # and its VJP is the plain form's gradient
        plain = lambda x: rope(x, positions, THETA, head_dim=d)
        np.testing.assert_allclose(
            jax.grad(lambda x: _weighted(forced(x)))(x),
            jax.grad(lambda x: _weighted(plain(x)))(x), atol=2e-6, rtol=2e-6,
        )


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


# -- yarn: frequencies as data (ISSUE 34) ------------------------------------


@pytest.mark.parametrize("theta, turned, factor, original, fast, slow", [
    (500000.0, 64, 128.0, 8192, 32.0, 1.0),  # Laguna-S-2.1's global layers
    (10000.0, 128, 4.0, 4096, 32.0, 1.0),
    (500000.0, 8, 8.0, 16, 4.0, 1.0),        # tests/test_laguna.py's tiny one
])
def test_yarn_frequencies_are_the_published_formula(
    theta, turned, factor, original, fast, slow
):
    import math

    from kubeflow_tpu.ops.rope import yarn_inv_freq

    got = yarn_inv_freq(
        theta, turned, factor=factor, original_max=original, beta_fast=fast,
        beta_slow=slow,
    )
    c = lambda r: turned * math.log(original / (2 * math.pi * r)) / (
        2 * math.log(theta)
    )
    low, high = max(math.floor(c(fast)), 0), min(math.ceil(c(slow)), turned - 1)
    want = []
    for t in range(turned // 2):
        extrap = theta ** (-2 * t / turned)
        ramp = min(max((t - low) / (high - low), 0.0), 1.0)
        want.append((extrap / factor) * ramp + extrap * (1 - ramp))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # fast pairs keep their frequency, slow ones are stretched by `factor`
    assert got[0] == pytest.approx(1.0)
    assert got[-1] == pytest.approx(theta ** (-(turned - 2) / turned) / factor, rel=1e-6)


@pytest.mark.parametrize("kernel", [False, True])
def test_tables_from_frequencies_and_a_scale_turn_like_the_formula(kernel):
    """Half a head under yarn's tables times an attention factor: the
    folded form and the kernel against the [B, S, H, D] formula written
    out, the lanes that stay unscaled."""
    from kubeflow_tpu.ops.rope import yarn_inv_freq

    b, s, h, d, scale = 2, 64, 3, 128, 1.4852030263919618
    x, positions = _x(b, s, h, d, jnp.float32)
    inv_freq = yarn_inv_freq(500000.0, 64, factor=128.0, original_max=8192)
    got = rope(
        x.reshape(b, s, h * d), positions, 500000.0, 0.5, head_dim=d,
        inv_freq=inv_freq, scale=scale, interpret=True if kernel else None,
    ).reshape(x.shape)
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    cos = scale * jnp.cos(angles)[:, :, None, :]
    sin = scale * jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :32], x[..., 32:64]
    want = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., 64:]], axis=-1
    )
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    cos_t, sin_t = rope_tables(
        positions, 500000.0, d, 0.5, inv_freq=inv_freq, scale=scale
    )
    assert np.all(cos_t[..., 64:] == 1.0) and np.all(sin_t[..., 64:] == 0.0)
    with pytest.raises(ValueError, match="one a pair is 32"):
        rope_tables(positions, 500000.0, d, 0.5, inv_freq=inv_freq[:8])


def test_a_wide_row_takes_fewer_rows_a_block():
    """72 heads of 128 in bfloat16: 64 rows a block, under the compiler's
    scoped VMEM with both buffers; the accepted cells' widths keep 256."""
    from kubeflow_tpu.ops.rope import _block_rows

    assert _block_rows(16 * 128, 2) == 256 and _block_rows(8 * 128, 2) == 256
    assert _block_rows(48 * 128, 2) == 128 and _block_rows(72 * 128, 2) == 64
