"""Pallas flash attention vs the dense reference (interpreter mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.ops.attention import dense_attention
from kubeflow_tpu.ops.flash import flash_attention


def _qkv(key, b, s, h, d, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    shape = (b, s, h, d)
    return (
        jax.random.normal(kq, shape, dtype),
        jax.random.normal(kk, shape, dtype),
        jax.random.normal(kv, shape, dtype),
    )


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("s,block", [(128, 64), (256, 128), (96, 32)])
def test_forward_matches_dense(causal, s, block):
    q, k, v = _qkv(jax.random.PRNGKey(0), 2, s, 2, 32)
    out = flash_attention(
        q, k, v, causal=causal, block_q=block, block_k=block, interpret=True
    )
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_uneven_blocks():
    """block_q != block_k, including blocks that leave some rows fully
    masked inside an executed causal block."""
    q, k, v = _qkv(jax.random.PRNGKey(1), 1, 128, 1, 16)
    out = flash_attention(
        q, k, v, causal=True, block_q=64, block_k=32, interpret=True
    )
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    out = flash_attention(
        q, k, v, causal=True, block_q=32, block_k=64, interpret=True
    )
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_grads_match_dense(causal):
    q, k, v = _qkv(jax.random.PRNGKey(2), 1, 128, 2, 16)

    def loss_flash(q, k, v):
        o = flash_attention(
            q, k, v, causal=causal, block_q=64, block_k=64, interpret=True
        )
        return jnp.sum(o * jnp.cos(o))

    def loss_dense(q, k, v):
        o = dense_attention(q, k, v, causal=causal)
        return jnp.sum(o * jnp.cos(o))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        np.testing.assert_allclose(
            gf, gd, atol=5e-5, rtol=5e-5, err_msg=f"d{name} mismatch"
        )


def test_bf16_inputs():
    q, k, v = _qkv(jax.random.PRNGKey(3), 1, 128, 2, 32, jnp.bfloat16)
    out = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = dense_attention(q, k, v)
    np.testing.assert_allclose(
        out.astype(jnp.float32), ref.astype(jnp.float32), atol=3e-2, rtol=3e-2
    )


def test_indivisible_seq_pads_and_matches_dense():
    # A sequence with NO 8-aligned divisor (1025 = 5^2 * 41: every
    # divisor is odd) used to raise; it now pads internally to the next
    # lane multiple, masks the tail, and matches dense numerics.
    q, k, v = _qkv(jax.random.PRNGKey(4), 1, 1025, 1, 16)
    out = flash_attention(q, k, v, interpret=True)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-2, rtol=2e-2)


def test_ring_chunks_must_tile_without_padding():
    # `flash_attention` pads a length with no 8-aligned divisor; the ring
    # path cannot (chunks must stay congruent across hops), so it asks.
    from kubeflow_tpu.ops.flash import flash_kernel_tileable

    assert flash_kernel_tileable(256)
    assert flash_kernel_tileable(1344)
    assert not flash_kernel_tileable(100)
    assert not flash_kernel_tileable(1025)


def test_block_fallback_matches_dense():
    """A sequence the default block doesn't divide (1664 = 13 * 128)
    degrades to a dividing block and still matches dense numerics."""
    q, k, v = _qkv(jax.random.PRNGKey(5), 1, 1664, 1, 16)
    out = flash_attention(q, k, v, causal=True, block_q=1024, block_k=1024,
                          interpret=True)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        out.astype(np.float32), ref.astype(np.float32), atol=2e-2, rtol=2e-2
    )


# -- ring flash (sequence-parallel composition) ----------------------------


def _ring_mesh(sp):
    import numpy as np
    from jax.sharding import Mesh

    devs = np.array(jax.devices()[: sp * 2]).reshape(2, sp)
    return Mesh(devs, ("dp", "sp"))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("sp", [2, 4])
def test_ring_flash_forward_matches_dense(causal, sp):
    from kubeflow_tpu.ops.attention import dense_attention
    from kubeflow_tpu.ops.flash import ring_flash_attention

    mesh = _ring_mesh(sp)
    q, k, v = _qkv(jax.random.PRNGKey(0), b=2, s=8 * sp, h=2, d=128)
    out = ring_flash_attention(
        q, k, v, mesh, causal=causal, heads_axis=None, interpret=True
    )
    want = dense_attention(q, k, v, causal=causal)
    assert jnp.allclose(out, want, atol=2e-2), (
        float(jnp.abs(out - want).max())
    )


@pytest.mark.parametrize(
    "causal,s,block",
    [
        (True, 16, 1024),
        (False, 16, 1024),
        # Chunks of 512 in 256-blocks: the diagonal hop runs the compact
        # grid with its diagonal blocks cut in two bands and one block
        # below them; the full hop runs the rectangular grid.
        (True, 1024, 256),
    ],
)
def test_ring_flash_grads_match_dense(causal, s, block):
    from kubeflow_tpu.ops.attention import dense_attention
    from kubeflow_tpu.ops.flash import ring_flash_attention

    mesh = _ring_mesh(2)
    q, k, v = _qkv(jax.random.PRNGKey(1), b=2, s=s, h=2, d=128)

    def ring_loss(q, k, v):
        out = ring_flash_attention(
            q, k, v, mesh, causal=causal, heads_axis=None, interpret=True,
            block_q=block, block_k=block,
        )
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def dense_loss(q, k, v):
        return jnp.sum(
            dense_attention(q, k, v, causal=causal).astype(jnp.float32)
            ** 2
        )

    got = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        assert jnp.allclose(g, w, atol=5e-2), (
            name, float(jnp.abs(g - w).max())
        )


def test_ring_flash_trivial_ring_is_flash():
    from kubeflow_tpu.ops.flash import ring_flash_attention

    import numpy as np
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1), ("dp", "sp"))
    q, k, v = _qkv(jax.random.PRNGKey(2), b=2, s=16, h=2, d=128)
    out = ring_flash_attention(q, k, v, mesh, interpret=True)
    assert out.shape == q.shape


def test_ring_flash_rejects_indivisible_sequence():
    from kubeflow_tpu.ops.flash import ring_flash_attention

    mesh = _ring_mesh(4)
    q, k, v = _qkv(jax.random.PRNGKey(3), b=1, s=18, h=2, d=128)
    with pytest.raises(ValueError, match="divide"):
        ring_flash_attention(q, k, v, mesh, interpret=True)


# -- a window: the band in the compact grid (ISSUE 34) -----------------------


def _band(attn_kw, q, k, v):
    group = q.shape[2] // k.shape[2]
    rep = lambda x: jnp.repeat(x, group, axis=2)
    flash = lambda q, k, v: flash_attention(q, k, v, interpret=True, **attn_kw)
    dense = lambda q, k, v: dense_attention(
        q, rep(k), rep(v), window=attn_kw["window"]
    )
    weigh = lambda f: lambda *a: jnp.sum(f(*a) * jnp.cos(f(*a)))
    grads = lambda f: jax.grad(weigh(f), argnums=(0, 1, 2))(q, k, v)
    return flash(q, k, v), dense(q, k, v), grads(flash), grads(dense)


@pytest.mark.parametrize("group", [1, 6, 9])
@pytest.mark.parametrize("window, block", [
    (16, 16),   # a block
    (5, 16),    # under a block
    (24, 16),   # a block and a half
    (33, 16),   # two blocks and one key: three blocks back
    (1, 16),    # the query's own position alone
    (8, 64),    # one block holds the whole sequence: both edges in it
])
def test_band_kernels_match_the_dense_band_mask(window, block, group):
    """Forward and gradients of the band kernels (interpreter) against the
    dense band mask, at windows that are a block, under a block and not a
    multiple of one, at 1, 6 and 9 query heads a K/V head."""
    s, hk = 64, 1 if group > 1 else 2
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(kq, (1, s, hk * group, 16))
    k = jax.random.normal(kk, (1, s, hk, 16))
    v = jax.random.normal(kv, (1, s, hk, 16))
    out, ref, g_flash, g_dense = _band(
        dict(window=window, block_q=block, block_k=block), q, k, v
    )
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        np.testing.assert_allclose(
            gf, gd, atol=5e-5, rtol=5e-5, err_msg=f"d{name} mismatch"
        )


@pytest.mark.parametrize("s, window, blocks", [
    (67, 9, (16, 16)),     # a padded tail: the body that masks by position
    (128, 40, (64, 32)),   # uneven blocks: the rectangular grid
    (512, 200, (128, 128)),  # lane tiles: bands of whole lanes, three kinds
])
def test_band_off_the_static_path_matches_the_dense_band_mask(s, window, blocks):
    q, k, v = _qkv(jax.random.PRNGKey(8), 1, s, 2, 16)
    out, ref, g_flash, g_dense = _band(
        dict(window=window, block_q=blocks[0], block_k=blocks[1]), q, k, v
    )
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    for gf, gd in zip(g_flash, g_dense):
        np.testing.assert_allclose(gf, gd, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("window", [64, 65, 4096])
def test_a_window_that_reaches_every_key_is_the_causal_call_bit_for_bit(window):
    from kubeflow_tpu.testing.hlo import pallas_kernel_names

    q, k, v = _qkv(jax.random.PRNGKey(9), 1, 64, 2, 16)
    how = dict(block_q=16, block_k=16, interpret=True)
    loss = lambda **kw: lambda q, k, v: jnp.sum(
        jnp.sin(flash_attention(q, k, v, **how, **kw))
    )
    both = jax.value_and_grad(loss(window=window), argnums=(0, 1, 2))(q, k, v)
    causal = jax.value_and_grad(loss(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree_util.tree_leaves(both), jax.tree_util.tree_leaves(causal)):
        assert np.array_equal(a, b)
    # ... because it is the same program
    names = pallas_kernel_names(jax.grad(loss(window=window)), q, k, v)
    assert names == ["flash_fwd_compact", "flash_delta", "flash_bwd_fused"]
    names = pallas_kernel_names(jax.grad(loss(window=63)), q, k, v)
    assert names == ["flash_fwd_window", "flash_delta", "flash_bwd_window_fused"]


@pytest.mark.parametrize("kw, message", [
    (dict(window=0), "a window of 0 key"),
    (dict(window=8, causal=False), "looks back only"),
])
def test_a_window_that_is_none_is_refused(kw, message):
    q, k, v = _qkv(jax.random.PRNGKey(10), 1, 32, 1, 16)
    with pytest.raises(ValueError, match=message):
        flash_attention(q, k, v, interpret=True, **kw)


def test_attend_takes_a_window_on_both_paths_and_refuses_it_on_the_ring(devices):
    from kubeflow_tpu.ops.attention import attend
    from kubeflow_tpu.parallel import MeshSpec, build_mesh

    kq, kk = jax.random.split(jax.random.PRNGKey(11))
    q = jax.random.normal(kq, (2, 64, 6, 16))
    k = jax.random.normal(kk, (2, 64, 2, 16))
    dense = attend(q, k, k, mesh=None, impl="dense", window=12)
    ref = dense_attention(
        q, jnp.repeat(k, 3, axis=2), jnp.repeat(k, 3, axis=2), window=12
    )
    np.testing.assert_allclose(dense, ref, atol=1e-6)
    np.testing.assert_allclose(
        attend(q, k, k, mesh=None, impl="flash", window=12), ref,
        atol=2e-5, rtol=2e-5,
    )
    on_mesh = attend(
        q, k, k, mesh=build_mesh(MeshSpec(dp=2), devices[:2]), impl="flash",
        window=12,
    )
    np.testing.assert_allclose(on_mesh, ref, atol=2e-5, rtol=2e-5)
    # every key: the causal call
    np.testing.assert_allclose(
        attend(q, k, k, mesh=None, impl="dense", window=64),
        attend(q, k, k, mesh=None, impl="dense"), atol=0,
    )
    ring = build_mesh(MeshSpec(sp=2), devices[:2])
    with pytest.raises(ValueError, match="the ring path has no window"):
        attend(q, k, k, mesh=ring, impl="auto", window=12)
    with pytest.raises(ValueError, match="a window of 0 key"):
        attend(q, k, k, mesh=None, impl="dense", window=0)


# -- two-part scores (latent attention) ---------------------------------------


def _two_part(key, b, s, h, d, r, dtype=jnp.float32):
    kq, kr, rest = jax.random.split(key, 3)
    return (
        *_qkv(rest, b, s, h, d, dtype),
        jax.random.normal(kq, (b, s, h, r), dtype),
        jax.random.normal(kr, (b, s, r), dtype),
    )


@pytest.mark.parametrize(
    "s, h, d, r, block",
    [
        (256, 2, 128, 64, 128),  # the published widths: seq_major q, k, v
        (200, 2, 128, 64, 128),  # ragged: padded inside, the tail masked
        (64, 3, 16, 8, 32),      # narrow heads: the whole call head-major
        (128, 2, 128, 128, 128),  # a rope part of whole lanes: head-major too
    ],
)
def test_two_part_scores_match_dense_forward_and_all_five_gradients(
    s, h, d, r, block
):
    """s = q·kᵀ + q_rope·k_ropeᵀ with ONE rope key for all heads: the
    interpreted kernels against `dense_attention`, o and the gradients of
    q, k, v, q_rope and k_rope (summed over the heads), in each layout
    of the 128-wide operands, at a scale of the caller's."""
    q, k, v, q_rope, k_rope = _two_part(jax.random.PRNGKey(7), 2, s, h, d, r)
    scale = 1.3 * (d + r) ** -0.5
    kernels = lambda q, k, v, qr, kr: flash_attention(
        q, k, v, q_rope=qr, k_rope=kr, scale=scale, block_q=block,
        block_k=block, interpret=True,
    )
    dense = lambda q, k, v, qr, kr: dense_attention(
        q, k, v, q_rope=qr, k_rope=kr, scale=scale
    )
    args = (q, k, v, q_rope, k_rope)
    np.testing.assert_allclose(kernels(*args), dense(*args), atol=2e-5, rtol=2e-5)
    weigh = lambda f: lambda *a: jnp.sum(f(*a) * jnp.cos(f(*a)))
    got = jax.grad(weigh(kernels), argnums=range(5))(*args)
    want = jax.grad(weigh(dense), argnums=range(5))(*args)
    for g, w, name in zip(got, want, ("q", "k", "v", "q_rope", "k_rope")):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=1e-4, rtol=1e-4, err_msg=name)


def test_two_part_default_scale_is_the_joint_width():
    q, k, v, q_rope, k_rope = _two_part(jax.random.PRNGKey(8), 1, 64, 2, 16, 8)
    out = flash_attention(
        q, k, v, q_rope=q_rope, k_rope=k_rope, block_q=32, block_k=32,
        interpret=True,
    )
    joint = lambda a, b: jnp.concatenate(
        [a, jnp.broadcast_to(b.reshape(1, 64, -1, 8), (1, 64, 2, 8))], axis=-1
    )
    # the same attention with q and k of 24 = 16 + 8, the key's rope part
    # repeated a head, over v of 16
    ref = dense_attention(joint(q, q_rope), joint(k, k_rope), v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("how, message", [
    (dict(window=16), "under a window of 16"),
    (dict(k_rope=None), "two-part scores take q_rope"),
    (dict(kv_heads=1), "two-part scores take q_rope"),
])
def test_two_part_scores_refuse_what_was_not_built(how, message):
    q, k, v, q_rope, k_rope = _two_part(jax.random.PRNGKey(9), 1, 64, 2, 16, 8)
    if "kv_heads" in how:
        k, v = k[:, :, :1], v[:, :, :1]
    with pytest.raises(ValueError, match=message):
        flash_attention(
            q, k, v, q_rope=q_rope, k_rope=how.get("k_rope", k_rope),
            window=how.get("window"), interpret=True,
        )


def test_unequal_k_and_v_say_what_the_kernels_accept():
    q, k, v = _qkv(jax.random.PRNGKey(10), 1, 64, 2, 24)
    with pytest.raises(ValueError, match="go as two parts"):
        flash_attention(q, k, v[..., :16], interpret=True)
