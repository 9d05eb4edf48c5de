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
