"""Named-axis collective wrappers on a virtual multi-device CPU mesh."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from kubeflow_tpu.parallel import collectives as col


def _smap(mesh, fn, in_specs, out_specs):
    return jax.jit(
        shard_map(
            fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
        )
    )


def test_psum_across_dp(mesh8):
    x = np.ones((8, 4), np.float32)

    def f(xs):
        return col.psum(xs, ("dp", "fsdp"))

    y = _smap(mesh8, f, P(("dp", "fsdp"), None), P(("dp", "fsdp"), None))(x)
    np.testing.assert_allclose(np.asarray(y), 4.0 * x)


def test_all_gather_tiled(mesh8):
    x = np.arange(8, dtype=np.float32).reshape(8, 1)

    def f(xs):
        return col.all_gather(xs, "dp")

    # Shards of 4 rows (dp=2) -> gathered back to 8 rows on each shard.
    y = _smap(mesh8, f, P("dp", None), P(None, None))(x)
    np.testing.assert_allclose(np.asarray(y), x)


def test_reduce_scatter_roundtrip(mesh8):
    # On replicated input: reduce_scatter sums the tp copies and scatters
    # rows; all_gather reassembles — the FSDP gradient path in miniature.
    x = np.random.default_rng(0).normal(size=(8, 8)).astype(np.float32)

    def f(xs):
        rs = col.reduce_scatter(xs, "tp", scatter_axis=0)
        assert rs.shape == (4, 8)
        return col.all_gather(rs, "tp")

    y = _smap(mesh8, f, P(None, None), P(None, None))(x)
    np.testing.assert_allclose(np.asarray(y), 2.0 * x, rtol=1e-6)


def test_ppermute_ring_shift(mesh8):
    # Each tp shard emits its own index; after shift=1 each holds its left
    # neighbor's index (the input array is only a shape carrier).
    def f(_):
        idx = col.axis_index("tp").astype(jnp.float32).reshape(1)
        return col.ppermute_ring(idx, "tp", shift=1)

    y = _smap(mesh8, f, P("tp"), P("tp"))(np.zeros(2, np.float32))
    # tp has 2 shards: shard 0 receives from ... perm sends i -> i+1;
    # so shard 1 gets value 0, shard 0 gets value 1.
    np.testing.assert_allclose(np.asarray(y), [1.0, 0.0])


def test_all_to_all(mesh8):
    # 2 tp shards, each with (2, 2) -> exchange halves.
    x = np.arange(16, dtype=np.float32).reshape(4, 4)

    def f(xs):
        return col.all_to_all(xs, "tp", split_axis=1, concat_axis=0)

    y = _smap(mesh8, f, P("tp", None), P(None, "tp"))(x)
    assert np.asarray(y).shape == (4, 4)
    # Round-trip restores the original.
    def g(xs):
        z = col.all_to_all(xs, "tp", split_axis=1, concat_axis=0)
        return col.all_to_all(z, "tp", split_axis=0, concat_axis=1)

    y2 = _smap(mesh8, g, P("tp", None), P("tp", None))(x)
    np.testing.assert_allclose(np.asarray(y2), x)
