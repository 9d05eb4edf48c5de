"""Named-axis collective wrappers on a virtual multi-device CPU mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from kubeflow_tpu.parallel import collectives as col
from kubeflow_tpu.testing.hlo import async_collective_counts


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


# The forms an asynchronous collective takes in compiled HLO text, cut
# down from real programs: the start/done pair (CPU, GPU, the TPU
# compiler before its last passes), and the TPU compiler's final text,
# where one all-reduce sits in an `async_collective_fusion` beside a
# matmul and another keeps its opcode and names the start it was given.
_HLO_START_DONE = """
HloModule jit_f, is_scheduled=true

ENTRY %main.4 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %all-reduce-start = f32[8]{0} all-reduce-start(%x), channel_id=1, replica_groups={{0,1}}, to_apply=%add
  %mul = f32[8]{0} multiply(%x, %x)
  %all-reduce-done = f32[8]{0} all-reduce-done(%all-reduce-start)
  ROOT %out = f32[8]{0} add(%all-reduce-done, %mul)
}
"""

_HLO_TPU_FUSED = """
HloModule jit_train_step, is_scheduled=true

%async_collective_fusion.3 (p0: bf16[4,8], p1: bf16[4,8]) -> (bf16[8,8], bf16[4,8]) {
  %p0 = bf16[4,8]{1,0} parameter(0)
  %p1 = bf16[4,8]{1,0} parameter(1)
  %all-reduce.7 = bf16[4,8]{1,0} all-reduce(%p0), channel_id=3, replica_groups=[2,2]<=[4], to_apply=%add, frontend_attributes={chain_id="0"}
  %conv = bf16[8,8]{1,0} convolution(%p1, %p1), dim_labels=bf_io->bf
  ROOT %t = (bf16[8,8]{1,0}, bf16[4,8]{1,0}) tuple(%conv, %all-reduce.7)
}

ENTRY %main.9 (a: bf16[4,8], b: bf16[4,8]) -> bf16[4,8] {
  %a = bf16[4,8]{1,0} parameter(0)
  %b = bf16[4,8]{1,0} parameter(1)
  %fusion.3 = (bf16[8,8]{1,0}, bf16[4,8]{1,0}) fusion(%a, %b), kind=kOutput, calls=%async_collective_fusion.3
  %gte = bf16[4,8]{1,0} get-tuple-element(%fusion.3), index=1
  ROOT %all-reduce.8 = bf16[4,8]{1,0} all-reduce(%gte), channel_id=4, replica_groups={{0,2},{1,3}}, to_apply=%add, frontend_attributes={async_collective_name="all-reduce-start.1"}
}
"""

_HLO_SYNCHRONOUS = """
ENTRY %main.3 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  ROOT %all-reduce = f32[8]{0} all-reduce(%x), channel_id=1, replica_groups={{0,1}}, to_apply=%add
}
"""


@pytest.mark.parametrize(
    "hlo,want",
    [
        (_HLO_START_DONE, {"start": 1, "fusion": 0, "tagged": 0}),
        (_HLO_TPU_FUSED, {"start": 0, "fusion": 1, "tagged": 1}),
        (_HLO_SYNCHRONOUS, {"start": 0, "fusion": 0, "tagged": 0}),
    ],
    ids=["start-done", "tpu-fused", "synchronous"],
)
def test_async_collective_counts_by_form(hlo, want):
    assert async_collective_counts(hlo) == want
