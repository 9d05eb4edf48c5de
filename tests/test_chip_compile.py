"""Compile the main path's Pallas kernels for the chip, without the chip.

The TPU's compiler is installed here and compiles for a chip that is
described (`v5e:2x2`, device kind "TPU v5 lite") and not attached. That
shows what interpret mode cannot: a block the lowering refuses, a
kernel that wants more scoped VMEM than it may have. Nothing runs, so
nothing here is a result or a time — only "the chip's compiler accepts
this program" at the shapes `chip_smoke.py` and `bench.py` reach.

This is the only file that describes the chip. The topology is built
inside a module-scoped fixture, never at import, in a `skipif` or in a
`parametrize` argument: only one process may hold the TPU library, the
driver runs the suite with several workers, and each worker imports
every test file. No child process, and the persistent compile cache is
off around these compiles (an entry compiled for a described chip
cannot be read back without one).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from kubeflow_tpu.ops.flash import (
    flash_attention,
    flash_schedule,
    ring_flash_attention,
)
from kubeflow_tpu.testing.hlo import (
    collective_counts,
    pallas_kernel_names,
    tpu_kernel_calls,
)

HEAD_DIM = 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()
    if prev_log is None:
        os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _qkv(b, h, s, sharding):
    x = jax.ShapeDtypeStruct((b, s, h, HEAD_DIM), jnp.bfloat16,
                             sharding=sharding)
    return x, x, x


def _loss(q, k, v, **kw):
    return flash_attention(q, k, v, interpret=False, **kw).astype(
        jnp.float32
    ).sum()


def _compile(fn, *args):
    """(compiled text, traced kernel names); raises what the chip's
    compiler would raise."""
    names = pallas_kernel_names(fn, *args)
    return jax.jit(fn).lower(*args).compile().as_text(), names


# (B, H, S): the flagship shapes of bench_lm / chip_smoke.py, then 32k on
# the far side of the fused/two-pass boundary.
SHAPES = [(8, 8, 2048), (2, 8, 8192), (2, 8, 16384), (2, 8, 32768)]


@pytest.mark.parametrize("b,h,s", SHAPES[:3])
def test_flash_forward_compiles(one_chip, b, h, s):
    text, names = _compile(
        lambda q, k, v: flash_attention(q, k, v, interpret=False),
        *_qkv(b, h, s, one_chip),
    )
    assert names == ["flash_fwd_compact"]
    assert tpu_kernel_calls(text) == 1


@pytest.mark.parametrize("b,h,s", SHAPES)
def test_flash_forward_backward_compiles_on_the_reported_schedule(
    one_chip, b, h, s
):
    """fwd+bwd compiles at each shape, and `flash_schedule`'s `bwd_fused`
    is the kernel actually traced: fused through 16k (where its VMEM is
    past the compiler's 16 MiB default and the call names its own
    limit), two-pass at 32k."""
    text, names = _compile(
        jax.grad(_loss, argnums=(0, 1, 2)), *_qkv(b, h, s, one_chip)
    )
    sched = flash_schedule(s, s, head_dim=HEAD_DIM, dtype_bytes=2)
    assert sched["bwd_fused"] == (s <= 16384)
    want = (
        ["flash_bwd_fused"] if sched["bwd_fused"]
        else ["flash_dq_compact", "flash_dkv_compact"]
    )
    assert names == ["flash_fwd_compact", "flash_delta", *want]
    assert tpu_kernel_calls(text) == len(names)


def test_sub_1024_blocks_select_the_replicated_lse_and_compile(one_chip):
    """A packed lse block below 1024 rows is (1, bq/128 < 8, 128): the
    lowering refuses it, so such sizes must select the replicated
    layout — and then compile."""
    sched = flash_schedule(4096, 4096, block_q=512, block_k=512)
    assert not sched["lse_packed"] and sched["bwd_fused"]
    text, names = _compile(
        jax.grad(
            lambda q, k, v: _loss(q, k, v, block_q=512, block_k=512),
            argnums=(0, 1, 2),
        ),
        *_qkv(2, 8, 4096, one_chip),
    )
    assert "flash_bwd_fused" in names
    assert tpu_kernel_calls(text) == len(names)


@pytest.mark.parametrize(
    "s,padded,block,packed",
    [
        (2000, 2000, 1000, False),  # tiles by 1000-row blocks, unpadded
        (2001, 2048, 1024, True),  # no aligned divisor: pads, masks the tail
    ],
)
def test_ragged_sequences_compile(one_chip, s, padded, block, packed):
    sched = flash_schedule(s, s)
    assert (sched["padded_seq_q"], sched["block_q"], sched["lse_packed"]) == (
        padded, block, packed
    )
    text, names = _compile(
        jax.grad(_loss, argnums=(0, 1, 2)), *_qkv(2, 8, s, one_chip)
    )
    assert names == ["flash_fwd_compact", "flash_delta", "flash_bwd_fused"]
    assert tpu_kernel_calls(text) == 3


def test_ring_flash_on_four_chips_moves_kv_by_permutes_only(topo):
    """`ring_flash_attention` over a four-device `sp` mesh at S=8192
    (chunk 2048), forward and gradient: the partitioned program holds
    the kernels and collective-permutes, and never gathers the
    sequence."""
    from kubeflow_tpu.parallel import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(sp=4), list(topo.devices))
    sharding = NamedSharding(mesh, P(None, "sp", None, None))
    q, k, v = _qkv(1, 8, 8192, sharding)

    def ring(q, k, v):
        return ring_flash_attention(q, k, v, mesh, interpret=False)

    for fn in (
        ring,
        jax.grad(
            lambda q, k, v: ring(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        ),
    ):
        text, names = _compile(fn, q, k, v)
        counts = collective_counts(text)
        assert counts["collective-permute"] > 0, counts
        assert counts["all-gather"] == 0, counts
        assert names and tpu_kernel_calls(text) > 0
