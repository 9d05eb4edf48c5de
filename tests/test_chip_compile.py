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
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from kubeflow_tpu.ops.flash import (
    _FUSED_VMEM_BUDGET,
    flash_attention,
    flash_schedule,
    ring_flash_attention,
)
from kubeflow_tpu.parallel import step_compiler_options
from kubeflow_tpu.testing.hlo import (
    async_collective_counts,
    collective_counts,
    compiled_hlo,
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
    # Both bodies of a step are in what compiles: banded on the s/1024
    # diagonal steps, mask-free on the rest (ISSUE 29); the footprint
    # model that admits the fused kernel is still an upper bound.
    assert sched["diag_steps"] == s // 1024 and sched["diag_tile"] == 512
    assert sched["interior_steps"] == sched["grid_steps"] - s // 1024
    assert (sched["bwd_fused_vmem_bytes"] <= _FUSED_VMEM_BUDGET) == (s <= 16384)
    want = (
        ["flash_bwd_fused"] if sched["bwd_fused"]
        else ["flash_dq_compact", "flash_dkv_compact"]
    )
    assert names == ["flash_fwd_compact", "flash_delta", *want]
    assert tpu_kernel_calls(text) == len(names)


@pytest.mark.parametrize("causal, grid", [(True, ""), (False, "_rect")])
def test_two_part_two_pass_kernels_compile_past_the_fused_boundary(
    one_chip, causal, grid
):
    """Latent attention's widths (128 + 64 over 128, 32 heads, one rope
    key) at S = 16,384, where the second dq ring no longer fits the fused
    backward's VMEM: the chip's compiler takes the two-pass kernels with
    their 64-lane head-major rope blocks, on the compact grid and on the
    rectangular one."""
    b, h, s, r = 1, 32, 16384, 64
    q, k, v = _qkv(b, h, s, one_chip)
    q_rope = jax.ShapeDtypeStruct((b, s, h, r), jnp.bfloat16, sharding=one_chip)
    k_rope = jax.ShapeDtypeStruct((b, s, r), jnp.bfloat16, sharding=one_chip)
    loss = lambda q, k, v, q_rope, k_rope: _loss(
        q, k, v, q_rope=q_rope, k_rope=k_rope, causal=causal
    )
    text, names = _compile(
        jax.grad(loss, argnums=(0, 1, 2, 3, 4)), q, k, v, q_rope, k_rope
    )
    sched = flash_schedule(s, s, head_dim=HEAD_DIM, rope_dim=r, dtype_bytes=2)
    assert not sched["bwd_fused"]
    assert names == [
        f"flash_fwd_mla{grid}", "flash_delta", f"flash_dq_mla{grid}",
        f"flash_dkv_mla{grid}",
    ]
    assert tpu_kernel_calls(text) == len(names)


def test_grouped_head_flash_compiles_at_the_zaya_cell_shape(one_chip):
    """8 query heads over 2 K/V heads at S = 8192 (`zaya1-8b-ep2.train-8k`):
    the kv head is picked in the index maps, the backward stays fused."""
    q = jax.ShapeDtypeStruct((2, 8192, 8, HEAD_DIM), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 8192, 2, HEAD_DIM), jnp.bfloat16,
                              sharding=one_chip)
    text, names = _compile(jax.grad(_loss, argnums=(0, 1, 2)), q, kv, kv)
    assert names == ["flash_fwd_compact", "flash_delta", "flash_bwd_fused"]
    assert tpu_kernel_calls(text) == len(names)


def test_heads_of_256_compile_at_the_qwen3_next_cell_shape(one_chip):
    """16 query heads of 256 over 2 K/V heads at S = 8192, batch 2
    (`qwen3-next-80b-a3b-ep16.train-8k`'s one attention layer): twice the
    width every other cell has, eight query heads a K/V head; the schedule
    keeps blocks of 1,024 and the fused backward inside its VMEM budget,
    and the chip's compiler takes both."""
    sched = flash_schedule(8192, 8192, head_dim=256, dtype_bytes=2)
    assert sched["layout"] == "seq_major" and sched["bwd_fused"]
    assert (sched["block_q"], sched["block_k"]) == (1024, 1024)
    assert sched["bwd_fused_vmem_bytes"] <= _FUSED_VMEM_BUDGET
    q = jax.ShapeDtypeStruct((2, 8192, 16, 256), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 8192, 2, 256), jnp.bfloat16, sharding=one_chip)
    text, names = _compile(jax.grad(_loss, argnums=(0, 1, 2)), q, kv, kv)
    assert names == ["flash_fwd_compact", "flash_delta", "flash_bwd_fused"]
    assert tpu_kernel_calls(text) == len(names)


def test_twenty_equal_heads_of_256_compile_at_the_glm_cell_shape(one_chip):
    """20 query heads of 256 over 20 K/V heads at S = 8192, batch 1
    (`glm-4.7-flash-ep8.train-8k`: a head's 64 rope and 192 own lanes side
    by side over values of 256, the one-part calls): equal heads at that
    width ran in no cell before; the fused backward's VMEM is the grouped
    call's, and the chip's compiler takes both."""
    sched = flash_schedule(8192, 8192, head_dim=256, dtype_bytes=2)
    assert sched["layout"] == "seq_major" and sched["bwd_fused"]
    assert sched["bwd_fused_vmem_bytes"] <= _FUSED_VMEM_BUDGET
    q = jax.ShapeDtypeStruct((1, 8192, 20, 256), jnp.bfloat16, sharding=one_chip)
    text, names = _compile(jax.grad(_loss, argnums=(0, 1, 2)), q, q, q)
    assert names == ["flash_fwd_compact", "flash_delta", "flash_bwd_fused"]
    assert tpu_kernel_calls(text) == len(names)


# (B, S, H, Hkv) of a layer's attention in each cell: `olmo-1b-cut.train-2k`,
# `-8k`, `zaya1-8b-ep2.train-8k`, a shard of `olmo-1b.train-2k-dp2tp2`, and
# `nemotron-3-super-tp2ep64.train-8k`'s 16 query heads over one K/V head.
CELL_SHAPES = [
    (8, 2048, 16, 16), (2, 8192, 16, 16), (2, 8192, 8, 2), (4, 2048, 8, 8),
    (1, 8192, 16, 1),
]


@pytest.mark.parametrize("b,s,h,hkv", CELL_SHAPES)
def test_seq_major_kernels_compile_at_the_cells_shapes(one_chip, b, s, h, hkv):
    """At the cells' head size the kernels read [B, S, H·128] (ISSUE 31):
    a head is a column block, fetched by a strided DMA, and the chip's
    compiler takes every such block spec, forward and fused backward, on
    the schedule `flash_schedule` reports."""
    sched = flash_schedule(s, s, head_dim=HEAD_DIM, dtype_bytes=2)
    assert sched["layout"] == "seq_major"
    assert sched["transposes_per_call"] == 0 and sched["bwd_fused"]
    q = jax.ShapeDtypeStruct((b, s, h, HEAD_DIM), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, s, hkv, HEAD_DIM), jnp.bfloat16,
                              sharding=one_chip)
    text, names = _compile(jax.grad(_loss, argnums=(0, 1, 2)), q, kv, kv)
    assert names == ["flash_fwd_compact", "flash_delta", "flash_bwd_fused"]
    assert tpu_kernel_calls(text) == len(names)


@pytest.mark.parametrize(
    "b,s,width,fraction",
    [(8, 2048, 2048, 1.0), (2, 8192, 2048, 1.0), (2, 8192, 1024, 0.5),
     (2, 8192, 256, 0.5), (4, 2048, 1024, 1.0)],
)
def test_rope_kernel_compiles_at_the_cells_shapes(
    one_chip, b, s, width, fraction
):
    """q and k of each cell (zaya's turn half of a head; its K is two
    heads wide), forward and the VJP's turn by the negated angle."""
    from kubeflow_tpu.ops.rope import rope

    x = jax.ShapeDtypeStruct((b, s, width), jnp.bfloat16, sharding=one_chip)
    positions = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=one_chip)

    def loss(x, positions):
        y = rope(
            x, positions, 10000.0, fraction, head_dim=HEAD_DIM,
            interpret=False,
        ).astype(jnp.float32)
        return (y * y).sum()

    text, names = _compile(jax.grad(loss), x, positions)
    assert names == ["rope_turn_fwd", "rope_turn_bwd"]
    assert tpu_kernel_calls(text) == 2


def test_a_sublayers_stream_passes_compile_at_the_xing_cell_shape(one_chip):
    """One round of four streams of 3,584 at 8,192 tokens, forward and
    backward: `hc_pre_fwd`, `hc_post_fwd`, `hc_post_bwd` and `hc_pre_bwd`
    over blocks of 128 whole rows of 14,336 (3.7 MB each: the calls state
    a VMEM limit above the compiler's 16 MiB) with the maps' blocks
    [128, 128] turned in VMEM, every call under an
    `hc.*` scope in the forward AND in the hand-written backward, which is
    what `hc_time_pct.train` reads. Two Sinkhorn iterations: the stack of
    20 is XLA's and half of a step's compile (PERF.md §7)."""
    from kubeflow_tpu.ops import streams

    n, d, s = 4, 3584, 8192
    spec = streams.Maps(n, d, 2, 30.0, 1e-6, 1e-6)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip
    )

    def loss(x, phi, a, bias):
        h, x, ho, hr = streams.mix_in(x, phi, a, bias, spec, interpret=False)
        mixed = streams.mix_out(x, h, hr, ho, spec, interpret=False)
        return (mixed.astype(jnp.float32) ** 2).sum()

    text, names = _compile(
        jax.grad(loss, argnums=(0, 1, 2, 3)),
        sds((1, s, n * d), jnp.bfloat16), sds((n * d, spec.maps), jnp.float32),
        sds((3,), jnp.float32), sds((spec.maps,), jnp.float32),
    )
    assert names == ["hc_pre_fwd", "hc_post_fwd", "hc_post_bwd", "hc_pre_bwd"]
    assert tpu_kernel_calls(text) == len(names)
    for name in names:
        scope = "hc.post" if "post" in name else "hc.pre"
        phase = "jvp" if name.endswith("fwd") else "transpose"
        assert re.search(
            rf'tpu_custom_call[^\n]*op_name="[^"]*{phase}\([^"]*{re.escape(scope)}'
            rf'[^"]*/{name}', text
        ), name


def test_grouped_matmul_kernels_compile_at_the_zaya_cell_shape(
    one_chip, monkeypatch
):
    """The expert layer's three kernels at the cell's size: 16,384 tokens
    over 8 held experts of 2048 x 2048, float32 weights, bfloat16 rows."""
    from kubeflow_tpu.ops import moe

    monkeypatch.setattr(moe, "kernels_compiled", lambda: True)
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip
    )
    weight = shape((8, 2048, 2048), jnp.float32)

    def loss(x, gate, w_gate, w_up, w_down, expert):
        out = moe.expert_mlp(x, expert, gate, (w_gate, w_up, w_down), 0)
        return out.astype(jnp.float32).sum()

    text, names = _compile(
        jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
        shape((16384, 2048), jnp.bfloat16), shape((16384,), jnp.float32),
        weight, weight, weight, shape((16384,), jnp.int32),
    )
    # one expert a token: the rows move by XLA's gathers
    assert sorted(set(names)) == ["moe_gmm_dlhs", "moe_gmm_dw", "moe_gmm_fwd"]
    assert len(names) == 9 == tpu_kernel_calls(text)


@pytest.mark.parametrize("call", ["forward", "dlhs"])
@pytest.mark.parametrize("cell, held, d, f, tiles, by_pairs", [
    ("zaya", 8, 2048, 2048, 72, False),  # 16 MiB a block and 8 rounded
    ("qwen3-next", 32, 2048, 512, 672, True),
    ("xing", 8, 3584, 1024, 264, True),  # 14 MiB a block beside a packed result
])
def test_grouped_matmuls_compile_with_float32_weights_at_the_cells_shapes(
    one_chip, cell, held, d, f, tiles, by_pairs, call
):
    """`moe_gmm_fwd` with the gated activation and `moe_gmm_dlhs` with
    the slope formed on the way in, added onto the call before (packed
    at more than one expert a token), bfloat16 rows over float32 weights
    as they lie: a block is fetched at 4 bytes, an expert ahead, by the
    kernel's own DMA, rounded into a scratch of the rows' dtype, and the
    VMEM limit follows what that adds."""
    from kubeflow_tpu.ops import moe

    assert moe._gmm_tiles(d, f) == (d, f) and moe._VMEM_LIMIT == 48 * 2 ** 20
    rows = tiles * moe.BLOCK_ROWS
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip
    )
    how = dict(block_rows=moe.BLOCK_ROWS, interpret=False)
    wide, narrow = shape((rows, d), jnp.bfloat16), shape((rows, f), jnp.bfloat16)
    table = (shape((tiles,), jnp.int32), shape((1,), jnp.int32))
    if call == "forward":
        fn = lambda x, w, te, nt, gate: moe._gmm(
            x, w, te, nt, gate=gate, act=True, **how
        )
        args = (wide, shape((held, d, f), jnp.float32), *table, narrow)
    else:
        packed = shape(moe._Packed.of(d).shape(rows), jnp.float32)
        fn = lambda g, w, te, nt, a, b, onto: moe._gmm(
            g, w, te, nt, (a, b), onto=onto, wrt=1, transpose_rhs=True,
            packed=by_pairs, **how,
        )
        args = (
            narrow, shape((held, d, f), jnp.float32), *table, narrow, narrow,
            packed if by_pairs else wide,
        )
    text, names = _compile(fn, *args)
    assert names == ["moe_gmm_fwd" if call == "forward" else "moe_gmm_dlhs"]
    assert tpu_kernel_calls(text) == 1
    assert " convert(" not in text.split("ENTRY")[1]


def test_latent_top_k_expert_kernels_compile_at_the_nemotron_cell_shape(
    one_chip, monkeypatch
):
    """The latent expert layer's kernels at `nemotron-3-super-tp2ep64
    .train-8k`'s size: 8,192 tokens with 22 experts each of 512, 8 held,
    two matrices an expert of 1024 x 2688 (21 lane columns: tiles of 896,
    `moe._tile`), float32 weights, bfloat16 rows."""
    from kubeflow_tpu.ops import moe

    monkeypatch.setattr(moe, "kernels_compiled", lambda: True)
    # one weight block an expert either way round; zaya's choice stands
    assert moe._gmm_tiles(1024, 2688) == (1024, 2688)
    assert moe._gmm_tiles(2688, 1024, packed=True) == (2688, 1024)
    assert moe._gmm_tiles(2048, 2048) == (2048, 2048)
    assert moe._tile(2688, 2048) == 896
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip
    )

    def loss(x, gate, w_in, w_down, expert):
        out = moe.expert_mlp(x, expert, gate, (w_in, w_down), 0)
        return out.astype(jnp.float32).sum()

    text, names = _compile(
        jax.grad(loss, argnums=(0, 1, 2, 3)),
        shape((8192, 1024), jnp.bfloat16), shape((8192, 22), jnp.float32),
        shape((8, 1024, 2688), jnp.float32), shape((8, 2688, 1024), jnp.float32),
        shape((8192, 22), jnp.int32),
    )
    # 22 experts a token: the rows move by the two `moe_rows_*` kernels,
    # each in both directions (the forward's combine is not in a gradient
    # of a sum, so one `moe_rows_sum` of the two is traced and dropped);
    # `moe_plan_rows` gives the rows their tokens and, backward,
    # `moe_plan_weights` their weights and `moe_plan_tokens` the tokens
    # their rows' products: the plan scatters and gathers nothing
    assert sorted(set(names)) == [
        "moe_gmm_dlhs", "moe_gmm_dw", "moe_gmm_fwd", "moe_plan_rows",
        "moe_plan_tokens", "moe_plan_weights", "moe_rows_sum", "moe_rows_take",
    ]
    assert [names.count(n) for n in sorted(set(names))] == [2, 2, 2, 1, 1, 1, 2, 2]
    assert tpu_kernel_calls(text) == 12
    assert " scatter(" not in text


@pytest.mark.parametrize("h, window, fwd, bwd", [
    (72, 512, "flash_fwd_window", "flash_bwd_window_fused"),
    (48, None, "flash_fwd_compact", "flash_bwd_fused"),
])
def test_band_and_global_kernels_compile_at_the_laguna_cell_shapes(
    one_chip, h, window, fwd, bwd
):
    """`laguna-s-2.1-ep32.train-8k`'s two attention calls: one 8k
    sequence, 72 query heads over 8 K/V heads under a window of 512 (a
    group of 9: the band's tables, its three kinds of step, a dq ring of
    two blocks) and 48 over 8 over the triangle (a group of 6)."""
    q = jax.ShapeDtypeStruct((1, 8192, h, HEAD_DIM), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8192, 8, HEAD_DIM), jnp.bfloat16, sharding=one_chip)
    text, names = _compile(
        jax.grad(lambda q, k, v: _loss(q, k, v, window=window), argnums=(0, 1, 2)),
        q, kv, kv,
    )
    assert names == [fwd, "flash_delta", bwd]
    assert tpu_kernel_calls(text) == 3
    sched = flash_schedule(8192, 8192, head_dim=HEAD_DIM, window=window)
    assert sched["bwd_fused"] and sched["lse_packed"]
    assert sched["grid_steps"] == (15 if window else 36)


def test_gated_top_10_expert_kernels_compile_at_the_laguna_cell_shape(
    one_chip, monkeypatch
):
    """The expert layer's kernels at `laguna-s-2.1-ep32.train-8k`'s size:
    8,192 tokens with 10 experts each of 256, 8 held, three matrices an
    expert of 3072 x 1024, float32 weights, bfloat16 rows: the gated
    expert (zaya's) with the rows' movers (nemotron's), packed rows of 24
    x 128 lanes."""
    from kubeflow_tpu.ops import moe

    monkeypatch.setattr(moe, "kernels_compiled", lambda: True)
    # the accepted cells' tiles stand
    assert moe._gmm_tiles(3072, 1024) == (3072, 1024)
    assert moe._gmm_tiles(1024, 3072, packed=True) == (1024, 3072)
    assert moe._gmm_tiles(1024, 2688) == (1024, 2688)
    assert moe._gmm_tiles(2048, 2048) == (2048, 2048)
    assert moe._Packed.of(3072) == (128, 24, 24)
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip
    )
    into = shape((8, 3072, 1024), jnp.float32)

    def loss(x, gate, w_gate, w_up, w_down, expert):
        out = moe.expert_mlp(x, expert, gate, (w_gate, w_up, w_down), 0)
        return out.astype(jnp.float32).sum()

    text, names = _compile(
        jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
        shape((8192, 3072), jnp.bfloat16), shape((8192, 10), jnp.float32),
        into, into, shape((8, 1024, 3072), jnp.float32),
        shape((8192, 10), jnp.int32),
    )
    assert sorted(set(names)) == [
        "moe_gmm_dlhs", "moe_gmm_dw", "moe_gmm_fwd", "moe_plan_rows",
        "moe_plan_tokens", "moe_plan_weights", "moe_rows_sum", "moe_rows_take",
    ]
    # three matmuls forward and three of each kind back; the rows taken
    # once forward and once back, and summed once forward (traced, and
    # dropped from a gradient of a sum) and once back: the second
    # matrix's `dlhs` adds onto the first's result; the rows' tokens
    # counted forward, their weights and the tokens' products backward
    assert [names.count(n) for n in sorted(set(names))] == [3, 3, 3, 1, 1, 1, 2, 2]
    assert tpu_kernel_calls(text) == 15
    assert " scatter(" not in text


@pytest.mark.parametrize("tokens,k,held", [
    (16384, 10, 32),  # qwen3-next-80b-a3b-ep16.train-8k's: 672 row tiles
    (8192, 22, 8),    # nemotron-3-super-tp2ep64.train-8k's
])
def test_the_plan_compiles_at_the_cells_shapes_and_scatters_nothing(
    one_chip, monkeypatch, tokens, k, held
):
    """`plan_dispatch`, the slots' weights and the backward's rows'
    weights at the widest cell's size and at the four cells' of 8 held: a
    running count a held expert in VMEM a tile in use, one call each of
    `moe_plan_rows`, `moe_plan_weights` and `moe_plan_tokens`, and no
    `scatter`, no `gather`, no `sort` and no `while` in the chip's program (the parent's held one of `n_held x N` single elements and a
    `searchsorted` loop)."""
    from kubeflow_tpu.ops import moe

    monkeypatch.setattr(moe, "kernels_compiled", lambda: True)
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip
    )

    def plan(expert, gate):
        plan = moe.plan_dispatch(expert, 0, held)
        by_row = moe.rows_values(plan, moe.held_weights(gate, plan))
        back = moe.tokens_values(plan, by_row)
        return plan, moe.slot_weights(gate, plan), by_row, back

    text, names = _compile(
        plan, shape((tokens, k), jnp.int32), shape((tokens, k), jnp.float32)
    )
    assert names == ["moe_plan_rows", "moe_plan_weights", "moe_plan_tokens"]
    assert tpu_kernel_calls(text) == 3 and " gather(" not in text
    assert " scatter(" not in text and " while(" not in text and " sort(" not in text
    assert moe.moe_schedule(tokens, k, held, 1024, 1024)["plan_updates"] == 0


@pytest.mark.parametrize("tokens,k,held,d", [
    (8192, 22, 8, 1024),  # nemotron-3-super-tp2ep64.train-8k's
    (16384, 1, 8, 2048),  # zaya1-8b-ep2.train-8k's, which runs the gathers
])
def test_row_movers_compile_at_the_cells_shapes(one_chip, tokens, k, held, d):
    """`moe_rows_take` (alone, and with the weights' gradient) and
    `moe_rows_sum` (weighted and not) at the two expert cells' sizes: one
    DMA a packed row, indices a tile at a time in SMEM."""
    from kubeflow_tpu.ops import moe

    shape = lambda dims, dtype: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip
    )
    slots = min(k, held)
    rows = moe.BLOCK_ROWS * moe.row_tiles(tokens, k, held)
    pack = lambda m: shape(moe._Packed.of(d).shape(m), jnp.float32)
    how = dict(width=d, out_dtype=jnp.bfloat16, interpret=False)

    def movers(x, buffer, row_token, n_tiles, scale, token_rows, count, weight):
        take = lambda *more: moe._rows_take(
            x, row_token, n_tiles, *more, block_rows=moe.BLOCK_ROWS, **how
        )
        add = lambda *more: moe._rows_sum(buffer, token_rows, count, *more, **how)
        return take(), take(scale, buffer), add(), add(weight)

    text, names = _compile(
        movers, pack(tokens), pack(rows), shape((rows,), jnp.int32),
        shape((1,), jnp.int32), shape((rows,), jnp.float32),
        shape((slots, tokens), jnp.int32), shape((tokens,), jnp.int32),
        shape((slots, tokens), jnp.float32),
    )
    assert names == ["moe_rows_take"] * 2 + ["moe_rows_sum"] * 2
    assert tpu_kernel_calls(text) == 4


def test_scan_kernels_compile_at_the_nemotron_cell_shape(one_chip):
    """The chunked state-space scan, forward and backward, at the cell's
    size: one sequence of 8,192, 64 heads of 64 in 4 groups, a state of
    128, chunks of 128 (`ops/ssd.py`)."""
    from kubeflow_tpu.ops import ssd

    shape = lambda dims, dtype: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip
    )
    x = shape((1, 8192, 64 * 64), jnp.bfloat16)
    bc = shape((1, 8192, 4 * 128), jnp.bfloat16)

    def loss(x, dt, a, b, c):
        y = ssd.ssd_scan(x, dt, a, b, c, groups=4, chunk=128, interpret=False)
        return y.astype(jnp.float32).sum()

    text, names = _compile(
        jax.grad(loss, argnums=(0, 1, 2, 3, 4)), x,
        shape((1, 8192, 64), jnp.float32), shape((64,), jnp.float32), bc, bc,
    )
    assert names == ["ssd_fwd", "ssd_bwd"]
    assert tpu_kernel_calls(text) == 2


def test_delta_rule_kernels_compile_at_the_kimi_cell_shape(one_chip):
    """The chunked gated delta rule, forward and backward, at the cell's
    size: one sequence of 8,192, 32 heads of 128, chunks of 64, 8 heads a
    program (`ops/kda.py`): b's blocks [C, heads] and [heads, C] are whole
    arrays' last two dimensions, and both kernels fit the scoped VMEM."""
    from kubeflow_tpu.ops import kda

    shape = lambda dims, dtype: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip
    )
    x = shape((1, 8192, 32 * 128), jnp.bfloat16)

    def loss(q, k, v, g, b):
        o = kda.kda_scan(q, k, v, g, b, chunk=64, interpret=False)
        return o.astype(jnp.float32).sum()

    text, names = _compile(
        jax.grad(loss, argnums=(0, 1, 2, 3, 4)), x, x, x,
        shape((1, 8192, 32 * 128), jnp.float32),
        shape((1, 8192, 32), jnp.float32),
    )
    assert names == ["kda_fwd", "kda_bwd"]
    assert tpu_kernel_calls(text) == 2


def test_delta_rule_head_form_kernels_compile_at_the_qwen3_next_cell_shape(one_chip):
    """The delta rule with a decay a HEAD, forward and backward, at the
    cell's size: two sequences of 8,192, 32 value heads of 128 over 16 key
    heads, chunks of 128, 8 value heads over 4 key heads a program
    (`ops/kda.py`): g and b enter as one [C, 16] float32 block a program,
    q and k as 4 lane tiles where v has 8, and a head's columns of it are
    lane slices of a value."""
    from kubeflow_tpu.ops import kda

    shape = lambda dims, dtype: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip
    )
    narrow = shape((2, 8192, 16 * 128), jnp.bfloat16)
    per_head = shape((2, 8192, 32), jnp.float32)

    def loss(q, k, v, g, b):
        o = kda.kda_scan(q, k, v, g, b, chunk=128, interpret=False)
        return o.astype(jnp.float32).sum()

    text, names = _compile(
        jax.grad(loss, argnums=(0, 1, 2, 3, 4)), narrow, narrow,
        shape((2, 8192, 32 * 128), jnp.bfloat16), per_head, per_head,
    )
    assert names == ["gdn_fwd", "gdn_bwd"]
    assert tpu_kernel_calls(text) == 2


@pytest.mark.parametrize("width, how", [
    (32 * 128, dict(sum_dtype=jnp.bfloat16, head_dim=128, scale=128 ** -0.5)),
    (32 * 128, dict(sum_dtype=jnp.bfloat16)),
    (4096 + 2 * 4 * 128, dict(bias=True)),
    (16 * 128, dict(sum_dtype=jnp.bfloat16, head_dim=128, scale=128 ** -0.5)),
], ids=["kimi q and k", "kimi v", "nemotron xBC", "qwen3-next q and k"])
def test_short_convolution_kernels_compile_at_the_cells_shapes(
    one_chip, width, how
):
    """The recurrent mixers' convolution, `silu` and a head's norm, forward
    and backward, at one sequence of 8,192 (`ops/shortconv.py`): blocks of
    2,048 rows one lane tile wide with halos of 16 rows, the float32
    scratch read at rows that are no multiple of eight (whole rows only:
    the chip's compiler refuses such a read of a lane slice), inside the
    default scoped VMEM."""
    from kubeflow_tpu.ops import shortconv

    shape = lambda dims, dtype: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip
    )
    how = dict(how)
    bias = shape((width,), jnp.float32) if how.pop("bias", False) else None

    def loss(u, w, bias):
        y = shortconv.short_conv(u, w, bias, eps=1e-5, interpret=False, **how)
        return y.astype(jnp.float32).sum()

    text, names = _compile(
        # with the value: the backward reads u and the cotangent alone,
        # so a gradient by itself holds no forward call at all
        jax.value_and_grad(loss, argnums=(0, 1)),
        shape((1, 8192, width), jnp.bfloat16), shape((4, width), jnp.float32),
        bias,
    )
    assert names == ["shortconv_fwd", "shortconv_bwd"]
    assert tpu_kernel_calls(text) == 2


@pytest.mark.parametrize("how", [
    dict(group=1024, gate_first=True, skip=True),
    dict(group=128, gate_first=False, skip=False),
    dict(group=128, gate_first=False, skip=False, act="silu"),
], ids=["nemotron: silu, groups of 1,024, the skip", "kimi: heads of 128, sigmoid",
        "qwen3-next: heads of 128, the norm then silu"])
def test_gated_norm_kernels_compile_at_the_cells_shapes(one_chip, how):
    """The recurrent mixers' gated norm, forward and backward, at one
    sequence of 8,192 by 4,096 lanes (`ops/gatenorm.py`): blocks one group
    wide (256 rows of 1,024 lanes, seven of them twice over in the
    backward; 2,048 rows of 128), inside the default scoped VMEM."""
    from kubeflow_tpu.ops import gatenorm

    shape = lambda dims, dtype: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip
    )
    wide = shape((1, 8192, 4096), jnp.bfloat16)
    vector = shape((4096,), jnp.float32)
    skip = (wide, vector) if how["skip"] else None

    def loss(o, gate, scale, skip):
        y = gatenorm.gated_norm(
            o, gate, scale, group=how["group"], eps=1e-5,
            gate_first=how["gate_first"], act=how.get("act"), skip=skip,
            interpret=False,
        )
        return y.astype(jnp.float32).sum()

    text, names = _compile(
        jax.value_and_grad(loss, argnums=(0, 1, 2, 3) if skip else (0, 1, 2)),
        wide, wide, vector, skip,
    )
    assert names == ["gatenorm_fwd", "gatenorm_bwd"]
    assert tpu_kernel_calls(text) == 2


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
        # tiles by 1000-row blocks, unpadded: one band a diagonal block
        (2000, 2000, 1000, False),
        # no aligned divisor: pads, masks the tail and by position
        (2001, 2048, 1024, True),
    ],
)
def test_ragged_sequences_compile(one_chip, s, padded, block, packed):
    sched = flash_schedule(s, s)
    assert (sched["padded_seq_q"], sched["block_q"], sched["lse_packed"]) == (
        padded, block, packed
    )
    assert sched["diag_tile"] == (1000 if padded == s else 0)
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


def _olmo_1b_step(devices, dp, tp):
    """(trainer, abstract arguments) of cell 2's step program
    (`benchmarks/workloads/olmo-1b.train-2k-dp2tp2.json`: OLMo-1B's widths,
    8 x 2048 tokens, adamw, no remat) cut to one layer, which already shows
    every form, on described devices."""
    from kubeflow_tpu.models.transformer import TransformerConfig, TransformerLM
    from kubeflow_tpu.parallel import MeshSpec, build_mesh
    from kubeflow_tpu.train import TrainConfig, Trainer

    mesh = build_mesh(MeshSpec(dp=dp, tp=tp), list(devices)[: dp * tp])
    cfg = TransformerConfig(
        vocab_size=50304, d_model=2048, n_layers=1, n_heads=16,
        head_dim=HEAD_DIM, d_ff=8192, remat_policy="none",
    )
    config = TrainConfig(
        batch_size=8, optimizer="adamw", label_smoothing=0.0,
        fsdp_params=False, train_metrics="loss",
    )
    trainer = Trainer(
        TransformerLM(cfg, mesh=mesh), config, mesh,
        example_input_shape=(2, 2048), example_input_dtype=jnp.int32,
        input_key="tokens", label_key="labels",
    )
    tokens = jax.ShapeDtypeStruct(
        (8, 2048), jnp.int32, sharding=trainer.batch_sharding(2)
    )
    return trainer, (trainer.abstract_state(), {"tokens": tokens, "labels": tokens})


def _as_on_the_chip(monkeypatch):
    """`jax.default_backend()` is still the CPU here: steer the two places
    that ask it (`ops/rope.py` asks `flash`'s), so the step holds the
    compiled kernels as on the chip."""
    from kubeflow_tpu.ops import attention, flash

    monkeypatch.setattr(attention, "kernels_compiled", lambda: True)
    monkeypatch.setattr(flash, "kernels_compiled", lambda: True)


def _replica_groups(text):
    return set(re.findall(r"replica_groups=(\S+?),? ", text))


def test_four_chip_step_compiles_with_asynchronous_collectives(
    topo, monkeypatch
):
    """The mechanism's engagement counter where there is no chip: on a
    `dp=2, tp=2` mesh of TPU devices `make_train_step()` hands the
    compiler `step_compiler_options`, the installed libtpu accepts every
    name, and the compiled step holds collectives in asynchronous form —
    the same all-reduces over the same groups as without the options,
    which holds none."""
    from kubeflow_tpu.train import trainer as trainer_module

    _as_on_the_chip(monkeypatch)
    trainer, args = _olmo_1b_step(topo.devices, dp=2, tp=2)
    assert step_compiler_options(trainer.mesh)
    text = compiled_hlo(trainer.make_train_step(), *args)
    monkeypatch.setattr(trainer_module, "step_compiler_options", lambda mesh: None)
    plain = compiled_hlo(trainer.make_train_step(), *args)

    assert sum(async_collective_counts(plain).values()) == 0
    counts = async_collective_counts(text)
    assert counts["fusion"] > 0 and counts["tagged"] > 0, counts
    assert _replica_groups(text) == _replica_groups(plain) != set()
    assert tpu_kernel_calls(text) == tpu_kernel_calls(plain) > 0


def test_equal_heads_step_is_the_program_it_was_before_grouped_heads(
    monkeypatch,
):
    """Cell 2's step (equal heads, `dp=2, tp=2`, flash under `shard_map`)
    was not run again on the chip when the kernels learned grouped K/V
    heads, so it is held here: with the two things grouping changes put
    back as they stood (k and v ride q's grid row; dK and dV returned as
    the kernels wrote them), the traced step — every kernel's body and
    every block's index map with it — is the same text. Since ISSUE 31
    that program reads q, k and v as [B, S, H·128]: rope's kernel and
    the flash kernels under `shard_map`, a shard's 8 heads the column
    blocks of a row, and no transpose round them."""
    from kubeflow_tpu.ops import flash

    from kubeflow_tpu.testing.hlo import _walk_eqns

    _as_on_the_chip(monkeypatch)
    trainer, args = _olmo_1b_step(jax.devices(), dp=2, tp=2)

    def traced():
        # An equation prints its kernel's body, not its blocks' index
        # maps: those are read from the calls' grid mappings.
        jaxpr = trainer.make_train_step().trace(*args).jaxpr
        calls = [
            eqn for eqn in _walk_eqns(jaxpr.jaxpr)
            if eqn.primitive.name == "pallas_call"
        ]
        maps = [
            str(block.index_map_jaxpr)
            for eqn in calls
            for block in eqn.params["grid_mapping"].block_mappings
        ]
        shapes = {
            eqn.params["name"]: [v.aval.shape for v in eqn.invars]
            for eqn in calls
        }
        return "\n".join([str(jaxpr), *maps]), shapes

    now, shapes = traced()
    assert "flash_bwd_fused" in now and "shard_map" in now
    # A shard: 4 of 8 batch rows, 8 of 16 heads, folded into the lanes.
    assert shapes["rope_turn_fwd"][0] == (4, 2048, 8 * HEAD_DIM)
    assert shapes["flash_fwd_compact"][2:] == [(4, 2048, 8 * HEAD_DIM)] * 3
    assert shapes["flash_bwd_fused"][2:6] == [(4, 2048, 8 * HEAD_DIM)] * 4
    jax.clear_caches()
    monkeypatch.setattr(flash, "_kv_row", lambda group: lambda g: g)
    monkeypatch.setattr(flash, "_sum_groups", lambda dk, like, d: dk)
    assert traced()[0] == now


def test_one_chip_step_holds_no_relayout_of_q(topo, monkeypatch):
    """`olmo-1b-cut`'s layer (8 x 2048 tokens, 16 heads of 128) compiled
    for the chip: between the projections' matmuls and the attention
    kernels no instruction of the step copies or transposes a q-sized
    array, in any of the shapes a relayout of q, k, v, o or their
    gradients has had ([B, S, H·D], [B, S, H, D], [B, H, S, D],
    [B·H, S, D]; the parent's step had five, two of them in float32)."""
    _as_on_the_chip(monkeypatch)
    trainer, args = _olmo_1b_step(topo.devices, dp=1, tp=1)
    text = compiled_hlo(trainer.make_train_step(), *args)
    entry = re.search(r"^ENTRY .*?^\}", text, re.M | re.S).group(0)
    q_sized = r"(?:8,2048,2048|8,2048,16,128|8,16,2048,128|128,2048,128)"
    relayouts = re.findall(
        rf"= \w+\[{q_sized}\]\S* (?:copy|transpose)\(", entry
    )
    assert relayouts == [], relayouts
    assert tpu_kernel_calls(entry) == 7  # rope x 4, flash fwd, delta, bwd


def test_one_chip_step_gets_no_options_and_no_collective(topo, monkeypatch):
    """One device: `jax.jit` receives what it received before the options
    existed, and the program has nothing to schedule between chips."""
    _as_on_the_chip(monkeypatch)
    trainer, args = _olmo_1b_step(topo.devices, dp=1, tp=1)
    assert step_compiler_options(trainer.mesh) is None
    text = compiled_hlo(trainer.make_train_step(), *args)
    counts = collective_counts(text)
    del counts["dynamic-slice"]  # rides along for the CPU backend only
    assert sum(counts.values()) == 0, counts
    assert sum(async_collective_counts(text).values()) == 0
    assert tpu_kernel_calls(text) > 0


def test_a_cell_shaped_step_stays_under_the_remat_plans_predicted_peak(
    topo, monkeypatch
):
    """The nemotron cell's widths at its 8,192 tokens, cut to one period's
    mixer, expert layer and attention (`M`, `E`, `*`), under
    `remat_policy="flash"` with a v5e's limit stated: the plan admits every
    name the three layers form, and what the chip's compiler counts for the
    step (arguments, temporaries and code) stays under the plan's predicted
    peak. The model is an upper bound, never a promise of room."""
    from kubeflow_tpu.models.transformer import (
        TransformerConfig, TransformerLM, remat_plan,
    )
    from kubeflow_tpu.ops import moe, ssd
    from kubeflow_tpu.parallel import MeshSpec, build_mesh
    from kubeflow_tpu.train import TrainConfig, Trainer
    from kubeflow_tpu.utils import memory

    _as_on_the_chip(monkeypatch)
    monkeypatch.setattr(moe, "kernels_compiled", lambda: True)
    monkeypatch.setattr(ssd, "kernels_compiled", lambda: True)
    monkeypatch.setattr(memory, "device_limit", lambda mesh: 16_909_336_064)
    cfg = TransformerConfig(
        vocab_size=16384, d_model=4096, n_layers=3, layer_pattern="ME*",
        n_heads=16, n_kv_heads=1, head_dim=HEAD_DIM, rope_fraction=0.0,
        norm_eps=1e-5, tie_embeddings=False, remat_policy="flash",
        d_ff=2688, num_experts=512, experts_held=(0, 8), experts_per_token=22,
        router="sigmoid", routed_scaling=5.0, mlp_act="relu2", moe_latent=1024,
        moe_shared_ff=5376, router_force_balance=True, ssm_heads=64,
        ssm_head_dim=64, ssm_state=128, ssm_groups=4, ssm_conv=4, ssm_chunk=128,
    )
    mesh = build_mesh(MeshSpec(), list(topo.devices)[:1])
    trainer = Trainer(
        TransformerLM(cfg, mesh=mesh),
        TrainConfig(batch_size=1, optimizer="adamw", label_smoothing=0.0,
                    fsdp_params=False, train_metrics="loss"),
        mesh, example_input_shape=(2, 8192), example_input_dtype=jnp.int32,
        input_key="tokens", label_key="labels",
    )
    tokens = jax.ShapeDtypeStruct(
        (1, 8192), jnp.int32, sharding=trainer.batch_sharding(2)
    )
    plan = remat_plan(cfg, 8192, trainer.step_memory())
    assert plan.names == (
        "moe_route", "moe_latent_in", "ssm_in_proj", "mlp_hidden", "attn_qkv",
        "ssm_conv", "mixer_gated",
    ) and plan.refused == ()
    compiled = trainer.make_train_step().lower(
        trainer.abstract_state(), {"tokens": tokens, "labels": tokens}
    ).compile()
    counted = compiled.memory_analysis()
    used = (
        counted.argument_size_in_bytes + counted.output_size_in_bytes
        - counted.alias_size_in_bytes + counted.temp_size_in_bytes
        + counted.generated_code_size_in_bytes
    )
    state = trainer.step_memory().state_bytes
    assert state + plan.saved_bytes < used <= plan.predicted_peak, (
        used, plan
    )


def test_a_xing_shaped_step_compiles_with_the_two_part_kernels(topo, monkeypatch):
    """Xing4.0-29B-A4B's published widths at its cell's 8,192 tokens, cut to
    the leading dense layer and one expert layer, under
    `remat_policy="flash"` with a v5e's limit stated: the chip's compiler
    takes the two-part flash calls (128 + 64 over 128, one rope key; the
    fused backward with its second dq ring), rope's kernel on heads of 64
    and the streams' row-block kernels; the plan admits every name, and what the
    compiler counts stays under the plan's predicted peak, as the other
    families' cuts do (8.81 GB counted; the top layer's backward is the
    fuller moment here: every checkpoint, every result kept, the streams
    in float32 with their cotangent and no gradient yet)."""
    from kubeflow_tpu.models.transformer import (
        AttentionKind, TransformerConfig, TransformerLM, remat_plan,
    )
    from kubeflow_tpu.ops import moe
    from kubeflow_tpu.parallel import MeshSpec, build_mesh
    from kubeflow_tpu.train import TrainConfig, Trainer
    from kubeflow_tpu.utils import memory

    _as_on_the_chip(monkeypatch)
    monkeypatch.setattr(moe, "kernels_compiled", lambda: True)
    monkeypatch.setattr(memory, "device_limit", lambda mesh: 16_909_336_064)
    kind = AttentionKind(32, None, 10_000.0, 1.0, (64.0, 4096, 32.0, 1.0, 1.0))
    cfg = TransformerConfig(
        vocab_size=16384, d_model=3584, n_layers=2, n_heads=32,
        head_dim=HEAD_DIM, q_latent=768, kv_latent=512, rope_head_dim=64,
        v_head_dim=128, softmax_scale=192 ** -0.5 * 2.0047,
        attention_kinds=(kind,), attention_pattern=(0, 0), residual_streams=4,
        tie_embeddings=False, remat_policy="flash", dense_layers=1,
        dense_d_ff=9216, d_ff=1024, num_experts=64, experts_held=(0, 8),
        experts_per_token=4, router="sigmoid", routed_scaling=2.0,
        moe_shared_ff=1024, router_force_balance=True,
    )
    mesh = build_mesh(MeshSpec(), list(topo.devices)[:1])
    trainer = Trainer(
        TransformerLM(cfg, mesh=mesh),
        TrainConfig(batch_size=1, optimizer="adamw", label_smoothing=0.0,
                    fsdp_params=False, train_metrics="loss"),
        mesh, example_input_shape=(2, 8192), example_input_dtype=jnp.int32,
        input_key="tokens", label_key="labels",
    )
    tokens = jax.ShapeDtypeStruct(
        (1, 8192), jnp.int32, sharding=trainer.batch_sharding(2)
    )
    plan = remat_plan(cfg, 8192, trainer.step_memory())
    assert plan.names == (
        "hc_maps", "moe_route", "attn_residual", "mlp_hidden", "attn_latent",
        "hc_out", "attn_qkv",
    ) and plan.refused == ()
    compiled = trainer.make_train_step().lower(
        trainer.abstract_state(), {"tokens": tokens, "labels": tokens}
    ).compile()
    text = compiled.as_text()
    for name in ("flash_fwd_mla", "flash_bwd_mla_fused", "flash_delta",
                 "rope_turn_fwd", "moe_gmm_fwd", "hc_pre_fwd", "hc_post_fwd",
                 "hc_post_bwd", "hc_pre_bwd"):
        assert re.search(rf"{name}[^\n]*tpu_custom_call|tpu_custom_call[^\n]*{name}", text), name
    assert "flash_fwd_compact" not in text and "flash_bwd_fused" not in text
    counted = compiled.memory_analysis()
    used = (
        counted.argument_size_in_bytes + counted.output_size_in_bytes
        - counted.alias_size_in_bytes + counted.temp_size_in_bytes
        + counted.generated_code_size_in_bytes
    )
    state = trainer.step_memory().state_bytes
    assert state + plan.saved_bytes < used <= plan.predicted_peak, (
        used, plan
    )


def test_a_glm_shaped_step_compiles_with_its_multi_token_module(topo, monkeypatch):
    """GLM-4.7-Flash's published widths at its cell's 8,192 tokens, cut to
    the leading dense layer and one expert layer, WITH the multi-token
    module (its block, its projection, the shared head a second time and
    the loss over two targets, the model's own scalar), under
    `remat_policy="flash"` with a v5e's limit stated: the chip's compiler
    takes the one-part flash calls at a head of 64 + 192 lanes over values
    of 256 (none with two parts), rope's kernel over a head's first 64
    lanes and the experts' kernels; the plan admits every name, the
    module's block's among them, no flash forward runs again, and what the
    compiler counts stays under the plan's predicted peak."""
    from kubeflow_tpu.models.transformer import (
        AttentionKind, TransformerConfig, TransformerLM, remat_plan,
    )
    from kubeflow_tpu.ops import moe
    from kubeflow_tpu.parallel import MeshSpec, build_mesh
    from kubeflow_tpu.train import TrainConfig, Trainer, profiling
    from kubeflow_tpu.utils import memory

    _as_on_the_chip(monkeypatch)
    monkeypatch.setattr(moe, "kernels_compiled", lambda: True)
    monkeypatch.setattr(memory, "device_limit", lambda mesh: 16_909_336_064)
    cfg = TransformerConfig(
        vocab_size=19360, d_model=2048, n_layers=2, n_heads=20, head_dim=192,
        q_latent=768, kv_latent=512, rope_head_dim=64, v_head_dim=256,
        norm_eps=1e-5, attention_kinds=(AttentionKind(20, rope_theta=1e6),),
        attention_pattern=(0, 0), tie_embeddings=False, remat_policy="flash",
        dense_layers=1, dense_d_ff=10240, d_ff=1536, num_experts=64,
        experts_held=(0, 8), experts_per_token=4, router="sigmoid",
        routed_scaling=1.8, moe_shared_ff=1536, router_force_balance=True,
        mtp_layers=1, mtp_weight=0.3,
    )
    mesh = build_mesh(MeshSpec(), list(topo.devices)[:1])
    trainer = Trainer(
        TransformerLM(cfg, mesh=mesh),
        TrainConfig(batch_size=1, optimizer="adamw", label_smoothing=0.0,
                    fsdp_params=False, train_metrics="loss",
                    loss_in_model=True),
        mesh, example_input_shape=(2, 8192), example_input_dtype=jnp.int32,
        input_key="tokens", label_key="labels",
    )
    tokens = jax.ShapeDtypeStruct(
        (1, 8192), jnp.int32, sharding=trainer.batch_sharding(2)
    )
    plan = remat_plan(cfg, 8192, trainer.step_memory())
    assert plan.names == (
        "moe_route", "attn_residual", "mlp_hidden", "attn_latent", "attn_qkv",
    ) and plan.refused == ()
    compiled = trainer.make_train_step().lower(
        trainer.abstract_state(), {"tokens": tokens, "labels": tokens}
    ).compile()
    text = compiled.as_text()
    for name in ("flash_fwd_compact", "flash_bwd_fused", "flash_delta",
                 "rope_turn_fwd", "moe_gmm_fwd"):
        assert re.search(rf"{name}[^\n]*tpu_custom_call|tpu_custom_call[^\n]*{name}", text), name
    assert "mla" not in text
    # The module's loss masks its last position over logits left whole (the
    # labels alone are rolled): a float array of S - 1 rows is no whole
    # number of tiles, and XLA relaid the float32 logits out for one in a
    # loop (25.8 ms a step on the chip, PERF.md §6, PR 48).
    assert not re.search(r"(f32|bf16)\[[\d,]*\b8191\b[\d,]*\]", text)
    table = profiling.program_scopes(text, root="TransformerLM")
    flash = {
        (scope.path.split("/")[0], scope.phase)
        for name, scope in table.items() if name.startswith("flash_fwd")
    }
    # three blocks' forward calls, the module's under its frame; none again
    assert flash == {
        ("layer_0", "forward"), ("layer_1", "forward"), ("mtp", "forward")}
    module = {scope.path for scope in table.values() if scope.path.startswith("mtp/")}
    assert {"mtp/mtp.proj/eh_proj", "mtp/mtp.loss"} <= module
    assert any(p.startswith("mtp/mtp.head") for p in module)
    assert any(p.startswith("mtp/block/attn/attend") for p in module)
    assert not any(p.startswith("mtp/mtp/") for p in module)
    counted = compiled.memory_analysis()
    used = (
        counted.argument_size_in_bytes + counted.output_size_in_bytes
        - counted.alias_size_in_bytes + counted.temp_size_in_bytes
        + counted.generated_code_size_in_bytes
    )
    state = trainer.step_memory().state_bytes
    assert state + plan.saved_bytes < used <= plan.predicted_peak, (
        used, plan
    )


def test_a_qwen3_next_shaped_expert_layer_routes_with_no_gather_and_no_scatter(
    topo, monkeypatch
):
    """One layer at Qwen3-Next's expert widths and its cell's 2 x 8,192
    tokens (ten of 512 experts by softmax, 32 held), under
    `remat_policy="flash"` with a v5e's limit stated: the chip's compiler
    takes the router's kernel pair (`ops/router.py`), once each a layer
    and neither again under the checkpoint (the chosen scores and the
    weights are kept by name), and nothing under `moe.route` moves an
    element singly: no `gather` and no `scatter` there (the parent's
    `take_along_axis` held one of each, 163,840 elements a layer)."""
    from kubeflow_tpu.models.transformer import TransformerConfig, TransformerLM
    from kubeflow_tpu.ops import moe, router
    from kubeflow_tpu.parallel import MeshSpec, build_mesh
    from kubeflow_tpu.train import TrainConfig, Trainer
    from kubeflow_tpu.utils import memory

    _as_on_the_chip(monkeypatch)
    monkeypatch.setattr(moe, "kernels_compiled", lambda: True)
    monkeypatch.setattr(memory, "device_limit", lambda mesh: 16_909_336_064)
    cfg = TransformerConfig(
        vocab_size=16384, d_model=2048, n_layers=1, n_heads=16, n_kv_heads=2,
        head_dim=HEAD_DIM, tie_embeddings=False, remat_policy="flash",
        d_ff=512, num_experts=512, experts_held=(0, 32), experts_per_token=10,
        router="softmax", moe_shared_ff=512, moe_shared_gate=True,
        router_force_balance=True,
    )
    mesh = build_mesh(MeshSpec(), list(topo.devices)[:1])
    trainer = Trainer(
        TransformerLM(cfg, mesh=mesh),
        TrainConfig(batch_size=2, optimizer="adamw", label_smoothing=0.0,
                    fsdp_params=False, train_metrics="loss"),
        mesh, example_input_shape=(2, 8192), example_input_dtype=jnp.int32,
        input_key="tokens", label_key="labels",
    )
    tokens = jax.ShapeDtypeStruct(
        (2, 8192), jnp.int32, sharding=trainer.batch_sharding(2)
    )
    text = trainer.make_train_step().lower(
        trainer.abstract_state(), {"tokens": tokens, "labels": tokens}
    ).compile().as_text()
    routed = [line for line in text.splitlines() if "moe.route" in line]
    assert routed
    assert not [
        line for line in routed if " gather(" in line or " scatter(" in line
    ]
    calls = [
        line for line in routed
        if "tpu_custom_call" in line and "route_weights_" in line
    ]
    assert sorted(
        re.search(r"route_weights_(fwd|bwd)", line).group(0) for line in calls
    ) == ["route_weights_bwd", "route_weights_fwd"]
    assert not [line for line in calls if "rematted_computation" in line]
    schedule = router.router_schedule(16384, 2048, 512, 10, jnp.bfloat16, compiled=True)
    assert schedule["form"] == "kernels"
    assert schedule["gathered_elements"] == schedule["scattered_elements"] == 0
