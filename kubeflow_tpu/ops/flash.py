"""Flash attention as a Pallas TPU kernel (forward + backward).

The reference has no kernels at all — its device-level compute lives inside
third-party containers (SURVEY.md §2.1). On TPU the hot op of the flagship
transformer is attention, and the XLA-fused dense path materializes the
[S, S] score matrix in HBM. This kernel is the classic blockwise
(flash-attention) schedule tiled for the MXU, with a long-context schedule
on top:

- **Compact causal grid.** For causal self-attention the grid enumerates
  ONLY the lower-triangular (q, k) block pairs: the grid is
  (batch*heads, T) with T = nq·(nq+1)/2, and two scalar-prefetched int32
  tables map the flat step index back to (i, j). Blocks above the
  diagonal cost zero grid steps — at large S that halves the step count
  outright, where the old rectangular grid paid a predicated-off
  DMA+step per masked block. The rectangular grid (with `_clamp_i` /
  `_clamp_j` DMA elision) remains as the fallback for non-causal,
  cross-shaped, or uneven-block configurations.
- **Two bodies a step, chosen by its place in the triangle.** On the
  compact grid a step is either ON the diagonal (j == i) or BELOW it
  (j < i), and reads which from the tables it already prefetches
  (`_step_tiles`, under `pl.when`). Below the diagonal no score is
  masked and none is -inf, so that body builds no mask and guards no
  exp. On the diagonal the block's q rows are cut into static row
  bands (`_diag_bands`, from the block size alone): band r runs every
  matmul of the step against keys [0, (r+1)·t) only, under a mask that
  is a constant (`_band_mask`: no i·bq, no j·bk), so with n bands
  n(n+1)/2 of the block's n² sub-tiles are computed where the whole
  square was computed and half of it masked away. Grid, DMA blocks,
  step count and HBM bytes are unchanged: only the arithmetic inside a
  step. The rectangular grid and any call with `kv_len` (a padded tail
  can mask keys the triangle does not) keep the body that masks by
  position. `flash_schedule` reports how often each engages
  (`diag_steps`, `interior_steps`, `diag_tile`,
  `computed_pairs_over_needed`).
- **A window's band.** With `window=W` (position i sees keys
  i - W < j <= i) the same compact grid enumerates only the block
  pairs that band touches, i - reach <= j <= i with reach =
  ceil((W - 1) / block) (`_band_reach`, `_tri_tables`), and a step is
  told from the tables by its distance i - j from the diagonal: ON the
  diagonal, where the band's far edge crosses (`_window_kinds`), or
  between them (a whole block, no mask). A block an edge cuts is run
  in row bands against the key lane tiles its rows can see
  (`_window_plan`), under a mask that is a constant of the distance
  (`_window_mask`); at W = 512 in blocks of 1024 that is 15 grid steps
  a head where the triangle has 36, and 1.25 times the band's pairs
  computed where the triangle under a mask would compute 8.9 times.
  The fused backward's dq ring holds the reach + 1 row blocks that are
  live at once (`_ring_rows`), whatever S is. The calls are named
  `flash_*_window*` (`_kernel_name`); W >= S is the causal call.
- **Two-part scores.** With `q_rope` [B, S, H, R] and `k_rope`
  [B, S, R] (latent attention: DeepSeek-V2's MLA) a head's scores are
  q·kᵀ + q_rope·k_ropeᵀ: a part a head, as wide as v, beside a rope part
  whose KEY is one for all heads. Every kernel takes q and k as tuples
  of parts (`_parts`, `_sum_parts`): the score tile is the sum of two
  products, dq and dk get a product a part (the fused backward's five
  block products become eight), each part with a ring or accumulator
  and an output of its own; v, o, dO, dv and `flash_delta` know nothing
  of it. The rope key's block is fetched by the index map of a K/V
  group that holds all heads (`_rope_specs`), so it lies in HBM once,
  and dk_rope leaves one partial a head, summed like a group's
  (`_sum_groups`). The 128-wide operands keep their layout; the 64-wide
  q_rope goes head-major (`ROPE_LAYOUT`). The calls are named
  `flash_*_mla*`.
  A call without the pair traces the program it traced before.
- **Lane-packed LSE.** The saved log-sum-exp is stored as
  [BH, S/128, 128] tiles — 128 per-row values per lane row — instead of
  the lane-replicated [BH, S, 128] buffer Mosaic's tiling would
  otherwise force (a [BH, S] vector output is not lowerable). That cuts
  the lse's HBM footprint and its fwd→bwd traffic 128×. Packing happens
  in-register via (128, 128) transposes of the lane-replicated scratch
  (a supported Mosaic relayout), not a 1-D reshape. A q block packs to
  a (bq/128, 128) tile, which the TPU lowering accepts only when
  bq/128 is a sublane multiple (bq % 1024 == 0) or the block is the
  whole sequence; every other block size falls back to the replicated
  layout with a slim [BH, S, 1] residual.
- **Shared-delta backward.** A small precompute kernel emits
  delta = rowsum(dO ∘ O) once per backward; the backward kernels read
  it as an input instead of each recomputing the rowsum on-chip — which
  also removes O entirely from the backward input streams (dO/O were
  previously re-streamed by each kernel).
- **Fused one-pass dq/dkv backward.** On the compact causal grid the
  backward is ONE kernel (`_dqkv_kernel_fused`) walking the triangle
  once in column-major order: dk/dv accumulate in per-column VMEM
  scratch (as the two-pass dkv kernel did), and each step's dq
  contribution lands in a per-row slot of a VMEM dq ring — every q row
  is live from the first kv column and retires in row order (row j's
  last contribution is column j's diagonal step), so slot j flushes to
  the dq output when column j completes. K/V are fetched once per
  COLUMN and only Q/dO (plus the slim lse/delta rows) stream per grid
  step; the two-pass backward streamed K/V per dq step AND Q/dO per
  dkv step, so fusing halves the dominant bwd HBM traffic — and the
  (s, p, ds) recurrence is computed once instead of twice (5 block
  matmuls, not 7). The dq ring costs S·d·4 bytes of VMEM on top of a
  fixed ~14 MiB, which is past the compiler's default 16 MiB scoped
  limit from S=8k on, so the fused call asks for its VMEM explicitly
  (`_FUSED_VMEM_BUDGET`) and fusion is gated by `_bwd_fused` (the same
  predicate `flash_schedule` reports as `bwd_fused`); past the budget —
  or on the rectangular fallback — the two-pass kernels run unchanged.
- **Internal padding.** Sequence lengths with no 8-aligned divisor pad
  to the next lane multiple inside `flash_attention`; the tail is
  masked in-kernel (`kv_len`) and sliced off the output, so ragged
  lengths run the kernel instead of silently falling back to the dense
  O(S²) path.
- **The projections' layout.** Where the head size is whole lanes
  (d % 128 == 0: `_head_layout`, `flash_schedule`'s `layout`) the
  kernels read q, k, v, dO and write o, dq, dk, dv as [B, S, H·d], the
  arrays the projections' matmuls write and read: a head is a column
  block of 128·k lanes, picked by the block specs' index maps (`_specs`),
  and `flash_attention` traces no transpose. Any other d folds the heads
  into the batch ([B·H, S, d]) by the four transposes in and four out
  that every call used to pay (10.7 ms of a 395 ms step at 16 heads,
  6 layers; PERF.md §6, PR 31). One spec builder, one set of bodies,
  bit-identical results.
- grid steps run sequentially on TPU, so the running max / normalizer /
  output accumulator live in VMEM scratch and carry across k-steps —
  HBM traffic is O(S·d), never O(S²); Q/K/V blocks stream HBM→VMEM via
  the BlockSpec pipeline (double-buffered by Pallas) and the two
  matmuls per block hit the MXU in float32 accumulation.

The forward names its outputs (`flash_attn_out`, `flash_attn_lse`) via
`jax.checkpoint_name`, so `remat_policy="flash"`
(`models/transformer.py`) can pin exactly {attention output, lse} across
a block checkpoint — the backward then never re-runs the forward kernel
(its residuals q/k/v recompute from the cheap projections; o and lse are
saved).

Everything is wired through ``jax.custom_vjp`` so the op drops into any
``jax.grad`` / ``pjit`` / ``shard_map`` context. On the CPU backend the
same kernels run under the Pallas interpreter (slow, test-only), which is
how the CPU test suite validates them against the dense reference; on
every other backend they are compiled, and a device the TPU compiler does
not know fails loudly instead of interpreting.

Every `pallas_call` carries a stable `name=` (`flash_fwd_*`,
`flash_delta`, `flash_bwd_fused`, `flash_dq_*`, `flash_dkv_*`, a
window's `flash_fwd_window`, `flash_bwd_window_fused`,
`flash_dq_window`, `flash_dkv_window`, and the two-part calls'
`flash_fwd_mla`, `flash_bwd_mla_fused`, `flash_dq_mla`,
`flash_dkv_mla`): that is
what `testing/hlo.pallas_kernel_names` reads out of a jaxpr to tell which
schedule was traced, and what a profiler trace keys kernel time on.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float("-inf")
_LANES = 128  # Mosaic min tile lane count (f32 tile is (8, 128))
_SUBLANES = 8  # Mosaic's minimum second-minor tile rows

# Compact causal grids carry two int32 (i, j) lookup tables in SMEM via
# scalar prefetch. Cap their length so a degenerate tiny-block × huge-S
# combination cannot blow the scalar-memory budget; past the cap the
# rectangular fallback (predicated blocks + clamped DMAs) still runs.
_MAX_COMPACT_STEPS = 1 << 16

# jax.checkpoint_name tags on the forward's outputs — the handles
# remat_policy="flash" (models/transformer.py) pins across a block
# checkpoint so the backward never re-runs the forward kernel.
CHECKPOINT_OUT_NAME = "flash_attn_out"
CHECKPOINT_LSE_NAME = "flash_attn_lse"


def kernels_compiled() -> bool:
    """Whether Pallas kernels compile for the default backend. Only the
    CPU backend (the test suite) interprets; any accelerator compiles,
    so a platform string this code has never seen reaches the TPU
    compiler and is refused there, never quietly interpreted or routed
    to dense attention. `ops/attention.attend` dispatches on the same
    predicate."""
    return jax.default_backend() != "cpu"


def _auto_interpret(interpret: bool | None) -> bool:
    if interpret is not None:
        return interpret
    return not kernels_compiled()


def row_blocks_apply(x, tile: int, mesh, compiled: bool | None = None) -> bool:
    """What the row-block kernel pairs' `kernels_apply` share
    (`ops/shortconv.py`, `gatenorm.py`, `streams.py`): kernels compile
    (`compiled` stands in for the backend: tests make the CPU interpret),
    x [B, S, W] is bfloat16, whole tiles of `tile` lanes (whole lane
    tiles) by whole blocks of 128 rows, on one device: a Pallas call does
    not partition itself under `jit`, so on a mesh the plain forms run
    (where the scans run in `shard_map`: ROADMAP Design 15)."""
    if compiled is None:
        compiled = kernels_compiled()
    return (
        compiled
        and x.ndim == 3
        and x.dtype == jnp.bfloat16
        and tile % _LANES == 0
        and x.shape[-1] % tile == 0
        and x.shape[1] % _LANES == 0
        and (mesh is None or mesh.size == 1)
    )


def _causal_mask(s, i, j, bq, bk):
    q_pos = i * bq + lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = j * bk + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(q_pos >= k_pos, s, _NEG_INF)


def _window_pos_mask(s, i, j, bq, bk, window: int):
    """Mask keys a window of `window` positions (the query's own among
    them) no longer reaches: the band's far edge, by position."""
    q_pos = i * bq + lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = j * bk + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(q_pos - k_pos < window, s, _NEG_INF)


def _kv_tail_mask(s, j, bk, kv_len: int):
    """Mask key positions past the true (pre-padding) sequence length."""
    k_pos = j * bk + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(k_pos < kv_len, s, _NEG_INF)


# -- lse layouts -------------------------------------------------------------
#
# Kernel-side the lse rides in one of two layouts:
#   packed     [BH, S/128, 128] — tile (w, l) holds lse[w*128 + l]; the
#              exact information content, 1/128th the replicated bytes.
#   replicated [BH, S, 128]     — every lane carries the row's value (the
#              layout Mosaic's (8, 128) tiling forces when the q block is
#              not lane-aligned).
# The packed layout needs S and the q block to be multiples of 128 so
# block boundaries land on packed-row boundaries, and each packed
# (bq/128, 128) block must itself be a
# legal TPU tile: its second-minor dim a sublane multiple, or the whole
# array's. Outside the kernels the canonical form is per-row
# [BH, S, 1] ("rows"), to which both layouts convert with free reshapes.


def _lse_layout_shape(bh: int, sq: int, packed: bool) -> tuple[int, ...]:
    if packed:
        return (bh, sq // _LANES, _LANES)
    return (bh, sq, _LANES)


def _lse_block(bq: int, packed: bool) -> tuple[int, ...]:
    if packed:
        return (1, bq // _LANES, _LANES)
    return (1, bq, _LANES)


def _lse_is_packed(sq: int, bq: int) -> bool:
    """The predicate the TPU lowering enforces on the packed lse block
    (1, bq/128, 128): bq/128 divides by 8, or the block spans the
    sequence. Interpret mode accepts any 128-multiple, which is how
    128..896-wide blocks passed every CPU test and were refused by the
    chip's compiler."""
    tile = _SUBLANES * _LANES
    return sq % _LANES == 0 and (
        bq % tile == 0 or (bq == sq and bq % _LANES == 0)
    )


def _pack_rows(x_rep):
    """(bq, 128) lane-replicated → (bq/128, 128) packed, in-kernel.

    Cross-lane packing without a Mosaic 1-D reshape: each 128-row chunk
    of the replicated buffer is transposed — a supported (128, 128)
    relayout — after which EVERY row of the transpose holds the chunk's
    128 per-row values; row 0 is the packed tile row."""
    bq = x_rep.shape[0]
    rows = [
        x_rep[w * _LANES:(w + 1) * _LANES, :].T[:1, :]
        for w in range(bq // _LANES)
    ]
    return rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=0)


def _unpack_rows(x_packed):
    """(m, 128) packed → (m*128, 128) lane-replicated, in-kernel (the
    inverse trick: broadcast each packed row across sublanes, transpose)."""
    m = x_packed.shape[0]
    chunks = [
        jnp.broadcast_to(x_packed[w:w + 1, :], (_LANES, _LANES)).T
        for w in range(m)
    ]
    return chunks[0] if m == 1 else jnp.concatenate(chunks, axis=0)


def _read_rows(ref0, packed: bool):
    """Kernel-side: an lse/delta block in either layout → (bq, 1) rows."""
    if packed:
        return _unpack_rows(ref0)[:, :1]
    return ref0[:, :1]


def _lse_rows(lse, sq: int):
    """Host-side: any lse form (packed / replicated / slim) → [BH, S, 1]."""
    if lse.shape[1] == sq:
        return lse[:, :, :1]
    return lse.reshape(lse.shape[0], sq, 1)


def _rows_to_layout(rows, packed: bool):
    """Host-side: [BH, S, 1] rows → the kernel layout."""
    bh, sq, _ = rows.shape
    if packed:
        return rows.reshape(bh, sq // _LANES, _LANES)
    return jnp.broadcast_to(rows, (bh, sq, _LANES))


# -- schedule ----------------------------------------------------------------


def _pick_block(block: int, s: int) -> int:
    """The requested block, clamped and — when it doesn't divide the
    sequence — degraded to the largest aligned divisor of `s` instead of
    erroring (a v5e sweep shows bigger blocks win, so prefer the largest
    block that tiles the sequence exactly). Every returned block is a
    multiple of the 8-row sublane so Mosaic can lower the (bq, ...)
    VMEM tiles; lane-aligned (128) divisors are preferred. Raising is
    internal-only now: ``flash_attention`` pads untileable sequences to
    the next lane multiple before the kernels ever see them."""
    block = min(block, s)
    if s % block == 0 and block % _SUBLANES == 0:
        return block
    for step in (_LANES, _SUBLANES):
        for candidate in range(block - block % step, step - 1, -step):
            if s % candidate == 0:
                return candidate
    raise ValueError(
        f"flash attention: no {_SUBLANES}-aligned block <= {block} divides "
        f"the sequence length ({s}); pad the sequence (flash_attention "
        "does this automatically) or use dense_attention"
    )


def _tileable(block: int, s: int) -> bool:
    try:
        _pick_block(block, s)
    except ValueError:
        return False
    return True


def _pad_to_tileable(block: int, s: int) -> int:
    """`s` when it already tiles, else the next lane multiple (which
    always tiles: 128 itself divides any 128-multiple)."""
    if _tileable(block, s):
        return s
    return -(-s // _LANES) * _LANES


def _band_reach(window: int | None, nq: int, bq: int) -> int:
    """How many blocks below the diagonal a row of blocks touches: all of
    them without a window; with one, query i·bq sees back to key
    i·bq - window + 1, which lies ceil((window - 1) / bq) blocks down."""
    if window is None:
        return nq - 1
    return min(nq - 1, (window + bq - 2) // bq)


def _band_steps(nq: int, reach: int) -> int:
    """Block pairs (i, j) with i - reach <= j <= i: the triangle's
    nq·(nq+1)/2 at reach = nq - 1."""
    return (reach + 1) * nq - reach * (reach + 1) // 2


def _compactable(
    causal: bool, sq: int, sk: int, bq: int, bk: int,
    window: int | None = None,
) -> bool:
    """Whether the triangular grid applies: causal self-attention with
    square blocks, so block row i runs exactly blocks j <= i (with a
    window: i - reach <= j <= i, the band)."""
    if not (causal and sq == sk and bq == bk):
        return False
    nq = sq // bq
    return _band_steps(nq, _band_reach(window, nq, bq)) <= _MAX_COMPACT_STEPS


def _grid_steps(
    causal: bool, sq: int, sk: int, bq: int, bk: int,
    window: int | None = None,
):
    """(steps, rectangular_steps, compact) per (batch*head) grid row."""
    nq, nk = sq // bq, sk // bk
    rect = nq * nk
    if _compactable(causal, sq, sk, bq, bk, window):
        return _band_steps(nq, _band_reach(window, nq, bq)), rect, True
    return rect, rect, False


def _tri_tables(nq: int, order: str, reach: int | None = None):
    """Scalar-prefetch lookup tables for the compact causal grid: the
    flat step index t → (i, j) over the lower triangle (with `reach`,
    over its band i - reach <= j <= i only). "row" order (fwd / dq: j
    contiguous per i) or "col" order (dkv: i contiguous per j)."""
    i, j = np.tril_indices(nq)
    if reach is not None:
        band = i - j <= reach
        i, j = i[band], j[band]
    if order == "col":
        o = np.lexsort((i, j))
        i, j = i[o], j[o]
    return jnp.asarray(i, jnp.int32), jnp.asarray(j, jnp.int32)


# -- fused backward gating + HBM byte model ----------------------------------
#
# The fused one-pass backward holds a full dq accumulator ring in VMEM
# (one f32 row-block slot per q block: every row is live from the first
# kv column), so its footprint grows with S where every other kernel's
# is fixed by the block sizes. What the v5e compiler allocates for it
# (bf16, d=128, 1024² blocks; compiled for a described v5e, PR 23):
# 14.93 MiB at S=2048, 17.93 at 8192, 21.93 at 16384 — a fixed 13.9 MiB
# (accumulators, double-buffered in/out blocks and ~9.4 MiB of
# (bq, bk) f32 temporaries) plus the S·d·4 ring. The compiler's default
# scoped-VMEM limit is 16 MiB of the chip's 128, so the fused call
# states its limit itself: `_FUSED_VMEM_BUDGET` is both what
# `_bwd_fused` holds the modelled footprint to and the
# `vmem_limit_bytes` handed to the compiler, a quarter of a v5e's VMEM.
# The model is an upper bound (18.5 / 21.5 / 25.5 MiB at those shapes),
# so what `_bwd_fused` admits, the compiler accepts. At 32k the model
# says 33.5 MiB and the backward falls to two-pass — the same 16k/32k
# boundary the first, too-small model (ring + accumulators + inputs
# against 12 MiB) drew.
_FUSED_VMEM_BUDGET = 32 * 1024 * 1024


def _lse_bytes_of(sq: int, packed: bool) -> int:
    return int(np.prod(_lse_layout_shape(1, sq, packed)[1:])) * 4


def _lse_block_bytes(bq: int, packed: bool) -> int:
    return int(np.prod(_lse_block(bq, packed))) * 4


def _fused_vmem_bytes(
    sq: int, bq: int, bk: int, d: int, itemsize: int, packed: bool,
    rope: int = 0,
) -> int:
    """Upper bound on the scoped VMEM the compiler allocates for the
    fused kernel: the dq ring (f32, one slot per q block — i.e. the
    whole padded sequence), per-column dk/dv f32 accumulators, the
    Pallas-double-buffered input AND output blocks, and the kernel
    body's live values — three (bq, bk) f32 temporaries of the
    s/p/dp/ds recurrence and the f32 casts of q and do. Checked against
    the compiler's own figures over bq ∈ {256..2048}, d ∈ {64..256},
    bf16 and f32: 10–25% above each. With a rope part of `rope` dims
    (two-part scores) everything q and k have, the rope part has too,
    in whole lane tiles: a second dq ring, a second dk accumulator, its
    blocks in and out and their f32 casts."""
    r = -(-rope // _LANES) * _LANES
    return _rope_vmem_bytes(sq, bq, bk, r, itemsize) + (
        sq * d * 4  # dq ring scratch
        + 2 * bk * d * 4  # dk/dv accumulators
        + 2 * 2 * bq * d * itemsize  # q, do blocks (double-buffered)
        + 2 * 2 * bk * d * itemsize  # k, v blocks (double-buffered)
        + 2 * (bq + 2 * bk) * d * itemsize  # dq, dk, dv output blocks
        + 2 * 2 * _lse_block_bytes(bq, packed)  # lse, delta blocks
        + 3 * bq * bk * 4  # s/p, dp, ds temporaries
        + 2 * bq * d * 4  # f32 q, do
    )


def _rope_vmem_bytes(sq: int, bq: int, bk: int, r: int, itemsize: int) -> int:
    return (
        sq * r * 4  # the rope part's dq ring
        + bk * r * 4  # its dk accumulator
        + 2 * (bq + bk) * r * itemsize  # q_rope, k_rope blocks
        + 2 * (bq + bk) * r * itemsize  # dq_rope, dk_rope output blocks
        + (bq + bk) * r * 4  # f32 q_rope, k_rope
    )


def _ring_rows(sq: int, bq: int, window: int | None) -> int:
    """Rows of the fused backward's dq ring: a slot for every q block
    that is live at once. Without a window every row is live from the
    first column, so the whole (padded) sequence; with one a row is live
    for the reach + 1 columns its band crosses, whatever S is."""
    return (_band_reach(window, sq // bq, bq) + 1) * bq


def _bwd_fused(
    causal: bool, sq: int, sk: int, bq: int, bk: int, d: int,
    itemsize: int, packed: bool, window: int | None = None, rope: int = 0,
) -> bool:
    """Whether the backward runs the fused one-pass kernel: compact
    causal grid (square blocks, self-attention) AND the modelled
    footprint fits the VMEM the call asks the compiler for. Shared
    verbatim by `flash_schedule` (reported as `bwd_fused`) and the
    `_flash_bwd_kernels` dispatch, so the accounting benches/tests gate
    on is the schedule that actually runs."""
    return _compactable(causal, sq, sk, bq, bk, window) and (
        _fused_vmem_bytes(
            _ring_rows(sq, bq, window), bq, bk, d, itemsize, packed, rope
        )
        <= _FUSED_VMEM_BUDGET
    )


def _bwd_hbm_bytes(
    causal: bool, sq: int, sk: int, bq: int, bk: int, d: int,
    itemsize: int, packed: bool, fused: bool, window: int | None = None,
    rope: int = 0,
) -> int:
    """Modeled backward HBM bytes per (batch·head) grid row, including
    the shared-delta precompute. With a rope part of `rope` dims q and dq
    are d + rope wide and k and dk too (a grid row fetches the one rope
    key's block a column as it fetches its head's k), v, dO and dv stay d. Counts what each kernel's BlockSpec
    pipeline actually moves: blocks whose index map is constant across
    consecutive grid steps are fetched once per row/column (Mosaic
    elides the re-fetch); blocks whose index changes stream once per
    step. DMA elision on the predicated rectangular fallback is not
    modeled (it is not the path this model exists to tune)."""
    steps, _, _ = _grid_steps(causal, sq, sk, bq, bk, window)
    lse_bytes = _lse_bytes_of(sq, packed)
    lse_blk = _lse_block_bytes(bq, packed)
    # delta = rowsum(dO ∘ O): one pass over (o, do), one lse-layout write.
    delta = 2 * sq * d * itemsize + lse_bytes
    if fused:
        # One walk, column-major: k/v resident per column; q/do/lse/delta
        # stream per step; dq+dk+dv written once each.
        return delta + (
            (2 * d + rope) * sk * itemsize  # k, v (once per column)
            + steps * (2 * d + rope) * bq * itemsize  # q, do per step
            + steps * 2 * lse_blk  # lse, delta rows per step
            + (3 * d + 2 * rope) * sq * itemsize  # dq, dk, dv writes
        )
    # Two passes over the same grid: the dq kernel (row-major) streams
    # k/v per step with q/do/lse/delta resident per row; the dkv kernel
    # (column-major) streams q/do/lse/delta per step with k/v resident.
    dq_pass = (
        (2 * d + rope) * sq * itemsize  # q, do (once per row)
        + 2 * lse_bytes  # lse, delta (once per row)
        + steps * (2 * d + rope) * bk * itemsize  # k, v per step
        + (d + rope) * sq * itemsize  # dq write
    )
    dkv_pass = (
        (2 * d + rope) * sk * itemsize  # k, v (once per column)
        + steps * (2 * d + rope) * bq * itemsize  # q, do per step
        + steps * 2 * lse_blk  # lse, delta rows per step
        + (2 * d + rope) * sk * itemsize  # dk, dv writes
    )
    return delta + dq_pass + dkv_pass


def _diag_accounting(
    causal: bool, seq_q: int, seq_k: int, sq: int, sk: int, bq: int, bk: int,
    window: int | None = None,
) -> dict:
    """What the kernels of one grid do with the triangle (with a window:
    its band), per grid row: how many steps run a banded body on the
    diagonal, how many one the window's far edge cuts and how many the
    mask-free body between them (all 0 where the kernels mask by
    position: the rectangular grid, or a padded tail), the diagonal
    band's rows, and the (q, k) pairs the steps compute over the pairs
    attention needs (`seq_q`, `seq_k` are the lengths before padding)."""
    steps, _, compact = _grid_steps(causal, sq, sk, bq, bk, window)
    nq, nk = sq // bq, sk // bk
    needed = seq_q * seq_k
    if causal:  # query r sees keys 0..r (with a window: the last `window`)
        m = min(seq_q, seq_k)
        needed = m * (m + 1) // 2 + (seq_q - m) * seq_k
        if window is not None:
            w = min(window, m)
            needed = w * (w + 1) // 2 + (m - w) * w
    bands = _bands(compact, bq, None if sk == seq_k else seq_k)
    edge = 0
    if bands and window is not None:
        kinds = _window_kinds(bq, bk, window, nq)
        pairs = lambda plan: sum(
            (r.stop - r.start) * (c.stop - c.start) for r, c in plan
        )
        diag = nq
        tile = _window_tile(bq, window)
        edge = sum(nq - d for d in kinds if d)
        interior = steps - diag - edge
        computed = interior * bq * bk + sum(
            (nq - d) * pairs(plan) for d, (plan, _) in kinds.items()
        )
    elif bands:
        tile = bq // bands
        diag, interior = nq, steps - nq
        computed = interior * bq * bk + diag * tile * tile * (
            bands * (bands + 1) // 2
        )
    else:
        tile = diag = interior = 0
        # Steps that run: all of a compact or non-causal grid; of a
        # causal rectangle, those not predicated off.
        ran = steps if compact or not causal else sum(
            min(nk, (i * bq + bq - 1) // bk + 1)
            - (0 if window is None else max(0, (i * bq - window + 1) // bk))
            for i in range(nq)
        )
        computed = ran * bq * bk
    return {
        "diag_steps": diag,
        "edge_steps": edge,
        "interior_steps": interior,
        "diag_tile": tile,
        "computed_pairs_over_needed": computed / needed,
    }


def flash_schedule(
    seq_q: int,
    seq_k: int,
    *,
    block_q: int = 1024,
    block_k: int = 1024,
    causal: bool = True,
    head_dim: int = 128,
    dtype_bytes: int = 2,
    window: int | None = None,
    rope_dim: int = 0,
) -> dict:
    """Static accounting for the schedule `flash_attention` would run.

    This is the single source of truth the kernel impls themselves use
    (`_grid_steps`, `_lse_is_packed`, `_pad_to_tileable`, `_bwd_fused`),
    exposed so benches and regression tests can assert grid-step counts
    and lse/backward HBM bytes without launching a kernel. All
    byte/step figures are per (batch*head) grid row, and the backward's
    kernels walk the forward's grid (the same blocks); `head_dim` and
    `dtype_bytes` (2 = bf16, the training dtype) parameterize the
    backward byte/VMEM models only. `window` as `flash_attention` takes
    it: a window that reaches the whole sequence is the causal call.
    `rope_dim` > 0 is the call with two-part scores (`q_rope`, `k_rope`):
    `head_dim` is then the part a head of q and k, and v's width."""
    window = _checked_window(window, causal, seq_k)
    _checked_rope(rope_dim, window)
    sp_q = _pad_to_tileable(block_q, seq_q)
    sp_k = _pad_to_tileable(block_k, seq_k)
    bq = _pick_block(block_q, sp_q)
    bk = _pick_block(block_k, sp_k)
    steps, rect, compact = _grid_steps(causal, sp_q, sp_k, bq, bk, window)
    packed = _lse_is_packed(sp_q, bq)
    lse_shape = _lse_layout_shape(1, sp_q, packed)[1:]
    fused = _bwd_fused(
        causal, sp_q, sp_k, bq, bk, head_dim, dtype_bytes, packed, window,
        rope_dim,
    )
    bwd_bytes = lambda f: _bwd_hbm_bytes(
        causal, sp_q, sp_k, bq, bk, head_dim, dtype_bytes, packed, f, window,
        rope_dim,
    )
    layout = _head_layout(head_dim)
    return {
        "padded_seq_q": sp_q,
        "padded_seq_k": sp_k,
        "block_q": bq,
        "block_k": bk,
        "compact": compact,
        "grid_steps": steps,
        "rect_grid_steps": rect,
        # Fused one-pass backward: whether it engages at these
        # shapes/dtype, the total bwd grid steps actually walked (one
        # triangle pass fused, two passes otherwise — the single-KV-pass
        # gate), and the modeled HBM bytes per bh row for BOTH paths so
        # benches can assert the fused path's ~halving.
        "bwd_fused": fused,
        "bwd_total_grid_steps": steps if fused else 2 * steps,
        "bwd_fused_vmem_bytes": _fused_vmem_bytes(
            _ring_rows(sp_q, bq, window), bq, bk, head_dim, dtype_bytes,
            packed, rope_dim,
        ),
        "bwd_hbm_bytes": bwd_bytes(fused),
        "bwd_hbm_bytes_fused": bwd_bytes(True),
        "bwd_hbm_bytes_two_pass": bwd_bytes(False),
        "lse_packed": packed,
        "lse_shape": lse_shape,
        "lse_bytes": int(np.prod(lse_shape)) * 4,
        "lse_replicated_bytes": sp_q * _LANES * 4,
        # The diagonal (see `_step_tiles`), in forward and backward alike;
        # with a window the band: `band_steps` block pairs a grid row,
        # `diag_steps` + `edge_steps` + `interior_steps` of them.
        "window": window,
        "band_steps": steps if window is not None and compact else 0,
        **_diag_accounting(
            causal, seq_q, seq_k, sp_q, sp_k, bq, bk, window
        ),
        # Where the kernels read a head (`_specs`), and the head-major
        # transposes round them: q, k, v, o forward, dO, dq, dk, dv
        # backward, or none.
        "layout": layout,
        "transposes_per_call": 0 if layout == "seq_major" else 8,
        # The widths a step's products have: a head's scores contract
        # `qk_dim` + `rope_dim` dims (the second against the one rope key
        # all heads share), its values are `v_dim` wide; the rope
        # operand's own layout (`ROPE_LAYOUT`), None without one.
        "qk_dim": head_dim,
        "rope_dim": rope_dim,
        "v_dim": head_dim,
        "rope_layout": ROPE_LAYOUT if rope_dim else None,
    }


# -- kernels -----------------------------------------------------------------
#
# Every kernel's step is the same arithmetic over a list of (rows, cols)
# tiles of its (bq, bk) block, in static slices; what differs with the
# step's place in the grid is the list. `_step_tiles` picks it:
#
#   the whole block, masked by position   the rectangular grid, and any
#       (`guard=True`)                     call with `kv_len`: the mask is
#                                          computed from (i, j), and a row
#                                          may be masked out altogether.
#   the whole block, no mask, no guard     compact grid, below the
#                                          diagonal (j < i): no score is
#                                          masked and none is -inf.
#   row bands under a static mask          compact grid, on the diagonal
#                                          (j == i): `_diag_plan`.

_WHOLE = slice(None)

# Row bands a diagonal block is cut into, chosen from the block size
# alone: the most of these whose band is still a whole number of lane
# tiles (a band's keys end with its own rows, so its edge has to fall
# on a multiple of 128); 1, the single tile with the static mask, where
# no such cut exists. Two, not four: timed on the v5e at bq = 1024, one
# layer's kernels alone, ms a call with 0 (the body that masks by
# position) / 1 / 2 / 4 bands (my chip run, PR 29; PERF.md §6):
#   (B·H, S) = (128, 2048)  forward 1.922 / 1.896 / 1.846 / 1.955,
#                           fused backward 3.432 / 3.141 / 2.765 / 2.792
#   (32, 8192)              forward 5.217 / 5.084 / 5.038 / 5.148,
#                           fused backward 10.027 / 9.256 / 8.876 / 8.904
# Four bands compute 10 of 16 sub-tiles against two bands' 3 of 4, but
# their (256, ·) matmuls reload the MXU's weights four times as often
# for the same rows, and the scheduler packs them no tighter.
_DIAG_BANDS = (2, 1)


def _diag_bands(bq: int) -> int:
    for n in _DIAG_BANDS:
        if bq % (n * _LANES) == 0:
            return n
    return 1


def _bands(compact: bool, bq: int, kv_len: int | None) -> int:
    """Bands of a diagonal block where a kernel knows the diagonal: on
    the compact grid, unless a padded tail (`kv_len`) can mask keys the
    triangle does not. 0 elsewhere: the body that masks by position."""
    return _diag_bands(bq) if compact and kv_len is None else 0


def _diag_plan(bq: int, n: int):
    """The tiles of a block ON the diagonal of a square grid: band r of
    `n` (rows [r·t, (r+1)·t), t = bq/n) against keys [0, (r+1)·t) and
    nothing beyond, under `_band_mask`. n(n+1)/2 of the block's n²
    (t, t) sub-tiles are computed."""
    t = bq // n
    return [
        (slice(r * t, (r + 1) * t), slice(0, (r + 1) * t)) for r in range(n)
    ]


def _band_mask(s, rows=None, cols=None):
    """The causal mask of a band of `_diag_plan`: its scores' last
    column is its last row's own position, so row a keeps columns up to
    a + (columns - rows). Static: on the diagonal of a square grid the
    mask is the same constant in every block."""
    rows, cols = s.shape
    keep = lax.broadcasted_iota(jnp.int32, s.shape, 0) + (cols - rows) >= (
        lax.broadcasted_iota(jnp.int32, s.shape, 1)
    )
    return jnp.where(keep, s, _NEG_INF)


# Rows of a band of a block that one of a window's edges cuts (the
# diagonal block, and the block or two where the band ends): the first of
# these cuts of the block whose band is no taller than a quarter of the
# window, else the finest, in whole lane tiles. A band runs its matmuls
# against the keys its rows can see and no others (`_window_plan`), so the
# finer the bands the fewer pairs computed for nothing, and the more
# often the MXU's weights are reloaded for as many rows. Timed on the v5e
# at (B, S, H) = (1, 8192, 72 over 8 K/V), d = 128, window 512, ms a
# call, forward / forward + fused backward (my chip run, PR 34):
#   blocks of 1024, bands of 128 / 256 / 512 / 1024 rows (1.25 / 1.5 /
#   2.0 / 2.97 times the band's pairs computed):
#       4.36 / 5.33 / 5.28 / 4.88        10.10 / 11.09 / 11.53 / 12.80
#   blocks of 512, bands of 128 / 256 / 512:
#       5.21 / 6.92 / 6.94               13.20 / 14.82 / 15.28
#   blocks of 256, bands of 128 / 256:  10.26 / 10.99    22.89 / 23.45
# Unlike the causal diagonal (`_DIAG_BANDS`: two bands beat four) the
# window's edge blocks are most of its steps, so the finest cut wins;
# smaller blocks only add grid steps. The global layers' call at 48
# heads beside it: 8.28 / 22.38.
_WINDOW_BANDS = (2, 4, 8)


def _window_tile(bq: int, window: int) -> int:
    cuts = [n for n in _WINDOW_BANDS if bq % (n * _LANES) == 0]
    for n in cuts:
        if bq // n <= max(_LANES, window // 4):
            return bq // n
    return bq // cuts[-1] if cuts else bq


def _window_plan(bq: int, bk: int, t: int, delta: int, window: int):
    """The tiles of a block whose first query stands `delta` positions
    after its first key, under a window: band r (rows [r·t, (r+1)·t))
    against the keys its rows see, key > query - window and key <= query,
    widened to whole lane tiles; a band that sees none is left out."""
    unit = _LANES if bk % _LANES == 0 else bk
    plan = []
    for r0 in range(0, bq, t):
        lo = max(0, delta + r0 - window + 1)
        hi = min(bk, delta + r0 + t)
        if lo < hi:
            plan.append((
                slice(r0, r0 + t),
                slice(lo // unit * unit, min(bk, -(-hi // unit) * unit)),
            ))
    return plan


def _window_mask(delta: int, window: int):
    """The mask of a tile of `_window_plan`: s[a, c] is query
    delta + rows.start + a against key cols.start + c, kept where
    0 <= query - key < window. Static, and only the side that cuts the
    tile is built."""

    def mask(s, rows, cols):
        off = delta + rows.start - cols.start  # query - key at s[0, 0]
        a = lax.broadcasted_iota(jnp.int32, s.shape, 0)
        c = lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = None
        if s.shape[1] - 1 > off:  # a key after a query
            keep = a + off >= c
        if off + s.shape[0] - 1 >= window:  # a key the window has left
            far = a + (off - window) < c
            keep = far if keep is None else keep & far
        return s if keep is None else jnp.where(keep, s, _NEG_INF)

    return mask


def _window_kinds(bq: int, bk: int, window: int, nq: int) -> dict:
    """{d: (plan, guard)} for the block distances d = i - j of a band
    that an edge cuts: 0, the diagonal, and those the window's far edge
    crosses; every distance between them is a whole block with nothing
    masked. `guard`: a row of a tile may see no key of it."""
    t = _window_tile(bq, window)
    kinds = {}
    for d in range(_band_reach(window, nq, bq) + 1):
        if d and (d + 1) * bq <= window:
            continue  # below the diagonal and wholly inside the window
        plan = _window_plan(bq, bk, t, d * bq, window)
        guard = any(
            (r.stop - r.start) + (d * bq + r.start - c.start) - window
            >= c.stop - c.start
            for r, c in plan
        )
        kinds[d] = (plan, guard)
    return kinds


def _step_tiles(
    i, j, run, tiles, *, causal, bq, bk, kv_len, compact, window=None, nq=0
):
    """Run `tiles(plan, mask, guard)` for grid step (i, j): by the
    step's place where the kernel can tell it from the grid (`_bands`),
    else the whole block masked by position, under `run`."""
    whole = [(_WHOLE, _WHOLE)]
    bands = _bands(compact, bq, kv_len)
    if bands and window is not None:
        # The band's three kinds of step, told apart by the distance
        # from the diagonal: cut by the diagonal (and, under a window
        # shorter than a block, by the far edge too), cut by the far
        # edge, or between them.
        kinds = _window_kinds(bq, bk, window, nq)
        inner = [
            d for d in range(1, _band_reach(window, nq, bq) + 1)
            if d not in kinds
        ]
        if inner:
            pl.when((i - j >= inner[0]) & (i - j <= inner[-1]))(
                lambda: tiles(whole, None, False)
            )
        for d, (plan, guard) in kinds.items():
            pl.when(i - j == d)(functools.partial(
                tiles, plan, _window_mask(d * bq, window), guard
            ))
        return
    if bands:
        pl.when(j < i)(lambda: tiles(whole, None, False))
        pl.when(j == i)(
            lambda: tiles(_diag_plan(bq, bands), _band_mask, False)
        )
        return

    def mask(s, rows=None, cols=None):
        if causal:
            s = _causal_mask(s, i, j, bq, bk)
        if window is not None:
            s = _window_pos_mask(s, i, j, bq, bk, window)
        if kv_len is not None:
            s = _kv_tail_mask(s, j, bk, kv_len)
        return s

    masked = causal or kv_len is not None
    pl.when(run)(lambda: tiles(whole, mask if masked else None, True))


def _dot_nt(a, b):
    return lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def _dot_nn(a, b):
    return lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _dot_tn(a, b):
    return lax.dot_general(
        a, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


# -- two-part scores ----------------------------------------------------------
#
# A kernel's q and k are tuples of refs, the PARTS of a head's scores:
# the part a head alone, and with latent attention (DeepSeek-V2's MLA) a
# rope part besides, whose key is ONE for all heads, so that
# s = q·kᵀ + q_rope·k_ropeᵀ. The bodies sum a product a part wherever
# they formed one: the scores, dq and dk (each part its own accumulator
# and output); v, o, dO and dv know nothing of it. With one part the
# loops run once and the traced body is the one it was.


def _parts(refs, scale=None):
    """The float32 blocks of an operand's parts (q's pre-scaled)."""
    blocks = tuple(ref[0].astype(jnp.float32) for ref in refs)
    if scale is None:
        return blocks
    return tuple(blk * scale for blk in blocks)


def _sum_parts(dot, a, b):
    """dot(a₀, b₀) + dot(a₁, b₁) + ...: the scores of two-part q and k."""
    s = dot(a[0], b[0])
    for x, y in zip(a[1:], b[1:]):
        s = s + dot(x, y)
    return s


def _fwd_tiles(
    q_refs, k_refs, v_ref, m_scr, l_scr, acc, plan, mask, guard, scale
):
    """One online-softmax update of its rows of (m, l, acc) a tile."""
    q_blk = _parts(q_refs, scale)
    k_blk = _parts(k_refs)
    v_blk = v_ref[0].astype(jnp.float32)
    for rows, cols in plan:
        s = _sum_parts(
            _dot_nt, [q[rows] for q in q_blk], [k[cols] for k in k_blk]
        )
        if mask is not None:
            s = mask(s, rows, cols)
        m_prev = m_scr[rows, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # Rows with every key masked so far keep m=-inf; exp(-inf - -inf)
        # is nan, so the correction needs the guard, and P too wherever a
        # whole row can be masked (`guard`). On the compact grid no row
        # is: below the diagonal nothing is masked, and on it every row
        # has its own position, so m is finite and exp(-inf - m) is 0.
        safe_m = jnp.where(m_new == _NEG_INF, 0.0, m_new)
        corr = jnp.where(m_prev == _NEG_INF, 0.0, jnp.exp(m_prev - safe_m))
        # The accumulators are scaled before P is formed: the order the
        # v5e's scheduler packs tightest (8,191 -> 7,423 bundles a step
        # for the same operations; PERF.md §6, PR 29).
        l_new = l_scr[rows, :1] * corr
        acc_new = acc[rows, :] * corr
        p = jnp.exp(s - safe_m)
        if guard:
            p = jnp.where(s == _NEG_INF, 0.0, p)
        lanes = (s.shape[0], _LANES)
        l_scr[rows, :] = jnp.broadcast_to(
            l_new + jnp.sum(p, axis=-1, keepdims=True), lanes
        )
        acc[rows, :] = acc_new + _dot_nn(p, v_blk[cols])
        m_scr[rows, :] = jnp.broadcast_to(m_new, lanes)


def _fwd_body(
    i, j, first, last, run, q_refs, k_refs, v_ref, o_ref, lse_ref,
    m_scr, l_scr, acc,
    *, scale: float, causal: bool, bq: int, bk: int,
    kv_len: int | None, packed: bool, compact: bool = False,
    window: int | None = None, nq: int = 0,
):
    @pl.when(first)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc[:] = jnp.zeros_like(acc)

    _step_tiles(
        i, j, run,
        functools.partial(
            _fwd_tiles, q_refs, k_refs, v_ref, m_scr, l_scr, acc, scale=scale
        ),
        causal=causal, bq=bq, bk=bk, kv_len=kv_len, compact=compact,
        window=window, nq=nq,
    )

    @pl.when(last)
    def _finalize():
        l = l_scr[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc[:] / safe_l).astype(o_ref.dtype)
        lse_rep = jnp.where(
            m_scr[:] == _NEG_INF,
            _NEG_INF,
            m_scr[:] + jnp.log(jnp.where(l_scr[:] == 0.0, 1.0, l_scr[:])),
        )
        lse_ref[0] = _pack_rows(lse_rep) if packed else lse_rep


def _rect_run(i, j, kw):
    """Whether rectangular grid step (i, j) holds a pair attention needs:
    not above the diagonal, and not below the window's band."""
    if not kw["causal"]:
        return True
    run = j * kw["bk"] <= i * kw["bq"] + kw["bq"] - 1
    if kw.get("window") is not None:
        run &= (j + 1) * kw["bk"] - 1 > i * kw["bq"] - kw["window"]
    return run


def _band_first(i, window, nq: int, bq: int):
    """The first k block of q block i's row on the compact grid."""
    if window is None:
        return 0
    return jnp.maximum(i - _band_reach(window, nq, bq), 0)


def _band_last(j, window, nq: int, bq: int):
    """The last q block of k block j's column on the compact grid."""
    if window is None:
        return nq - 1
    return jnp.minimum(j + _band_reach(window, nq, bq), nq - 1)


def _qk_refs(refs, n_in: int, rope: bool):
    """A kernel's refs with q's and k's parts gathered: `refs` are its
    `n_in` inputs (q, k, ... ), then with `rope` the pair (q_rope,
    k_rope), then outputs and scratch -> (q_refs, k_refs, the inputs
    after k, outputs and scratch)."""
    (q_ref, k_ref, *ins), rest = refs[:n_in], refs[n_in:]
    if not rope:
        return (q_ref,), (k_ref,), ins, rest
    (qr_ref, kr_ref), rest = rest[:2], rest[2:]
    return (q_ref, qr_ref), (k_ref, kr_ref), ins, rest


def _fwd_kernel(*refs, rope: bool = False, **kw):
    """Rectangular grid: (bh, nq, nk), k innermost; causal blocks above
    the diagonal are predicated off (they still cost a grid step — the
    compact kernel below is the one that doesn't pay them)."""
    q_refs, k_refs, (v_ref,), rest = _qk_refs(refs, 3, rope)
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)
    _fwd_body(
        i, j, j == 0, j == nk - 1, _rect_run(i, j, kw),
        q_refs, k_refs, v_ref, *rest, **kw
    )


def _fwd_kernel_compact(rows_ref, cols_ref, *refs, rope: bool = False, **kw):
    """Compact causal grid: (bh, T) over lower-triangular block pairs;
    the scalar-prefetched tables recover (i, j). Every enumerated block
    runs — skipped blocks simply don't exist in the grid."""
    q_refs, k_refs, (v_ref,), rest = _qk_refs(refs, 3, rope)
    t = pl.program_id(1)
    i = rows_ref[t]
    j = cols_ref[t]
    first = _band_first(i, kw["window"], kw["nq"], kw["bq"])
    _fwd_body(
        i, j, j == first, j == i, True,
        q_refs, k_refs, v_ref, *rest, compact=True, **kw,
    )


def _delta_kernel(o_ref, do_ref, delta_ref, *, packed: bool):
    """delta = rowsum(dO ∘ O), computed ONCE per backward and shared by
    the dq and dkv kernels (each previously recomputed it per grid row,
    re-streaming dO and O from HBM to do so)."""
    delta = jnp.sum(
        do_ref[0].astype(jnp.float32) * o_ref[0].astype(jnp.float32),
        axis=-1,
        keepdims=True,
    )
    rep = jnp.broadcast_to(delta, (delta.shape[0], _LANES))
    delta_ref[0] = _pack_rows(rep) if packed else rep


def _bwd_tiles(
    q_refs, k_refs, v_ref, do_ref, lse_ref, delta_ref, plan, mask, guard,
    scale, packed, dk_acc=None, dv_acc=None, dq=None,
):
    """The (s, p, ds) recurrence, computed once a tile of `plan` and fed
    to whichever gradients the kernel accumulates: dV += pᵀ·dO and dK +=
    dSᵀ·(scale·q) on the tile's key rows of `dv_acc` / `dk_acc`, and
    `dq(rows, dS·K)`. q is loaded pre-scaled, so dK carries the
    1/sqrt(d) factor and dq takes it once more where the kernel writes
    it out. `dk_acc` is a tuple, an accumulator a part of k, and `dq`
    gets a tuple, a product a part."""
    q_blk = _parts(q_refs, scale)
    k_blk = _parts(k_refs)
    v_blk = v_ref[0].astype(jnp.float32)
    do_blk = do_ref[0].astype(jnp.float32)
    lse_blk = _read_rows(lse_ref[0], packed)
    delta_blk = _read_rows(delta_ref[0], packed)
    for rows, cols in plan:
        q = [blk[rows] for blk in q_blk]
        k = [blk[cols] for blk in k_blk]
        do = do_blk[rows]
        s = _sum_parts(_dot_nt, q, k)
        if mask is not None:
            s = mask(s, rows, cols)
        # A masked score is -inf and lse is finite wherever a row has a
        # key, so exp gives 0; `guard` covers the rows that have none
        # (lse = -inf too: nan).
        p = jnp.exp(s - lse_blk[rows])
        if guard:
            p = jnp.where(s == _NEG_INF, 0.0, p)
        if dv_acc is not None:
            dv_acc[cols, :] = dv_acc[cols, :] + _dot_tn(p, do)
        ds = p * (_dot_nt(do, v_blk[cols]) - delta_blk[rows])
        if dk_acc is not None:
            for acc, part in zip(dk_acc, q):
                acc[cols, :] = acc[cols, :] + _dot_tn(ds, part)
        if dq is not None:
            dq(rows, tuple(_dot_nn(ds, part) for part in k))


def _halves(refs):
    """Outputs then scratch, a ref a part each: (outputs, scratch)."""
    return refs[: len(refs) // 2], refs[len(refs) // 2:]


def _dq_body(
    i, j, first, last, run, q_refs, k_refs, ins, dq_refs, dq_accs,
    *, scale: float, causal: bool, bq: int, bk: int,
    kv_len: int | None, packed: bool, compact: bool = False,
    window: int | None = None, nq: int = 0,
):
    @pl.when(first)
    def _init():
        for dq_acc in dq_accs:
            dq_acc[:] = jnp.zeros_like(dq_acc)

    def dq(rows, dq_rows):
        for dq_acc, part in zip(dq_accs, dq_rows):
            dq_acc[rows, :] = dq_acc[rows, :] + part

    _step_tiles(
        i, j, run,
        functools.partial(
            _bwd_tiles, q_refs, k_refs, *ins,
            scale=scale, packed=packed, dq=dq,
        ),
        causal=causal, bq=bq, bk=bk, kv_len=kv_len, compact=compact,
        window=window, nq=nq,
    )

    @pl.when(last)
    def _finalize():
        for dq_ref, dq_acc in zip(dq_refs, dq_accs):
            dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _dq_kernel(*refs, rope: bool = False, **kw):
    """Inputs q, k, v, dO, lse, delta (and the rope pair); a dq output
    and an accumulator a part."""
    q_refs, k_refs, ins, rest = _qk_refs(refs, 6, rope)
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)
    _dq_body(
        i, j, j == 0, j == nk - 1, _rect_run(i, j, kw),
        q_refs, k_refs, ins, *_halves(rest), **kw,
    )


def _dq_kernel_compact(rows_ref, cols_ref, *refs, rope: bool = False, **kw):
    q_refs, k_refs, ins, rest = _qk_refs(refs, 6, rope)
    t = pl.program_id(1)
    i = rows_ref[t]
    j = cols_ref[t]
    first = _band_first(i, kw["window"], kw["nq"], kw["bq"])
    _dq_body(
        i, j, j == first, j == i, True,
        q_refs, k_refs, ins, *_halves(rest), compact=True, **kw,
    )


def _dkv_refs(refs):
    """(dk, dv[, dk_rope]) outputs then as many accumulators ->
    (dk outputs a part, dv output, dk accumulators a part, dv's)."""
    outs, accs = _halves(refs)
    return (outs[0], *outs[2:]), outs[1], (accs[0], *accs[2:]), accs[1]


def _dkv_body(
    i, j, first, last, run, q_refs, k_refs, ins, dk_refs, dv_ref, dk_accs,
    dv_acc,
    *, scale: float, causal: bool, bq: int, bk: int,
    kv_len: int | None, packed: bool, compact: bool = False,
    window: int | None = None, nq: int = 0,
):
    @pl.when(first)
    def _init():
        for dk_acc in dk_accs:
            dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    _step_tiles(
        i, j, run,
        functools.partial(
            _bwd_tiles, q_refs, k_refs, *ins,
            scale=scale, packed=packed, dk_acc=dk_accs, dv_acc=dv_acc,
        ),
        causal=causal, bq=bq, bk=bk, kv_len=kv_len, compact=compact,
        window=window, nq=nq,
    )

    @pl.when(last)
    def _finalize():
        # dK = Σ dSᵀ·(scale·q); q was loaded pre-scaled, so the accumulator
        # already carries the 1/sqrt(d) factor. dV is scale-free.
        for dk_ref, dk_acc in zip(dk_refs, dk_accs):
            dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _dkv_kernel(*refs, rope: bool = False, **kw):
    """Inputs as `_dq_kernel`'s; outputs dk, dv (and dk_rope), then as
    many accumulators."""
    q_refs, k_refs, ins, rest = _qk_refs(refs, 6, rope)
    j = pl.program_id(1)  # k block (outer)
    i = pl.program_id(2)  # q block (inner)
    nq = pl.num_programs(2)
    _dkv_body(
        i, j, i == 0, i == nq - 1, _rect_run(i, j, kw),
        q_refs, k_refs, ins, *_dkv_refs(rest), **kw,
    )


def _dkv_kernel_compact(rows_ref, cols_ref, *refs, rope: bool = False, **kw):
    """Column-major compact traversal: for each k block j, q blocks
    i = j..nq-1 are contiguous, so dk/dv accumulate across exactly the
    blocks that exist below the diagonal."""
    q_refs, k_refs, ins, rest = _qk_refs(refs, 6, rope)
    t = pl.program_id(1)
    i = rows_ref[t]
    j = cols_ref[t]
    last = _band_last(j, kw["window"], kw["nq"], kw["bq"])
    _dkv_body(
        i, j, i == j, i == last, True,
        q_refs, k_refs, ins, *_dkv_refs(rest), compact=True, **kw,
    )


def _dqkv_kernel_fused(
    rows_ref, cols_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref, dk_ref, dv_ref, dq_ring, dk_acc, dv_acc, **kw,
):
    """Fused one-pass backward over the compact causal grid, column-major
    (for each kv block j, q blocks i = j..nq-1 are contiguous; under a
    window i = j..j+reach, the band's column).

    Each step computes the (s, p, ds) recurrence ONCE and feeds all
    three gradients: dk/dv accumulate in per-column scratch exactly like
    `_dkv_kernel_compact`, and the step's dq contribution ds·K lands in
    slot i of the dq ring. Every q row is live from column 0 and retires
    in row order — row j's last contribution is column j's diagonal
    step (the column's FIRST step, since i ascends from j) — so slot j
    flushes to the dq output block when column j completes. The three
    output BlockSpecs all ride the column index, which is constant
    within a column: one HBM write per output block.

    Input streams are q/do/lse/delta (per step) and k/v (once per
    column). O is NOT an input — delta carries the rowsum(dO ∘ O)
    precompute (shared-delta contract, see `_delta_kernel`).

    Under a window a row is live for the reach + 1 columns its band
    crosses and no longer, so the ring has that many slots (`_ring_rows`),
    row i in slot i mod (reach + 1): row j + reach enters at column j
    into the slot row j - 1 left when column j - 1 flushed it. A row's
    first column is where the band ends, and not every band of its rows
    has work there (`_window_plan`), so the slot is zeroed as the row
    enters and every contribution accumulates.

    The body is `_dqkv_fused_body`, over q, k, dq, dk, the ring and dk's
    accumulator as tuples of one part; `_dqkv_kernel_fused_mla` hands it
    two."""
    _dqkv_fused_body(
        rows_ref, cols_ref, (q_ref,), (k_ref,),
        (v_ref, do_ref, lse_ref, delta_ref), (dq_ref,), (dk_ref,), dv_ref,
        (dq_ring,), (dk_acc,), dv_acc, **kw,
    )


def _dqkv_kernel_fused_mla(
    rows_ref, cols_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    qr_ref, kr_ref, dq_ref, dk_ref, dv_ref, dqr_ref, dkr_ref,
    dq_ring, dk_acc, dv_acc, dqr_ring, dkr_acc, **kw,
):
    """`_dqkv_kernel_fused` with two-part scores: the rope pair last of
    the inputs, its two gradients last of the outputs, its dq ring and dk
    accumulator last of the scratch."""
    _dqkv_fused_body(
        rows_ref, cols_ref, (q_ref, qr_ref), (k_ref, kr_ref),
        (v_ref, do_ref, lse_ref, delta_ref), (dq_ref, dqr_ref),
        (dk_ref, dkr_ref), dv_ref, (dq_ring, dqr_ring), (dk_acc, dkr_acc),
        dv_acc, **kw,
    )


def _dqkv_fused_body(
    rows_ref, cols_ref, q_refs, k_refs, ins, dq_refs, dk_refs, dv_ref,
    dq_rings, dk_accs, dv_acc,
    *, scale: float, causal: bool, bq: int, bk: int,
    kv_len: int | None, packed: bool, nq: int, window: int | None = None,
):
    t = pl.program_id(1)
    i = rows_ref[t]
    j = cols_ref[t]
    first = i == j  # column j's first step (the diagonal block)
    last = i == _band_last(j, window, nq, bq)  # column j's last step
    slots = _ring_rows(nq * bq, bq, window) // bq
    slot_of = lambda row: row if window is None else lax.rem(row, slots)

    @pl.when(first)
    def _init():
        for dk_acc in dk_accs:
            dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def dq(rows, dq_rows):
        # dq contribution of column j to (a band of) row block i; q was
        # loaded pre-scaled, so the ring carries the 1/sqrt(d) factor
        # once more at flush (same algebra as `_dq_body`'s finalize).
        start = rows.start or 0
        slot = pl.ds(slot_of(i) * bq + start, (rows.stop or bq) - start)
        if window is not None:
            for dq_ring, part in zip(dq_rings, dq_rows):
                dq_ring[slot, :] = dq_ring[slot, :] + part
            return

        @pl.when(j == 0)
        def _seed():
            # Column 0 is every row's first contribution — a store, not
            # an accumulate, so the ring never needs a zeroing pass.
            for dq_ring, part in zip(dq_rings, dq_rows):
                dq_ring[slot, :] = part

        @pl.when(j > 0)
        def _accum():
            for dq_ring, part in zip(dq_rings, dq_rows):
                dq_ring[slot, :] = dq_ring[slot, :] + part

    if window is not None:
        @pl.when(j == _band_first(i, window, nq, bq))
        def _enter():
            for dq_ring in dq_rings:
                dq_ring[pl.ds(slot_of(i) * bq, bq), :] = jnp.zeros(
                    (bq, dq_ring.shape[1]), dq_ring.dtype
                )

    _step_tiles(
        i, j, True,
        functools.partial(
            _bwd_tiles, q_refs, k_refs, *ins,
            scale=scale, packed=packed, dk_acc=dk_accs, dv_acc=dv_acc, dq=dq,
        ),
        causal=causal, bq=bq, bk=bk, kv_len=kv_len, compact=True,
        window=window, nq=nq,
    )

    @pl.when(last)
    def _flush():
        for dk_ref, dk_acc in zip(dk_refs, dk_accs):
            dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)
        # Row j retired at this column's diagonal step; its completed
        # slot flushes into the column-indexed dq output block.
        for dq_ref, dq_ring in zip(dq_refs, dq_rings):
            dq_ref[0] = (
                dq_ring[pl.ds(slot_of(j) * bq, bq), :] * scale
            ).astype(dq_ref.dtype)


# -- clamped index maps (rectangular fallback only) --------------------------


def _clamp_j(i, j, bq: int, bk: int, causal: bool):
    """K-block index for rectangular grid step (i, j). Under causality,
    blocks strictly above the diagonal are compute-skipped (`pl.when`),
    but Pallas would still DMA their K/V tiles; clamping the index to
    the diagonal makes every skipped step re-address the block the
    previous step already holds, so Mosaic elides the copy. The compact
    grid doesn't enumerate those steps at all — this clamp only matters
    for the non-compacted fallback."""
    if not causal:
        return j
    return jnp.minimum(j, (i * bq + bq - 1) // bk)


def _clamp_i(i, j, bq: int, bk: int, causal: bool):
    """Q-block index for the rectangular dk/dv grid (i inner, ascending):
    steps below the first unmasked q block are compute-skipped; clamping
    them onto that first block elides their DMAs the same way."""
    if not causal:
        return i
    return jnp.maximum(i, (j * bk) // bq)


# -- block specs: where a head's blocks lie ----------------------------------
#
# A kernel's grid rows are (batch·head), head-minor, and every block is
# (1, blk, d). What a row indexes is an array [rows / heads, S, heads·d]:
#
#   seq_major   heads = H: q, o, dO, dq are [B, S, H·d] and k, v, dk, dv
#               [B, S, Hkv·d], the arrays the projections write (a
#               reshape of [B, S, H, d], no copy). Grid row g = b·H + h
#               addresses column block h of batch row b, fetched by a
#               strided DMA: blk pieces of d lanes, H·d apart. Legal on
#               the chip where d is whole lanes (`_head_layout`).
#   head_major  heads = 1: the heads are folded into the batch,
#               [B·H, S, d], by a transpose outside the kernels. The same
#               builder at heads = 1: what any other d runs, and the ring
#               path, whose rotating chunks are flat already.
#
# The block is the same bytes in VMEM either way, so the kernel bodies,
# grids, tables, scratch and the VMEM model know nothing of the layout.
# The lse and delta arrays are the kernels' own residuals, which nobody
# transposes: always [B·H, ...], row g.
#
# What the strided fetch costs: a (1024, 128) bf16 block out of
# [S, H·128] is 64 tiles of 4 KB, H tiles apart, where head_major's is
# one run of 256 KB. One layer's kernels alone on the v5e, the same
# build in both layouts, ms a call head_major / seq_major (my chip run,
# PR 31, 2026-09-28; bf16, d = 128, 1024-blocks, min of 4 rounds of 25;
# head_major equals the parent's to 0.3 %):
#   (B, S, H) = (8, 2048, 16)   forward 1.841 / 1.853, fused backward
#                               2.756 / 2.760, delta 0.240 / 0.261
#   (2, 8192, 16)               forward 5.023 / 5.068, backward 8.857 /
#                               8.890, delta 0.243 / 0.263
#   (2, 8192, 8 over 2 K/V)     forward 2.538 / 2.563, backward with the
#                               group's sum 4.440 / 4.530
#   (4, 2048, 8)                forward 0.487 / 0.488, backward 0.717 /
#                               0.720
# 0.1-0.9 % with equal heads, 2 % with grouped heads: the kernels are
# bound by their matmuls, not by how a block arrives.


def _head_layout(d: int) -> str:
    """The layout `flash_attention` hands the kernels, from the head size
    alone: a head is a legal column block of [B, S, H·d] when d is a
    whole number of lanes. Any other d folds the heads into the batch by
    transposes; a head of 192 = 128 + 64 (latent attention) would, eight
    a call, and is not handed over as one operand but as two parts, 128
    a head beside a rope operand of 64 (`ROPE_LAYOUT`)."""
    return "seq_major" if d % _LANES == 0 else "head_major"


# How the 64-wide rope part of q reaches the kernels where the 128-wide
# operands are read seq_major. A head's 64 lanes are no legal column
# block of [B, S, H·64], so the operand is folded head-major, [B·H, S, 64]:
# one transpose in, one out for dq_rope. Padding it to 128 lanes a head
# ([B, S, H·128], the upper 64 zeros, dq sliced back) was built and timed
# beside it; two heads a 128-lane column block was not built: the two
# heads' grid rows would share one dq_rope output block, which a row
# writes whole. VMEM and the MXU see 128 lanes either way (a 64-lane
# contraction fills half the array). On the v5e at (B, S, H) = (1, 8192,
# 32), 128 + 64 over 128, bf16, the kernels with the transposes or the
# pad round them, ms a call forward / forward + backward (my chip run,
# PR 39; min of 3 rounds of 20): head-major 6.73 / 20.78, padded 6.84 /
# 20.92; the one-part call at d = 128 beside them 5.07 / 14.09 (a third
# product forward and three more backward, each on a half-filled array:
# x 1.33 and x 1.56).
ROPE_LAYOUT = "head_major"


def _kv_row(group: int):
    """Grid row of q (batch·head, head-minor) -> row of k/v. With grouped
    K/V heads `group` query heads share one: q row b·H + h reads k/v row
    b·H/group + h // group = (b·H + h) // group, so the kernels never see
    a repeated copy of K or V. Equal heads keep the identity map."""
    if group == 1:
        return lambda g: g
    return lambda g: g // group


def _head_block(heads: int):
    """(row, idx) -> index of block idx along the sequence of head-row
    `row`, in an array [rows / heads, S, heads·d] of (1, blk, d) blocks."""
    if heads == 1:
        return lambda row, idx: (row, idx, 0)
    return lambda row, idx: (row // heads, idx, row % heads)


def _head_counts(q, k, heads: int):
    """(d, grid rows, kv heads in a row of k, query heads a kv head) of
    q [Bq, S, heads·d] over k [Bk, S, kv_heads·d]."""
    d = q.shape[2] // heads
    rows = q.shape[0] * heads
    kv_heads = k.shape[2] // d
    return d, rows, kv_heads, rows // (k.shape[0] * kv_heads)


def _specs(heads: int, kv_heads: int, group: int, d: int):
    """The one builder of the kernels' block specs: (q_spec, kv_spec,
    stat_spec). Each takes the block's rows (for the lse / delta
    statistics the block's whole shape) and `idx`, the map from the
    grid's indices after the leading row (and any prefetched tables) to
    the block's number along the sequence."""
    q_at, kv_at = _head_block(heads), _head_block(kv_heads)
    kv_row = _kv_row(group)

    def q_spec(blk, idx):
        return pl.BlockSpec((1, blk, d), lambda g, *a: q_at(g, idx(*a)))

    def kv_spec(blk, idx):
        return pl.BlockSpec(
            (1, blk, d), lambda g, *a: kv_at(kv_row(g), idx(*a))
        )

    def stat_spec(block, idx):
        return pl.BlockSpec(block, lambda g, *a: (g, idx(*a), 0))

    return q_spec, kv_spec, stat_spec


def _sum_groups(dk, like, d: int):
    """Per-query-head dK or dV, as the kernels write them in q's layout
    [Bq, S, heads·d], -> per-kv-head in `like`'s [Bk, S, kv_heads·d],
    added in float32 in the group's order. A group's partials are
    adjacent rows (head_major: a split of the leading axis) or adjacent
    column blocks (seq_major), taken as whole-lane slices: splitting the
    lanes into [.., kv_heads, group, d] would be a relayout of all of dK."""
    if dk.shape == like.shape:
        return dk
    (bq, s, w), (bk, _, wk) = dk.shape, like.shape
    group = (bq // bk) * (w // wk)
    if w == wk:
        rows = dk.reshape(bk, group, s, w)
        partials = [[rows[:, g] for g in range(group)]]
    else:
        partials = [
            [
                lax.slice_in_dim(dk, i * d, (i + 1) * d, axis=2)
                for i in range(j * group, (j + 1) * group)
            ]
            for j in range(wk // d)
        ]
    return jnp.concatenate(
        [
            functools.reduce(
                jnp.add, (x.astype(jnp.float32) for x in head)
            ).astype(like.dtype)
            for head in partials
        ],
        axis=-1,
    )


# -- pallas_call wrappers ----------------------------------------------------


def _kernel_name(
    base: str, grid: str, window: int | None, rope: bool = False
) -> str:
    """A `pallas_call`'s name: what a jaxpr and a device trace tell the
    schedules apart by. The causal calls keep `<base>_<grid>`; a window's
    say so (`flash_fwd_window`, `flash_bwd_window_fused`,
    `flash_dq_window`, `flash_dkv_window`, and `..._window_rect` off the
    compact grid), so a trace tells a model's window layers' calls from
    its global layers'; the calls with two-part scores are marked `mla`
    the same way (`flash_fwd_mla`, `flash_bwd_mla_fused`, `flash_dq_mla`,
    `flash_dkv_mla`, `..._mla_rect`)."""
    if window is None and not rope:
        return f"{base}_{grid}"
    mark = "mla" if rope else "window"
    return f"{base}_{mark}" + ("" if grid == "compact" else f"_{grid}")


def _checked_rope(rope_dim: int, window: int | None) -> None:
    """Two-part scores under a window were never built: refused with
    their numbers."""
    if rope_dim and window is not None:
        raise ValueError(
            f"flash attention: a rope part of {rope_dim} dims under a "
            f"window of {window} keys: two-part scores run the causal "
            "triangle only"
        )


def _rope_specs(rope, rows: int):
    """(q_rope's spec builder, k_rope's, the block's lanes) for the pair
    `rope` = (q_rope [B·H, S, r], k_rope [B, S, r]) of a call of `rows`
    grid rows: `_specs` with one K/V head that every query head of a
    batch row reads."""
    _, k_rope = rope
    r = k_rope.shape[2]
    q_spec, k_spec, _ = _specs(1, 1, rows // k_rope.shape[0], r)
    return q_spec, k_spec, r


def _checked_window(window, causal: bool, seq_k: int) -> int | None:
    """`window` as the kernels take it: None for a window that reaches
    every earlier key (the causal call, the same program), else at least
    the query's own position, and only under the causal mask."""
    if window is None:
        return None
    if window < 1 or not causal:
        raise ValueError(
            f"flash attention: a window of {window} key(s) "
            f"(causal={causal}): a window counts the query's own position, "
            "so it is at least 1, and looks back only"
        )
    return None if window >= seq_k else int(window)


_T_ROW = lambda t, rows, cols: rows[t]  # compact grids: step t's q block
_T_COL = lambda t, rows, cols: cols[t]  # ... and its k block


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "block_q", "block_k", "interpret", "kv_len", "packed",
        "heads", "window", "scale",
    ),
)
def _flash_fwd_impl(
    q, k, v, causal, block_q, block_k, interpret, kv_len=None, packed=False,
    heads=1, window=None, rope=None, scale=None,
):
    """q [Bq, S, heads·d] over k, v [Bk, S, kv_heads·d] (`_specs`) ->
    (o in q's layout, lse [Bq·heads, ...] in the kernel lse layout).
    `rope` = (q_rope, k_rope) adds the second part of the scores
    (`_rope_specs`); `scale` is the scores' factor (None: d^-1/2)."""
    d, bh, kv_heads, group = _head_counts(q, k, heads)
    sq, sk = q.shape[1], k.shape[1]
    bq = _pick_block(block_q, sq)
    bk = _pick_block(block_k, sk)
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    steps, _, compact = _grid_steps(causal, sq, sk, bq, bk, window)
    nq = sq // bq
    q_spec, kv_spec, stat_spec = _specs(heads, kv_heads, group, d)
    kernel_kw = dict(
        scale=scale, causal=causal, bq=bq, bk=bk, kv_len=kv_len,
        packed=packed, window=window, nq=nq,
    )
    rope_in = lambda qidx, kidx: []  # the rope pair's specs, last of the inputs
    matmul_dims = 2 * d  # contracted by QK^T, produced by PV, a pair
    two_part = rope is not None
    if two_part:
        kernel_kw["rope"] = True
        rq_spec, rk_spec, r = _rope_specs(rope, bh)
        rope_in = lambda qidx, kidx: [rq_spec(bq, qidx), rk_spec(bk, kidx)]
        matmul_dims += r
    rope = rope or ()
    out_shape = [
        jax.ShapeDtypeStruct(q.shape, q.dtype),
        jax.ShapeDtypeStruct(_lse_layout_shape(bh, sq, packed), jnp.float32),
    ]
    scratch = [
        pltpu.VMEM((bq, _LANES), jnp.float32),
        pltpu.VMEM((bq, _LANES), jnp.float32),
        pltpu.VMEM((bq, d), jnp.float32),
    ]
    cost = pl.CostEstimate(
        flops=2 * matmul_dims * bh * steps * bq * bk,
        bytes_accessed=(q.size + 2 * k.size + sum(x.size for x in rope))
        * q.dtype.itemsize,
        transcendentals=bh * steps * bq * bk,
    )
    if compact:
        rows, cols = _tri_tables(nq, "row", _band_reach(window, nq, bq))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, steps),
            in_specs=[
                q_spec(bq, _T_ROW), kv_spec(bk, _T_COL), kv_spec(bk, _T_COL),
                *rope_in(_T_ROW, _T_COL),
            ],
            out_specs=[
                q_spec(bq, _T_ROW),
                stat_spec(_lse_block(bq, packed), _T_ROW),
            ],
            scratch_shapes=scratch,
        )
        return pl.pallas_call(
            functools.partial(_fwd_kernel_compact, **kernel_kw),
            grid_spec=grid_spec,
            out_shape=out_shape,
            cost_estimate=cost,
            interpret=interpret,
            name=_kernel_name("flash_fwd", "compact", window, two_part),
        )(rows, cols, q, k, v, *rope)
    row_i = lambda i, j: i
    clamped_j = lambda i, j: _clamp_j(i, j, bq, bk, causal)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, **kernel_kw),
        grid=(bh, nq, sk // bk),
        in_specs=[
            q_spec(bq, row_i), kv_spec(bk, clamped_j), kv_spec(bk, clamped_j),
            *rope_in(row_i, clamped_j),
        ],
        out_specs=[
            q_spec(bq, row_i), stat_spec(_lse_block(bq, packed), row_i)
        ],
        out_shape=out_shape,
        scratch_shapes=scratch,
        cost_estimate=cost,
        interpret=interpret,
        name=_kernel_name("flash_fwd", "rect", window, two_part),
    )(q, k, v, *rope)


@functools.partial(
    jax.jit, static_argnames=("block_q", "interpret", "packed", "heads")
)
def _flash_delta_impl(o, do, block_q, interpret, packed, heads=1):
    """The shared-delta precompute: one O(S·d) pass over (o, do), both in
    q's layout; delta comes out [Bq·heads, ...] like the lse."""
    d, bh, _, _ = _head_counts(o, o, heads)
    sq = o.shape[1]
    bq = _pick_block(block_q, sq)
    q_spec, _, stat_spec = _specs(heads, heads, 1, d)
    block_i = lambda i: i
    return pl.pallas_call(
        functools.partial(_delta_kernel, packed=packed),
        grid=(bh, sq // bq),
        in_specs=[q_spec(bq, block_i), q_spec(bq, block_i)],
        out_specs=stat_spec(_lse_block(bq, packed), block_i),
        out_shape=jax.ShapeDtypeStruct(
            _lse_layout_shape(bh, sq, packed), jnp.float32
        ),
        interpret=interpret,
        name="flash_delta",
    )(o, do)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "block_q", "block_k", "interpret", "kv_len", "packed",
        "fused", "heads", "window", "scale",
    ),
)
def _flash_bwd_kernels(
    q, k, v, do, lse, delta, causal, block_q, block_k, interpret,
    kv_len=None, packed=False, fused=None, heads=1, window=None,
    rope=None, scale=None,
):
    """Backward kernels over a precomputed (lse, delta) pair (both in
    the kernel lse layout): the fused one-pass dq/dkv kernel when
    `_bwd_fused` allows (compact causal grid + dq ring fits VMEM), else
    the two-pass dq + dkv kernels. `fused=None` auto-selects via the
    same predicate `flash_schedule` reports; tests pass True/False to
    pin a path (True on a non-compactable or over-budget shape is an
    error — the fused kernel only exists on the compact grid). q, do and
    the returned dq are [Bq, S, heads·d]; k, v and the returned dk, dv
    [Bk, S, kv_heads·d] (`_specs`). With `rope` = (q_rope, k_rope) a
    fourth result, (dq_rope in q_rope's layout, dk_rope summed over the
    heads that share the key)."""
    d, bh, kv_heads, group = _head_counts(q, k, heads)
    sq, sk = q.shape[1], k.shape[1]
    bq = _pick_block(block_q, sq)
    bk = _pick_block(block_k, sk)
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    steps, _, compact = _grid_steps(causal, sq, sk, bq, bk, window)
    nq, nk = sq // bq, sk // bk
    reach = _band_reach(window, nq, bq)
    ring_rows = _ring_rows(sq, bq, window)
    r = 0 if rope is None else rope[1].shape[2]
    if fused is None:
        fused = _bwd_fused(
            causal, sq, sk, bq, bk, d, q.dtype.itemsize, packed, window, r
        )
    elif fused:
        if not _compactable(causal, sq, sk, bq, bk, window):
            raise ValueError(
                "fused flash backward requires the compact causal grid "
                f"(causal self-attention, square blocks); got "
                f"causal={causal} sq={sq} sk={sk} bq={bq} bk={bk}"
            )
        vmem = _fused_vmem_bytes(
            ring_rows, bq, bk, d, q.dtype.itemsize, packed, r
        )
        if vmem > _FUSED_VMEM_BUDGET:
            raise ValueError(
                "fused flash backward forced on an over-budget shape: "
                f"the dq ring + buffers need {vmem / 2**20:.1f} MiB "
                f"of VMEM (budget {_FUSED_VMEM_BUDGET / 2**20:.0f} MiB) "
                "— use the two-pass path"
            )
    kw = dict(
        scale=scale, causal=causal, bq=bq, bk=bk, kv_len=kv_len,
        packed=packed, window=window, nq=nq,
    )
    q_spec, kv_spec, stat_spec = _specs(heads, kv_heads, group, d)
    # dK and dV leave every kernel as one partial a QUERY head, in q's
    # layout (the accumulators and output blocks ride the q grid row);
    # the group's partials are summed after the call.
    dq_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
    dkv_shape = [
        jax.ShapeDtypeStruct(q.shape[:1] + (sk, q.shape[2]), x.dtype)
        for x in (k, v)
    ]
    # The rope part: its specs ride the indices q's and k's do, its dq and
    # dk leave as q_rope's shape (one dk_rope partial a query head, summed
    # after the call like a K/V group's), each with a scratch of its own.
    rope_specs = lambda qidx, kidx: []
    rope_out = lambda blk, idx: []
    rope_shape, rope_scratch = lambda s_: [], lambda rows: []
    two_part = rope is not None
    if two_part:
        rq_spec, rk_spec, _ = _rope_specs(rope, bh)
        rope_specs = lambda qidx, kidx: [rq_spec(bq, qidx), rk_spec(bk, kidx)]
        rope_out = lambda blk, idx: [rq_spec(blk, idx)]
        rope_shape = lambda s_: [jax.ShapeDtypeStruct(
            (rope[0].shape[0], s_, rope[0].shape[2]), rope[0].dtype
        )]
        rope_scratch = lambda rows: [pltpu.VMEM((rows, r), jnp.float32)]

    def grouped(dq, dk, dv, dq_rope=None, dk_rope=None):
        dk, dv = _sum_groups(dk, k, d), _sum_groups(dv, v, d)
        if not two_part:
            return dq, dk, dv
        return dq, dk, dv, (dq_rope, _sum_groups(dk_rope, rope[1], r))

    rope = rope or ()
    # The two-pass kernels gather q's and k's parts themselves (`_qk_refs`);
    # the fused one has an entry a form, its streams pinned by name.
    parts_kw = dict(kw, rope=two_part)
    fused_kernel = _dqkv_kernel_fused_mla if two_part else _dqkv_kernel_fused

    def _in_specs(qidx, kidx):
        # q/do/lse/delta ride the q-block index, k/v the k-block index
        # (and, with grouped heads, the kv head of the q grid row).
        stat = _lse_block(bq, packed)
        return [
            q_spec(bq, qidx), kv_spec(bk, kidx), kv_spec(bk, kidx),
            q_spec(bq, qidx), stat_spec(stat, qidx), stat_spec(stat, qidx),
            *rope_specs(qidx, kidx),
        ]

    if fused:
        # One pass over the triangle, column-major: dk/dv per-column
        # accumulators + the dq ring (see `_dqkv_kernel_fused`). All
        # three outputs ride the column index. The cost estimate counts
        # the 5 block matmuls (the two-pass path re-derives s/dp and
        # pays 7) and the modeled one-pass HBM bytes.
        rows_c, cols_c = _tri_tables(nq, "col", reach)
        cost = pl.CostEstimate(
            flops=(10 * d + 6 * r) * bh * steps * bq * bk,
            bytes_accessed=bh * (
                _bwd_hbm_bytes(
                    causal, sq, sk, bq, bk, d, q.dtype.itemsize, packed,
                    True, window, r,
                )
                - 2 * sq * d * q.dtype.itemsize  # delta precompute's share
                - _lse_bytes_of(sq, packed)
            ),
            transcendentals=bh * steps * bq * bk,
        )
        grads = pl.pallas_call(
            functools.partial(fused_kernel, **kw),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(bh, steps),
                in_specs=_in_specs(_T_ROW, _T_COL),
                out_specs=[
                    q_spec(bq, _T_COL), q_spec(bk, _T_COL), q_spec(bk, _T_COL),
                    *rope_out(bq, _T_COL), *rope_out(bk, _T_COL),
                ],
                scratch_shapes=[
                    pltpu.VMEM((ring_rows, d), jnp.float32),  # dq ring
                    pltpu.VMEM((bk, d), jnp.float32),
                    pltpu.VMEM((bk, d), jnp.float32),
                    *rope_scratch(ring_rows), *rope_scratch(bk),
                ],
            ),
            out_shape=[
                dq_shape, *dkv_shape, *rope_shape(sq), *rope_shape(sk)
            ],
            cost_estimate=cost,
            # The one kernel whose footprint grows with S: past the
            # compiler's 16 MiB default from S=8k on, so it names the
            # limit `_bwd_fused` admitted it under.
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_FUSED_VMEM_BUDGET
            ),
            interpret=interpret,
            name=_kernel_name("flash_bwd", "fused", window, two_part),
        )(rows_c, cols_c, q, k, v, do, lse, delta, *rope)
        return grouped(*grads)

    if compact:
        rows, cols = _tri_tables(nq, "row", reach)
        dq, *dq_rope = pl.pallas_call(
            functools.partial(_dq_kernel_compact, **parts_kw),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(bh, steps),
                in_specs=_in_specs(_T_ROW, _T_COL),
                out_specs=[q_spec(bq, _T_ROW), *rope_out(bq, _T_ROW)],
                scratch_shapes=[
                    pltpu.VMEM((bq, d), jnp.float32), *rope_scratch(bq)
                ],
            ),
            out_shape=[dq_shape, *rope_shape(sq)],
            interpret=interpret,
            name=_kernel_name("flash_dq", "compact", window, two_part),
        )(rows, cols, q, k, v, do, lse, delta, *rope)
        rows_c, cols_c = _tri_tables(nq, "col", reach)
        dk, dv, *dk_rope = pl.pallas_call(
            functools.partial(_dkv_kernel_compact, **parts_kw),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(bh, steps),
                in_specs=_in_specs(_T_ROW, _T_COL),
                out_specs=[
                    q_spec(bk, _T_COL), q_spec(bk, _T_COL),
                    *rope_out(bk, _T_COL),
                ],
                scratch_shapes=[
                    pltpu.VMEM((bk, d), jnp.float32),
                    pltpu.VMEM((bk, d), jnp.float32),
                    *rope_scratch(bk),
                ],
            ),
            out_shape=[*dkv_shape, *rope_shape(sk)],
            interpret=interpret,
            name=_kernel_name("flash_dkv", "compact", window, two_part),
        )(rows_c, cols_c, q, k, v, do, lse, delta, *rope)
        return grouped(dq, dk, dv, *dq_rope, *dk_rope)

    row_i, col_j = lambda i, j: i, lambda j, i: j
    dq, *dq_rope = pl.pallas_call(
        functools.partial(_dq_kernel, **parts_kw),
        grid=(bh, nq, nk),
        in_specs=_in_specs(
            row_i, lambda i, j: _clamp_j(i, j, bq, bk, causal)
        ),
        out_specs=[q_spec(bq, row_i), *rope_out(bq, row_i)],
        out_shape=[dq_shape, *rope_shape(sq)],
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32), *rope_scratch(bq)],
        interpret=interpret,
        name=_kernel_name("flash_dq", "rect", window, two_part),
    )(q, k, v, do, lse, delta, *rope)

    dk, dv, *dk_rope = pl.pallas_call(
        functools.partial(_dkv_kernel, **parts_kw),
        grid=(bh, nk, nq),
        in_specs=_in_specs(
            lambda j, i: _clamp_i(i, j, bq, bk, causal), col_j
        ),
        out_specs=[
            q_spec(bk, col_j), q_spec(bk, col_j), *rope_out(bk, col_j)
        ],
        out_shape=[*dkv_shape, *rope_shape(sk)],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
            *rope_scratch(bk),
        ],
        interpret=interpret,
        name=_kernel_name("flash_dkv", "rect", window, two_part),
    )(q, k, v, do, lse, delta, *rope)
    return grouped(dq, dk, dv, *dq_rope, *dk_rope)


def _flash_bwd_impl(
    q, k, v, o, lse, do, causal, block_q, block_k, interpret,
    kv_len=None, packed=False, heads=1, window=None, rope=None, scale=None,
):
    delta = _flash_delta_impl(o, do, block_q, interpret, packed, heads)
    return _flash_bwd_kernels(
        q, k, v, do, lse, delta, causal, block_q, block_k, interpret,
        kv_len, packed, None, heads, window, rope, scale,
    )


# -- custom VJP --------------------------------------------------------------


def _residual_packed(sq: int, block_q: int) -> bool:
    return _lse_is_packed(sq, _pick_block(block_q, sq))


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11)
)
def _flash_core(
    q, k, v, rope, causal, block_q, block_k, interpret, kv_len, heads,
    window=None, scale=None,
):
    """q [Bq, S, heads·d], k, v [Bk, S, kv_heads·d] (`_specs`) ->
    (o, lse), o in q's layout and lse [Bq·heads, ...]. The lse output
    carries NO cotangent path (its incoming gradient is discarded in the
    VJP) — it exists so callers and `remat_policy="flash"` can hold the
    softmax statistics. `rope` is None, or (q_rope, k_rope) for two-part
    scores (`_rope_specs`); `scale` the scores' factor (None: d^-1/2)."""
    return _flash_vjp_fwd(
        q, k, v, rope, causal, block_q, block_k, interpret, kv_len, heads,
        window, scale,
    )[0]


def _flash_vjp_fwd(
    q, k, v, rope, causal, block_q, block_k, interpret, kv_len, heads, window,
    scale,
):
    packed = _residual_packed(q.shape[1], block_q)
    o, lse = _flash_fwd_impl(
        q, k, v, causal, block_q, block_k, interpret, kv_len, packed, heads,
        window, rope, scale,
    )
    # Residual slimming: in the packed layout the lse residual is already
    # exactly the information (1/128th the old lane-replicated buffer);
    # the replicated fallback keeps one lane and re-broadcasts in bwd.
    # checkpoint_name AFTER slimming, so remat_policy="flash" saves the
    # slim form — these named values are both the primal outputs and the
    # VJP residuals, which is what lets a checkpoint policy that saves
    # them dead-code-eliminate the forward kernel from the backward.
    if not packed:
        lse = lse[:, :, :1]
    o = checkpoint_name(o, CHECKPOINT_OUT_NAME)
    lse = checkpoint_name(lse, CHECKPOINT_LSE_NAME)
    return (o, lse), (q, k, v, rope, o, lse)


def _flash_vjp_bwd(causal, block_q, block_k, interpret, kv_len, heads,
                   window, scale, residuals, cts):
    q, k, v, rope, o, lse = residuals
    do, _ = cts  # the lse output is statistics-only; its cotangent drops
    packed = _residual_packed(q.shape[1], block_q)
    lse_layout = _rows_to_layout(_lse_rows(lse, q.shape[1]), packed)
    dq, dk, dv, *drope = _flash_bwd_impl(
        q, k, v, o, lse_layout, do, causal, block_q, block_k, interpret,
        kv_len, packed, heads, window, rope, scale,
    )
    return dq, dk, dv, (drope[0] if drope else None)


_flash_core.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _checked_rope_pair(q, k, q_rope, k_rope, window):
    """The rope pair of a two-part call, or a refusal with its numbers."""
    b, sq, h, _ = q.shape
    if (
        q_rope is None or k_rope is None or k.shape[2] != h
        or q_rope.shape[:3] != (b, sq, h)
        or k_rope.shape != (b, k.shape[1], q_rope.shape[-1])
    ):
        raise ValueError(
            "flash attention: two-part scores take q_rope [B, S, H, R] and "
            "k_rope [B, S, R] (one rope key for all heads) beside q and k "
            f"of equal heads; got q {q.shape}, k {k.shape}, q_rope "
            f"{getattr(q_rope, 'shape', None)}, k_rope "
            f"{getattr(k_rope, 'shape', None)}"
        )
    _checked_rope(q_rope.shape[-1], window)
    return q_rope, k_rope


def _fold_heads(x, layout: str):
    """[B, S, H, d] -> the kernels' [B, S, H·d] (a reshape: the array the
    projection wrote) or [B·H, S, d] (a transpose: a copy)."""
    b, s, h, d = x.shape
    if layout == "seq_major":
        return x.reshape(b, s, h * d)
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _unfold_heads(x, b: int, h: int, layout: str):
    """The inverse of `_fold_heads`."""
    if layout == "seq_major":
        return x.reshape(b, x.shape[1], h, x.shape[2] // h)
    return x.reshape(b, h, *x.shape[1:]).transpose(0, 2, 1, 3)


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    block_q: int = 1024,
    block_k: int = 1024,
    interpret: bool | None = None,
    return_lse: bool = False,
    window: int | None = None,
    q_rope=None,
    k_rope=None,
    scale: float | None = None,
):
    """Blockwise attention on the MXU. q: [B, S, H, D]; k, v: [B, S, Hkv, D]
    with H a multiple of Hkv (query head h attends over kv head
    h // (H/Hkv); the kernels' index maps pick it, K and V are never
    repeated) → [B, S, H, D].

    **Two-part scores** (latent attention). With ``q_rope`` [B, S, H, R]
    and ``k_rope`` [B, S, R] a head's scores are ``q·kᵀ + q_rope·k_ropeᵀ``:
    D dims a head (as wide as v) and R more against ONE rope key all H
    heads share, which the kernels read once a K block and never repeated
    a head; v and o stay D wide and nothing is padded on the value side.
    Returns o; ``dk_rope`` is the sum over the heads. ``scale`` is the
    scores' factor, (D + R)^-1/2 unless given. The 128-wide operands keep
    their layout; the R-wide ``q_rope`` goes head-major (`ROPE_LAYOUT`).
    The calls are named ``flash_*_mla*``. Needs equal heads (Hkv = H) and
    no window.

    Numerically matches ``dense_attention`` (same online-softmax math) while
    never materializing the [S, S] score matrix in HBM — at S=8192 the
    dense path OOMs a 16 GB v5e chip outright; this runs. ``interpret=None``
    autodetects: Pallas interpreter on the CPU backend (tests), compiled
    on anything else.

    **Layout.** Where D is whole lanes (D % 128 == 0: `_head_layout`, reported
    as `flash_schedule()`'s `layout`) the kernels read q, k, v and dO and
    write o, dq, dk, dv where the projections' matmuls read and write
    them: [B, S, H, D] goes in and out as [B, S, H·D], a reshape, and a
    head is a column block of it. The custom VJP's residuals are then the
    projections' own arrays. Any other D folds the heads into the batch by
    a transpose on the way in and on the way out (four forward, four
    backward: `transposes_per_call`), the same kernels and the same spec
    builder at one head a row. Same blocks, same order, same bodies:
    outputs and gradients are bit-identical between the two.

    Sequence lengths that don't divide into 8-aligned blocks are padded
    internally to the next lane multiple; the tail is masked in-kernel
    and sliced off the output, so ragged lengths run this kernel instead
    of falling back to the dense O(S²) path. Causal self-attention runs
    the compact triangular grid (see module docstring): ~half the grid
    steps of the rectangular schedule at large S.

    ``window=W`` (causal only) lets position i see keys i - W < j <= i,
    W of them with its own. On the compact grid the tables then
    enumerate only the block pairs that band touches, and a step is on
    the diagonal, where the band ends (masked from the far side, by
    constants) or between them (no mask): `_step_tiles`,
    `flash_schedule(..., window=)`. The fused backward's dq ring holds
    the rows live at once, reach + 1 blocks, whatever S is. The calls are
    named `flash_*_window*` (`_kernel_name`). ``W >= S`` is the causal
    call: the same program as ``window=None``.

    ``return_lse=True`` additionally returns the log-sum-exp as
    [B, H, S] (float32). The lse return is statistics-only: no gradient
    flows through it.

    Default blocks come from a v5e sweep (B=4, H=16, D=128, causal,
    serialized timing): (1024, 1024) beats the small-block configs at
    every length — vs (256, 512): fwd 43.0 vs 26.6 TF/s at S=8k and 67.9
    vs 34.7 TF/s at S=16k (fwd+bwd 85.2 vs 47.4 TF/s); 2048-wide blocks
    fail to compile (VMEM). Blocks clamp to the sequence and degrade to a
    lane-aligned divisor, so short sequences are unaffected.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    if k.shape != v.shape or h % k.shape[2]:
        raise ValueError(
            f"flash attention: {h} query heads over k {k.shape} / v "
            f"{v.shape}: k and v must agree and their heads divide q's; a "
            "q and k wider than v go as two parts, the part as wide as v "
            "and a rope part (q_rope [B, S, H, R], k_rope [B, S, R]); two "
            "parts that are together as wide as v go joined, as one "
            "(`ops/attention.attend` joins them; `latent_form`)"
        )
    interp = _auto_interpret(interpret)
    window = _checked_window(window, causal, sk)
    sp_q = _pad_to_tileable(block_q, sq)
    sp_k = _pad_to_tileable(block_k, sk)
    kv_len = sk if sp_k != sk else None
    rope = None
    if q_rope is not None or k_rope is not None:
        rope = _checked_rope_pair(q, k, q_rope, k_rope, window)
        if scale is None:
            scale = 1.0 / math.sqrt(d + q_rope.shape[-1])
    if sp_q != sq or sp_k != sk:
        pad = lambda x, s: jnp.pad(
            x, ((0, 0), (0, s - x.shape[1])) + ((0, 0),) * (x.ndim - 2)
        )
        q, k, v = pad(q, sp_q), pad(k, sp_k), pad(v, sp_k)
        if rope:
            rope = pad(rope[0], sp_q), pad(rope[1], sp_k)
    layout = _head_layout(d)
    if rope:  # q_rope head-major, whatever q's layout (`ROPE_LAYOUT`)
        rope = _fold_heads(rope[0], ROPE_LAYOUT), rope[1]
    o, lse = _flash_core(
        _fold_heads(q, layout), _fold_heads(k, layout),
        _fold_heads(v, layout), rope, causal, block_q, block_k, interp,
        kv_len, h if layout == "seq_major" else 1, window, scale,
    )
    o = _unfold_heads(o, b, h, layout)
    if sp_q != sq:
        o = o[:, :sq]
    if not return_lse:
        return o
    lse_rows = _lse_rows(lse, sp_q).reshape(b, h, sp_q)[:, :, :sq]
    return o, lse_rows


def flash_kernel_tileable(seq: int, block: int = 1024) -> bool:
    """True when `seq` divides into 8-aligned flash blocks WITHOUT
    padding. The ring path needs this (chunks must stay congruent across
    hops, so it cannot pad); `flash_attention` pads what does not."""
    return _tileable(block, seq)


# -- ring flash: sequence-parallel flash attention --------------------------
#
# The long-context composition the platform's sp axis exists for: each
# device holds a sequence chunk, K/V chunks rotate around the ring
# (`ops/attention.ring_attention` topology), and every hop runs the
# Pallas kernel instead of materializing the [C, C] score matrix —
# blockwise-parallel ring attention. Per-hop (o_i, lse_i) pairs merge
# with the standard log-sum-exp algebra; the backward re-walks the ring
# passing the GLOBAL (o, lse) into the kernel's bwd (whose
# p = exp(s - lse) and delta = rowsum(do*o) are then the global softmax
# weights), accumulating dk/dv in the rotating frame and delivering them
# home with one final rotation. delta is the SAME for every hop (it
# depends only on the global o/do), so the shared-delta precompute runs
# once per backward, not once per hop.


def _flat_heads(x):
    return _fold_heads(x, "head_major")


def _unflat_heads(x, b, h):
    return _unfold_heads(x, b, h, "head_major")


def _hop_branches(qf, kf, vf, bq, bk, interpret):
    """(full, diagonal, skip) branch thunks for one ring hop — the hop
    kind is data-dependent (axis_index), the kernel's causal flag is
    static, so lax.switch picks among three static traces. Each branch
    returns (o, lse) with lse in per-row [BH, C, 1] form."""
    bh, c, d = qf.shape
    packed = _residual_packed(c, bq)

    def full_blk():
        o, lse = _flash_fwd_impl(qf, kf, vf, False, bq, bk, interpret,
                                 None, packed)
        return o, _lse_rows(lse, c)

    def diag_blk():
        o, lse = _flash_fwd_impl(qf, kf, vf, True, bq, bk, interpret,
                                 None, packed)
        return o, _lse_rows(lse, c)

    def skip_blk():
        return (
            jnp.zeros((bh, c, d), qf.dtype),
            jnp.full((bh, c, 1), _NEG_INF, jnp.float32),
        )

    return (full_blk, diag_blk, skip_blk)


def _hop_index(src, my):
    # 0 = full (earlier chunk), 1 = diagonal (own chunk), 2 = skip
    # (later chunk — fully masked under causality).
    return jnp.where(src == my, 1, jnp.where(src < my, 0, 2))


def _ring_rotate(x, axis: str, n: int):
    # One helper for both attention modules: the dense-hop ring and the
    # flash-hop ring MUST share the same permutation direction.
    from kubeflow_tpu.ops.attention import _rotate

    return _rotate(x, axis, n)


def _ring_flash_fwd_pass(q, k, v, axis, causal, bq, bk, interpret):
    from kubeflow_tpu.parallel.collectives import axis_size

    b, c, h, d = q.shape
    n = axis_size(axis)
    my = lax.axis_index(axis)
    qf = _flat_heads(q)
    bh = b * h

    acc = jnp.zeros((bh, c, d), jnp.float32)
    m = jnp.full((bh, c, 1), _NEG_INF, jnp.float32)
    l = jnp.zeros((bh, c, 1), jnp.float32)
    k_cur, v_cur = k, v
    for i in range(n):
        src = (my - i) % n
        branches = _hop_branches(
            qf, _flat_heads(k_cur), _flat_heads(v_cur), bq, bk, interpret
        )
        if causal:
            o_i, lse_i = lax.switch(_hop_index(src, my), branches)
        else:
            o_i, lse_i = branches[0]()
        # Log-sum-exp merge of the hop's normalized output into the
        # running global softmax (same algebra as the kernel's own
        # online accumulation, one level up), in per-row [BH, C, 1]
        # space — the lane-replicated merge buffers are gone with the
        # packed lse layout.
        m_new = jnp.maximum(m, lse_i)
        corr = jnp.where(m == _NEG_INF, 0.0, jnp.exp(m - m_new))
        w = jnp.where(lse_i == _NEG_INF, 0.0, jnp.exp(lse_i - m_new))
        acc = acc * corr + w * o_i.astype(jnp.float32)
        l = l * corr + w
        m = m_new
        if i + 1 < n:
            k_cur = _ring_rotate(k_cur, axis, n)
            v_cur = _ring_rotate(v_cur, axis, n)

    safe_l = jnp.where(l == 0.0, 1.0, l)
    o = (acc / safe_l).astype(q.dtype)
    lse_tot = m + jnp.log(safe_l)  # [BH, C, 1] rows form
    return _unflat_heads(o, b, h), lse_tot


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring_flash_body(q, k, v, axis, causal, bq, bk, interpret):
    o, lse = _ring_flash_fwd_pass(q, k, v, axis, causal, bq, bk, interpret)
    o = checkpoint_name(o, CHECKPOINT_OUT_NAME)
    del lse
    return o

def _ring_flash_body_fwd(q, k, v, axis, causal, bq, bk, interpret):
    o, lse = _ring_flash_fwd_pass(q, k, v, axis, causal, bq, bk, interpret)
    # The global lse rides in per-row [BH, C, 1] form — already slim.
    # Named so remat_policy="flash" can pin (o, lse) and skip re-walking
    # the forward ring inside the backward.
    o = checkpoint_name(o, CHECKPOINT_OUT_NAME)
    lse = checkpoint_name(lse, CHECKPOINT_LSE_NAME)
    return o, (q, k, v, o, lse)


def _ring_flash_body_bwd(axis, causal, bq, bk, interpret, residuals, do):
    from kubeflow_tpu.parallel.collectives import axis_size

    q, k, v, o, lse_rows = residuals
    b, c, h, d = q.shape
    n = axis_size(axis)
    my = lax.axis_index(axis)
    qf, of, dof = _flat_heads(q), _flat_heads(o), _flat_heads(do)
    bh = b * h
    packed = _residual_packed(c, bq)
    lse_layout = _rows_to_layout(lse_rows, packed)
    # Shared delta across the whole ring: delta = rowsum(do ∘ o) depends
    # only on the GLOBAL output and its cotangent, which every hop
    # shares — one precompute pass feeds all n hops' dq/dkv kernels.
    delta = _flash_delta_impl(of, dof, bq, interpret, packed)

    dq = jnp.zeros((bh, c, d), jnp.float32)
    # dk/dv accumulate in the ROTATING frame: each hop adds its
    # contribution to the chunk currently held, and the accumulators
    # travel with the chunk.
    k_cur, v_cur = k, v
    dk_cur = jnp.zeros((bh, c, d), jnp.float32)
    dv_cur = jnp.zeros((bh, c, d), jnp.float32)
    for i in range(n):
        src = (my - i) % n
        kf, vf = _flat_heads(k_cur), _flat_heads(v_cur)

        def full_blk():
            return _flash_bwd_kernels(
                qf, kf, vf, dof, lse_layout, delta, False, bq, bk,
                interpret, None, packed,
            )

        def diag_blk():
            return _flash_bwd_kernels(
                qf, kf, vf, dof, lse_layout, delta, True, bq, bk,
                interpret, None, packed,
            )

        def skip_blk():
            z = jnp.zeros((bh, c, d), q.dtype)
            return z, z, z

        if causal:
            dq_i, dk_i, dv_i = lax.switch(
                _hop_index(src, my), (full_blk, diag_blk, skip_blk)
            )
        else:
            dq_i, dk_i, dv_i = full_blk()
        dq = dq + dq_i.astype(jnp.float32)
        dk_cur = dk_cur + dk_i.astype(jnp.float32)
        dv_cur = dv_cur + dv_i.astype(jnp.float32)
        if i + 1 < n:
            k_cur = _ring_rotate(k_cur, axis, n)
            v_cur = _ring_rotate(v_cur, axis, n)
            dk_cur = _ring_rotate(dk_cur, axis, n)
            dv_cur = _ring_rotate(dv_cur, axis, n)
    # After n-1 rotations the chunk (and its gradient) sits one hop
    # short of home — one final rotation delivers dk/dv to their owners.
    dk_home = _ring_rotate(dk_cur, axis, n)
    dv_home = _ring_rotate(dv_cur, axis, n)
    return (
        _unflat_heads(dq, b, h).astype(q.dtype),
        _unflat_heads(dk_home, b, h).astype(k.dtype),
        _unflat_heads(dv_home, b, h).astype(v.dtype),
    )


_ring_flash_body.defvjp(_ring_flash_body_fwd, _ring_flash_body_bwd)


def ring_flash_attention(
    q,
    k,
    v,
    mesh,
    *,
    causal: bool = True,
    sp_axis: str = "sp",
    heads_axis: str | None = "tp",
    block_q: int = 1024,
    block_k: int = 1024,
    interpret: bool | None = None,
):
    """Sequence-parallel flash attention over `mesh`'s sp ring.

    q, k, v: GLOBAL [B, S, H, D]; S divides by the ring, H by tp. Each
    hop runs the Pallas kernel on the local [C, C] tile (C = S/ring), so
    per-device attention memory is O(C·D) instead of O(C²) — the
    composition that takes the single-chip S=16k flash ceiling to
    ring-size × 16k. Differentiable end-to-end (custom VJP re-walks the
    ring with global statistics). Falls back to single-device flash when
    the ring is trivial. Ring chunks must tile WITHOUT padding
    (`flash_kernel_tileable`): padded chunks would de-synchronize the
    hop algebra."""
    if mesh.shape.get(sp_axis, 1) == 1:
        return flash_attention(
            q, k, v, causal=causal, block_q=block_q, block_k=block_k,
            interpret=interpret,
        )
    from jax.sharding import PartitionSpec as P

    from kubeflow_tpu.parallel.sharding import batch_axes

    ring = mesh.shape[sp_axis]
    if q.shape[1] % ring:
        raise ValueError(
            f"ring flash attention: sequence length {q.shape[1]} does "
            f"not divide the {sp_axis!r} ring size {ring}"
        )
    chunk = q.shape[1] // ring
    if not flash_kernel_tileable(chunk, block_q) or not (
        flash_kernel_tileable(chunk, block_k)
    ):
        raise ValueError(
            f"ring flash attention: per-device chunk {chunk} does not "
            "divide into 8-aligned flash blocks (the ring cannot pad); "
            "use ring_attention or resize the sp axis"
        )
    spec = P(batch_axes(mesh), sp_axis, heads_axis, None)
    interp = _auto_interpret(interpret)

    def body(q_, k_, v_):
        # nondiff custom_vjp args must be positional, so no partial().
        return _ring_flash_body(
            q_, k_, v_, sp_axis, causal, block_q, block_k, interp
        )

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)
