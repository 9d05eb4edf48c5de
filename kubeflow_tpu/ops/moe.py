"""Dropless top-k expert dispatch and grouped matmuls (Pallas TPU).

What `models/transformer.ExpertLayer` runs on the experts it holds: a
token has one row for each of its k experts; the rows are ordered by
expert into a row buffer, grouped matmuls (the expert MLP: gated, three
matrices, or `relu(.)^2`, two) run over it, and each token's rows are
brought back and added up by their weights. No capacity and no dropped
token at any imbalance. The buffer is sized for the worst case, and
`n_tiles`, the count of row tiles in use that the plan carries, bounds
every pass over it: the work follows the rows routed here, not `experts
x capacity` and not the buffer's length.

- **The plan** (`plan_dispatch`). Each held expert's tokens occupy a run
  of whole row tiles (`block_rows` rows; an expert with no token still
  gets one tile of zero rows, so every expert's weight gradient is
  written: `tiles_in_use`). The buffer is sized for the worst case
  (`row_tiles`): a token's k experts differ, so at most `min(k,
  experts)` of its rows are held here, and `ceil(N min(k, experts) /
  block_rows) + experts` tiles hold them; `tile_expert[t]` names tile
  t's expert and `n_tiles` how many tiles are in use. Pairs routed to
  experts held elsewhere get no row. A tile never straddles two experts,
  so the kernels need no masks: a grouped matmul is a tiled matmul whose
  weight block is picked by a scalar-prefetched table. What is built
  from what: `mine` [n_held, N] (token n is routed to held expert e)
  from the ids by compares; `count`, its running sum along the tokens,
  gives a pair's place among its expert's rows and, at its end, the
  expert's tiles; `tile_expert` is a compare of the tiles' numbers with
  the experts' last tiles; a token's rows (`token_rows`), its slots'
  weights (`slot_weights`) and their transpose are selects and sums over
  `[h, n_held, N]`, which the vector unit does at its own rate. The one
  step that turns "token n has a row of expert e" round into "row r is
  token n's" is the kernel `moe_plan_rows`, at k > 1, over the row tiles
  IN USE (its grid ends at `n_tiles`): row i of expert e is the token
  `#{n : count[e, n] <= i}`, counted in two levels: the whole lines of
  128 tokens whose last count is at or under i (a compare with the
  lines' ends), then, in the next line, the tokens at or under i; a
  row's line is picked from the expert's `[N / 128, 128]` counts by a
  product with a one at its number, a byte a product so that it is exact.
  The backward's `moe_plan_weights` picks a row's weight the same way,
  `held[e, n]` at the row's token, where a scatter of `h x N` weights
  stood, and `moe_plan_tokens`, its transpose, puts a row's product with
  its token's cotangent at the token's place in its expert's block (the
  weights' gradient), where a gather of `h x N` elements stood: the
  combine takes its weights by held expert, `[n_held, N]`
  (`held_weights`). No step is sized by `n_held x N` or `h x N` single
  elements:
  XLA scatters one element in 5 ns on this chip, 524,288 of them twice a
  layer (forward, and again under remat) to place 10,240 rows until
  PR 46. (At one expert a token the plan scatters a row's token for each
  of N tokens and the movers are XLA's gathers: `moe_schedule()`'s
  `plan_updates`.)
- **`moe_gmm_fwd` / `moe_gmm_dlhs`**: `out[rows of e] = lhs[rows of e] @
  w[e]` (or `@ w[e].T` for the operand's gradient). Grid (column tiles,
  row tiles, contraction tiles); consecutive row tiles of one expert keep
  the weight block's index, so an expert's weights are fetched once a
  column tile. The weights are read as they lie in HBM, at the
  parameters' own dtype: where that is not the rows' (float32 parameters
  under bfloat16 rows) a fetched block is rounded to the rows' dtype in
  VMEM, to nearest even as XLA's convert rounds, ONCE, on the first row
  tile that meets it (`t == 0` or another expert than the tile before;
  every step where the contraction takes several, since each fetches),
  into a scratch the matmuls of the expert's other row tiles read; no
  copy of the weights in the rows' dtype is ever written to HBM. Such a
  block is twice the bytes for the same matmuls, and the pipeline would
  fetch it one grid step ahead, behind an expert's LAST row tile alone
  (16 MiB behind 12 us of matmul in zaya), into one of two buffers: with
  the contraction in one step the kernel fetches it itself, an expert
  ahead, behind all of the expert's tiles, into the ONE block it has
  just rounded (`_rounded_weights`; a float32 block and its rounded copy
  are 24 MiB in zaya where two bfloat16 buffers were 16, and the VMEM
  limit grows by the difference: XLA keeps arrays of its own in VMEM
  across a call, zaya's 64 MiB row buffers among them, in what the
  call's limit leaves of the chip's 128 MiB). Where
  the two dtypes are equal there is no scratch and no rounding. A tile
  past `n_tiles` costs a grid step and nothing else: no matmul, no
  fetch, no rounding and no write (every index is clamped to the last
  tile in use, whose output block stays resident and is written back
  once). The expert's activation is the epilogue, on the float32
  accumulator, of the last matmul that reads the rows, which also
  leaves the product itself: `relu(z)^2` of the two-matrix expert's
  one, `silu(a) b` of the gated expert's second, which takes the
  first's result a as one more operand. Its slope (`2 relu(z) dh`; `da
  = dh b silu'(a)`, `db = dh silu(a)`) is formed in VMEM, in float32,
  by the `dlhs` and `dw` calls that consume it, and the gated expert's
  second `dlhs` adds onto the first's result in place: the gated expert
  meets no XLA pass over its buffer, for the activation, its derivative
  or the sum of its two operand gradients.
- **`moe_gmm_dw`**: `dw[e] = lhs[rows of e].T @ g[rows of e]`, accumulated
  in float32 over an expert's tiles and written once.
- **The rows' movers.** With one expert a token (`expert` [N]) the
  dispatch and the combine are XLA gathers that are each other's inverse
  (a row names one token and a token at most one row), over a buffer that
  is half in use. With k > 1 (`expert` [N, k]) they are two kernels that
  stop at the tiles in use: `moe_rows_take`, row side, `out[r] = scale[r]
  x[row_token[r]]` by one DMA a row from an operand left in HBM (the
  dispatch; the combine's transpose with the row's weight as `scale`,
  which also forms the weights' gradient `<rows[r], g[row_token[r]]>`),
  and `moe_rows_sum`, token side, `y[n] = sum_j w[j, n]
  rows[token_rows[j, n]]` in float32 over the slots a token has (the
  combine; the dispatch's transpose with no weights); `[h, N, d]` is
  never materialised. What a DMA fetches a row at a time is kept
  *packed* (`_Packed`): `[M, d]` float32 as `[8 M, 128]` at d = 1024, a
  row in whole tiles of sublanes (a one-row slice of a tiled array is
  not a legal DMA); the matmul before a `moe_rows_sum` writes its result
  that way.

**Who may read what.** Only the kernels here and gathers by the plan's
indices (which name rows in use only) read a result of a `moe_gmm_fwd`
/ `moe_gmm_dlhs` / `moe_rows_take` call; nothing is written past the
tiles in use. The form of the expert (`len(weights)`) picks the
activation, the rank of `expert` picks the movers; nothing else decides
anything.

`_experts_in` (dispatch, then the matmuls that read the rows) and
`_experts_out` (the last matmul, then the combine) tie the kernels
together with a `custom_vjp` each; the weights go into every grouped
matmul at their own dtype (float32 parameters), forward and backward, and
the kernels round the blocks they fetch: no bfloat16 copy of an expert's
weights is formed outside a kernel, kept as a residual or read twice
(`moe_schedule()`'s `weight_itemsize`, `weight_rounds` and
`weight_cast_bytes`). On the CPU backend the kernels run under the Pallas
interpreter (tests), which fills unwritten memory with NaN; anywhere else
they are compiled (`ops/flash.kernels_compiled`). Every `pallas_call` has
a `name=` starting `moe_gmm_`, `moe_rows_` or `moe_plan_`: what a device
trace keys their time on. `expert_mlp` forms the plan and the weights by
held expert and by slot under the scope `moe.plan` (the backward's two
`moe_plan_*` calls too), beside `moe.dispatch`, `moe.experts` and
`moe.combine`.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from kubeflow_tpu.ops.flash import kernels_compiled
from kubeflow_tpu.parallel.sharding import batch_axes

BLOCK_ROWS = 256
# `jax.checkpoint_name` of the rows' tokens, `moe_plan_rows`' result: a
# policy that keeps it (`remat_policy="flash"`) runs the kernel once a
# layer, forward, and not again for the backward.
CHECKPOINT_ROWS_NAME = "moe_plan_rows"
# Tile caps: a weight block of 2048 x 2048 bf16 is 8 MiB (16 double-
# buffered), which keeps an expert of this width to one fetch a pass; a
# side may be as long as 4096 where the other is short (1024 x 2688: one
# block an expert, one grid step a row tile). The caps count elements at
# the rows' 2 bytes: where the weights lie wider, a call's VMEM limit
# grows by the bytes that adds (`_gmm`), not the blocks smaller.
_TILE_SIDE = 4096
_TILE_WEIGHT = 2048 * 2048
_TILE_DW = 3 * 1024 * 1024  # elements of dw's float32 accumulator
_VMEM_LIMIT = 48 * 1024 * 1024
_SUBLANES = 8  # a float32 tile's height: the least a DMA may slice
_SUM_TOKENS = 256  # tokens a grid step of `moe_rows_sum`
_SUM_VMEM = 8 * 1024 * 1024  # its gathered rows, [slots, tokens, d] float32
_PLAN_ROWS = 128  # rows a pass of the `moe_plan_*` kernels: 16 registers a line
_ROUND_ROWS = 256  # rows of a weight block rounded a pass
# The forms of expert by their count of matrices: what the last matmul on
# the rows makes of its product (`_activate`, `_slope`).
_FORMS = {2: "relu2", 3: "gated"}


def _tile(dim: int, cap: int) -> int:
    """The largest tile <= cap that divides `dim`: of whole 128-lane
    columns where `dim` is made of them (2688 = 21 x 128 tiles by 896),
    else by halving from cap."""
    if dim % 128 == 0:
        return 128 * max(
            m for m in range(1, min(dim, cap) // 128 + 1) if (dim // 128) % m == 0
        )
    t = min(dim, cap)
    while dim % t:
        t //= 2
    return t


def _gmm_tiles(contract: int, cols: int, packed: bool = False) -> tuple[int, int]:
    """(contraction tile, column tile) of a grouped matmul; a packed
    result is a whole row wide."""
    to = cols if packed else _tile(cols, _TILE_SIDE)
    return _tile(contract, min(_TILE_SIDE, max(128, _TILE_WEIGHT // to))), to


def row_tiles(tokens: int, k: int, n_held: int, block_rows: int = BLOCK_ROWS) -> int:
    """Row tiles of the buffer for `tokens` tokens of k experts each over
    `n_held` held experts: the worst case, static."""
    return -(-tokens * min(k, n_held) // block_rows) + n_held


def tiles_in_use(counts, block_rows: int = BLOCK_ROWS):
    """Row tiles each held expert occupies, from the rows routed to it
    (`counts` [n_held]): whole tiles, and one for an expert with none.
    Their sum is `n_tiles`."""
    return jnp.maximum(-(-counts // block_rows), 1)


def plan_dispatch(expert, lo, n_held: int, block_rows: int = BLOCK_ROWS,
                  interpret: bool | None = None):
    """Where each token-expert pair's row is, from the expert ids.

    expert: [N] or [N, k] int32 (a token's k experts all differ), ids
    over ALL experts; this shard holds `lo .. lo + n_held - 1`. An
    expert's rows are in the order of their tokens. Everything comes from
    `mine` [n_held, N] (is token n routed to held expert e) by cumulative
    sums and selects over [n_held, N]; a row's token is counted from
    `count`, an expert's running count of its tokens, over the tiles in
    use (`moe_plan_rows`; at [N] the one scatter, of N tokens). Returns a
    dict: `tile_expert` [tiles] (local expert of each row tile), `n_tiles`
    [1] (tiles in use), and for [N]: `dst` [N] (the token's row, or
    `rows` = out of range where its expert is held elsewhere), `src`
    [rows] (the row's token, or N for a row of padding). For [N, k] a
    token's rows side by side: of its k pairs at most `h = min(k,
    n_held)` are held here, in the order of their experts, so
    `token_rows` [h, N] lists them (`rows` where it has fewer),
    `token_count` [N] says how many it has, `row_token` [rows] is a row's
    token (N for padding), and `hit` [k, n_held, N] (pair j of token n is
    held expert e) with `place` [h, n_held, N] (held expert e is token
    n's slot s) carry a pair's weight to its slot (`slot_weights`): the
    movers read h slots a token, not k (8 for 22 in the Nemotron cell).
    """
    by_pairs = expert.ndim == 2
    pairs = expert.T if by_pairs else expert[None, :]
    k, tokens = pairs.shape
    tiles = row_tiles(tokens, k, n_held, block_rows)
    rows = tiles * block_rows
    hit = (pairs - lo)[:, None, :] == jnp.arange(
        n_held, dtype=pairs.dtype
    )[None, :, None]
    mine = jnp.any(hit, axis=0)
    ones = mine.astype(jnp.int32)
    count = jnp.cumsum(ones, axis=1)
    group_tiles = tiles_in_use(count[:, -1], block_rows)
    ends = jnp.cumsum(group_tiles)
    tile_first = (ends - group_tiles).astype(jnp.int32)
    row = jnp.where(
        mine, (tile_first * block_rows)[:, None] + count - 1, rows
    ).astype(jnp.int32)
    tile_expert = jnp.minimum(
        jnp.searchsorted(
            ends, jnp.arange(tiles), side="right", method="compare_all"
        ),
        n_held - 1,
    ).astype(jnp.int32)
    n_tiles = ends[-1:].astype(jnp.int32)
    plan = {"tile_expert": tile_expert, "n_tiles": n_tiles}
    if not by_pairs:
        dst = jnp.min(row, axis=0)
        src = jnp.full((rows,), tokens, jnp.int32).at[dst].set(
            jnp.arange(tokens, dtype=jnp.int32), mode="drop"
        )
        plan.update(dst=dst, src=src)
        return plan
    h = min(k, n_held)
    slot = jnp.cumsum(ones, axis=0) - 1
    place = mine[None] & (
        slot[None] == jnp.arange(h, dtype=jnp.int32)[:, None, None]
    )
    in_use = jnp.arange(rows, dtype=jnp.int32) // block_rows < n_tiles
    plan.update(
        hit=hit, place=place,
        token_rows=jnp.min(jnp.where(place, row[None], rows), axis=1),
        token_count=jnp.sum(ones, axis=0),
        row_token=checkpoint_name(jnp.where(in_use, _plan_rows(
            count, tile_expert, n_tiles, tile_first, block_rows=block_rows,
            interpret=_interpreted(interpret),
        ), tokens), CHECKPOINT_ROWS_NAME),
    )
    return plan


def held_weights(gate, plan):
    """`gate` [N, k] by held expert, [n_held, N] (zeros where token n is
    not routed to e): selects and sums, so its transpose is too."""
    return jnp.sum(jnp.where(plan["hit"], gate.T[:, None, :], 0.0), axis=0)


def _by_slot(held, plan):
    """[n_held, N] by a token's slots, [h, N] (zeros where it has fewer)."""
    return jnp.sum(jnp.where(plan["place"], held[None], 0.0), axis=1)


def slot_weights(gate, plan):
    """`gate` [N, k] by a token's slots, [h, N] (zeros where it has
    fewer)."""
    return _by_slot(held_weights(gate, plan), plan)


def moe_schedule(
    tokens: int, k: int, held: int, width_in: int, width_out: int, *,
    live_tiles: int | None = None, block_rows: int = BLOCK_ROWS,
    weight_itemsize: int = 4,
) -> dict:
    """What one `moe_gmm_fwd` call of `width_in` -> `width_out` and the
    rows' movers touch, from the shapes and the count of tiles in use
    (all of them where `live_tiles` is None): static, for tests and for
    reading a trace. `*_bytes` count a row tile's operands and results
    once at 2 bytes (4 where packed) and every weight block the call
    fetches at the `weight_itemsize` bytes it lies in HBM at (float32
    parameters: 4): each held expert's once a column tile where the
    contraction is one step, a row tile's every time where it is several.
    `weight_rounds` of those blocks are rounded in VMEM to the rows' 2
    bytes, one rounding a fetch (none where the weights lie at 2 bytes
    already), and `weight_cast_bytes`, what XLA moves to cast a matrix
    for the call, is 0 (`6 x held x d x f` a pass until PR 49, and the
    kernel read the 2-byte copy besides). `activation` says for each
    form of expert who forms
    its activation and slope, `results_past_live` how many kernel results
    are written past the tiles in use, `plan_updates` how many single
    elements the plan and its backward scatter: none at k > 1 (a row's
    token is counted by `moe_plan_rows` and its weight picked by
    `moe_plan_weights`, a grid step a tile in use each), a row's token
    for each of the N tokens at k = 1."""
    tiles = row_tiles(tokens, k, held, block_rows)
    live = tiles if live_tiles is None else live_tiles
    tc, to = _gmm_tiles(width_in, width_out)
    grid = (width_out // to, tiles, width_in // tc)
    fetches = grid[0] * (held if grid[2] == 1 else live * grid[2])
    weights = fetches * tc * to * weight_itemsize
    rows = lambda t: t * block_rows
    return {
        "tiles": tiles, "rows": rows(tiles),
        "live_tiles": live, "rows_touched": rows(live),
        "gmm_grid": grid, "gmm_grid_steps": math.prod(grid),
        "gmm_dead_steps": grid[0] * (tiles - live) * grid[2],
        "gmm_bytes": weights + 2 * rows(live) * (width_in + width_out),
        "weight_itemsize": weight_itemsize,
        "weight_rounds": fetches if weight_itemsize != 2 else 0,
        "weight_cast_bytes": 0,
        "activation": {form: "kernel" for form in _FORMS.values()},
        "results_past_live": 0,
        "movers": "moe_rows" if k > 1 else "xla_gather",
        "plan_updates": 0 if k > 1 else tokens,
        "plan_rows_grid_steps": live if k > 1 else 0,
        "rows_take_grid_steps": tiles,
        "rows_take_bytes": rows(live) * width_in * (4 + 2),
        "rows_sum_grid_steps": tokens // _sum_tokens(tokens, min(k, held), width_in),
        "rows_sum_bytes": rows(live) * width_in * 4 + tokens * width_in * 2,
    }


# -- kernels -----------------------------------------------------------------


def _params(semantics, more_vmem: int = 0):
    return pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=_VMEM_LIMIT + more_vmem
    )


def _live(t, nt):
    """Tiles past the ones in use re-address the last one: no fetch, and
    an output block that stays where it is."""
    return jnp.minimum(t, nt[0] - 1)


def _live_step(t, c, steps, nt):
    """The contraction block of step c: the last one on a tile past the
    ones in use, which is where the last tile in use left off."""
    return jnp.where(t < nt[0], c, steps - 1)


def _activate(pre, gate=None):
    """The expert's activation of the float32 product `pre`: `relu(pre)^2`,
    or `silu(gate) pre` of the gated expert."""
    if gate is None:
        return jnp.square(jnp.maximum(pre, 0.0))
    return jax.nn.silu(gate.astype(jnp.float32)) * pre


def _slope(g, saved, wrt: int):
    """`g` times the activation's derivative by its product `wrt`, from
    the products the forward kept: `(z,)` of `relu(z)^2`, `(a, b)` of
    `silu(a) b`. Float32, rounded once to g's dtype."""
    first, *second = (v.astype(jnp.float32) for v in saved)
    if not second:
        slope = 2.0 * jnp.maximum(first, 0.0)
    else:
        s = jax.nn.sigmoid(first)
        slope = first * s if wrt else second[0] * s * (1.0 + first * (1.0 - s))
    return (g.astype(jnp.float32) * slope).astype(g.dtype)


class _Packed(NamedTuple):
    """How rows of width d lie *packed*, `[M, d]` float32 as `[M sublanes,
    lanes]`: a row is `pieces` runs of `lanes` (128 where d is made of
    them, the strided loads' need; else all of d, which only the
    interpreter takes), one a sublane, in `sublanes` sublanes: whole
    float32 tiles, which a DMA may slice from a tiled array where a
    single row it may not; those past `pieces` are never read."""

    lanes: int
    pieces: int
    sublanes: int

    @classmethod
    def of(cls, d: int) -> "_Packed":
        lanes = 128 if d % 128 == 0 else d
        pieces = d // lanes
        return cls(lanes, pieces, -(-pieces // _SUBLANES) * _SUBLANES)

    def shape(self, m: int) -> tuple[int, int]:
        return (m * self.sublanes, self.lanes)

    def piece(self, ref, s: int, rows: int):
        """Piece s of each of a packed block's `rows` rows: [rows, lanes]."""
        return ref[pl.ds(s, rows, stride=self.sublanes), :]

    def fetch(self, src_hbm, row, buf, place, sem):
        """The DMA of packed row `row` of `src_hbm` to place `place` of
        `buf`."""
        at = lambda i: pl.ds(
            pl.multiple_of(i * self.sublanes, self.sublanes), self.sublanes
        )
        return pltpu.make_async_copy(src_hbm.at[at(row)], buf.at[at(place)], sem)

    def wait(self, src_hbm, buf, sem, count):
        """Until `count` fetched rows have landed."""
        def one(_, carry):
            self.fetch(src_hbm, 0, buf, 0, sem).wait()
            return carry

        lax.fori_loop(0, count, one, 0)

    def store(self, ref, value, onto=None):
        """value [R, d] into a packed block, added to the packed block
        `onto` where one is given."""
        rows = value.shape[0]
        for s in range(self.pieces):
            piece = value[:, s * self.lanes:(s + 1) * self.lanes]
            if onto is not None:
                piece = piece + self.piece(onto, s, rows)
            ref[pl.ds(s, rows, stride=self.sublanes), :] = piece.astype(ref.dtype)


def _round_block(src_ref, dst_ref):
    """A block rounded to `dst_ref`'s dtype, `_ROUND_ROWS` rows a pass:
    a whole block taken as one value would lie in VMEM a second time."""
    rows = src_ref.shape[0]
    step = _tile(rows, _ROUND_ROWS)

    def some(i, carry):
        at = pl.ds(pl.multiple_of(i * step, step), step)
        dst_ref[at, :] = src_ref[at, :].astype(dst_ref.dtype)
        return carry

    lax.fori_loop(0, rows // step, some, 0)


def _rounded_weights(te_ref, rhs_ref, rounded_ref, *ahead, live,
                     transpose_rhs: bool):
    """The weight block of this grid step in the rows' dtype, where the
    weights lie in another: `rounded_ref`, filled once each time a block
    is fetched. With the contraction in steps (`ahead` empty) the pipeline
    fetches `rhs_ref`'s block every step and every step in use rounds it.
    With the contraction in one step `rhs_ref` is the whole operand, left
    in HBM, and the fetch is the kernel's own (`ahead`: one block in the
    weights' dtype and its semaphore): an expert's first row tile within
    a column tile waits for its block, rounds it, and starts the NEXT
    expert's into the block it has just read (every held expert has a
    tile in use, `tiles_in_use`, so expert e + 1 follows e); the expert's
    other row tiles read the rounded block. A block is so fetched behind
    all of an expert's matmuls, where the pipeline, a grid step ahead,
    would fetch it behind the last one's and hold two of them."""
    if not ahead:
        pl.when(live)(lambda: _round_block(rhs_ref.at[0], rounded_ref.at[0]))
        return rounded_ref
    wide, sem = ahead
    o, t = pl.program_id(0), pl.program_id(1)
    e = te_ref[t]
    to = rounded_ref.shape[1] if transpose_rhs else rounded_ref.shape[2]

    def fetch(expert):
        cols = pl.ds(pl.multiple_of(o * to, to), to)
        block = rhs_ref.at[expert, cols, :] if transpose_rhs else (
            rhs_ref.at[expert, :, cols]
        )
        return pltpu.make_async_copy(block, wide, sem)

    @pl.when(live & ((t == 0) | (te_ref[jnp.maximum(t - 1, 0)] != e)))
    def _an_experts_first_tile():
        pl.when(t == 0)(lambda: fetch(e).start())
        fetch(e).wait()
        _round_block(wide, rounded_ref.at[0])
        pl.when(e + 1 < rhs_ref.shape[0])(lambda: fetch(e + 1).start())

    return rounded_ref


def _gmm_kernel(te_ref, nt_ref, *refs, transpose_rhs: bool, saved: int,
                wrt: int, act: bool, gated: bool, onto: bool, packed: bool):
    lhs_ref, rhs_ref, *refs = refs
    saved_refs, refs = refs[:saved], refs[saved:]
    gate_ref = refs.pop(0) if gated else None
    onto_ref = refs.pop(0) if onto else None
    out_ref, *refs = refs
    pre_ref = refs.pop(0) if act else None
    acc, *rounding = refs
    t, c = pl.program_id(1), pl.program_id(2)
    live = t < nt_ref[0]

    @pl.when(c == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    if rounding:  # the weights lie in another dtype than the rows'
        rhs_ref = _rounded_weights(
            te_ref, rhs_ref, *rounding, live=live, transpose_rhs=transpose_rhs
        )

    @pl.when(live)
    def _compute():
        dims = (((1,), (1,)), ((), ())) if transpose_rhs else (
            ((1,), (0,)), ((), ())
        )
        lhs = lhs_ref[...]
        if saved:
            lhs = _slope(lhs, [ref[...] for ref in saved_refs], wrt)
        acc[...] += lax.dot_general(
            lhs, rhs_ref[0], dims, preferred_element_type=jnp.float32,
        )

    @pl.when((c == pl.num_programs(2) - 1) & live)
    def _write():
        value = acc[...]
        if act:
            pre_ref[...] = value.astype(pre_ref.dtype)
            value = _activate(value, gate_ref[...] if gated else None)
        if packed:
            _Packed.of(value.shape[1]).store(out_ref, value, onto_ref)
        else:
            if onto:
                value += onto_ref[...].astype(jnp.float32)
            out_ref[...] = value.astype(out_ref.dtype)


def _dw_kernel(te_ref, nt_ref, lhs_ref, g_ref, *refs, wrt: int):
    *saved_refs, out_ref, acc = refs
    t = pl.program_id(2)
    n_tiles = nt_ref[0]
    e = te_ref[t]
    used = t < n_tiles
    first = (t == 0) | (te_ref[jnp.maximum(t - 1, 0)] != e)
    last = (t == n_tiles - 1) | (
        te_ref[jnp.minimum(t + 1, pl.num_programs(2) - 1)] != e
    )

    @pl.when(used & first)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(used)
    def _compute():
        g = g_ref[...]
        if saved_refs:
            g = _slope(g, [ref[...] for ref in saved_refs], wrt)
        acc[...] += lax.dot_general(
            lhs_ref[...], g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(used & last)
    def _write():
        out_ref[0] = acc[...].astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "wrt", "block_rows", "transpose_rhs", "act", "packed", "interpret",
    ),
)
def _gmm(lhs, rhs, tile_expert, n_tiles, saved=(), gate=None, onto=None, *,
         wrt=0, block_rows, transpose_rhs=False, act=False, packed=False,
         interpret):
    """One grouped matmul over the row buffer; nothing is written past
    the tiles in use. `act`: the result is the expert's activation of the
    product (`_activate`: `relu(.)^2`, or `silu(gate) .` with `gate`, the
    gate's product [rows, cols]) and a second result is the product
    itself. `saved`: the operand is `lhs` times the activation's slope by
    its product `wrt` (`_slope`), formed in VMEM. `onto`: a result of
    this call's own kind, which the product is added to in place.
    `packed`: the result is float32 and packed (`_Packed`: a
    `moe_rows_sum` reads it)."""
    rows, contract = lhs.shape
    cols = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tc, to = _gmm_tiles(contract, cols, packed)
    steps = contract // tc
    at = lambda t, c, nt: _live_step(t, c, steps, nt)
    if transpose_rhs:
        rhs_spec = pl.BlockSpec(
            (1, to, tc),
            lambda o, t, c, te, nt: (te[_live(t, nt)], o, at(t, c, nt)),
        )
    else:
        rhs_spec = pl.BlockSpec(
            (1, tc, to),
            lambda o, t, c, te, nt: (te[_live(t, nt)], at(t, c, nt), o),
        )
    operand = pl.BlockSpec(
        (block_rows, tc), lambda o, t, c, te, nt: (_live(t, nt), at(t, c, nt))
    )
    plain = pl.BlockSpec(
        (block_rows, to), lambda o, t, c, te, nt: (_live(t, nt), o)
    )
    result, out_shape = plain, jax.ShapeDtypeStruct((rows, cols), lhs.dtype)
    if packed:
        form = _Packed.of(cols)
        result = pl.BlockSpec(
            form.shape(block_rows), lambda o, t, c, te, nt: (_live(t, nt), 0)
        )
        out_shape = jax.ShapeDtypeStruct(form.shape(rows), jnp.float32)
    gated, added = gate is not None, onto is not None
    # weights that lie in another dtype than the rows': a block rounded in
    # VMEM (`_rounded_weights`), fetched an expert ahead by the kernel where
    # the contraction is one step; the limit grows by what the blocks take
    # beyond the two in the rows' dtype the tile caps are sized for
    block, more_vmem, rounding = rhs_spec.block_shape, 0, []
    if rhs.dtype != lhs.dtype:
        rounding = [pltpu.VMEM(block, lhs.dtype)]
        fetched = 2  # the pipeline's two buffers
        if steps == 1:
            rounding += [pltpu.VMEM(block[1:], rhs.dtype), pltpu.SemaphoreType.DMA(())]
            rhs_spec, fetched = pl.BlockSpec(memory_space=pl.ANY), 1
        more_vmem = math.prod(block) * max(
            fetched * rhs.dtype.itemsize - lhs.dtype.itemsize, 0
        )
    operands = (
        tile_expert, n_tiles, lhs, rhs, *saved, *([gate] * gated),
        *([onto] * added),
    )
    out = pl.pallas_call(
        functools.partial(
            _gmm_kernel, transpose_rhs=transpose_rhs, saved=len(saved),
            wrt=wrt, act=act, gated=gated, onto=added, packed=packed,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(cols // to, rows // block_rows, steps),
            in_specs=[operand, rhs_spec] + [operand] * len(saved)
            + [plain] * gated + [result] * added,
            out_specs=[result] * (1 + act),
            scratch_shapes=[pltpu.VMEM((block_rows, to), jnp.float32)] + rounding,
        ),
        out_shape=[out_shape] * (1 + act),
        input_output_aliases={len(operands) - 1: 0} if added else {},
        compiler_params=_params(("parallel", "arbitrary", "arbitrary"), more_vmem),
        interpret=interpret,
        name="moe_gmm_dlhs" if transpose_rhs else "moe_gmm_fwd",
    )(*operands)
    return tuple(out) if act else out[0]


@functools.partial(
    jax.jit,
    static_argnames=("wrt", "n_experts", "block_rows", "out_dtype", "interpret"),
)
def _gmm_dw(lhs, g, tile_expert, n_tiles, saved=(), *, wrt=0, n_experts,
            block_rows, out_dtype, interpret):
    """`dw[e] = lhs[rows of e].T @ g[rows of e]`; with `saved`, g is g
    times the activation's slope by its product `wrt` (`_slope`), formed
    in VMEM."""
    rows, k = lhs.shape
    cols = g.shape[1]
    to = _tile(cols, _TILE_SIDE)
    tk = _tile(k, min(_TILE_SIDE, max(128, _TILE_DW // to)))
    g_spec = pl.BlockSpec(
        (block_rows, to), lambda i, o, t, te, nt: (_live(t, nt), o)
    )
    return pl.pallas_call(
        functools.partial(_dw_kernel, wrt=wrt),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(k // tk, cols // to, rows // block_rows),
            in_specs=[
                pl.BlockSpec(
                    (block_rows, tk),
                    lambda i, o, t, te, nt: (_live(t, nt), i),
                ),
                g_spec,
            ] + [g_spec] * len(saved),
            out_specs=pl.BlockSpec(
                (1, tk, to), lambda i, o, t, te, nt: (te[_live(t, nt)], i, o)
            ),
            scratch_shapes=[pltpu.VMEM((tk, to), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n_experts, k, cols), out_dtype),
        compiler_params=_params(("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="moe_gmm_dw",
    )(tile_expert, n_tiles, lhs, g, *saved)


# -- the rows' movers at k > 1 -------------------------------------------------


def _pack(x):
    """[M, d] packed (`_Packed`), float32. A relayout on the TPU; only
    token-sized arrays go through it."""
    m, d = x.shape
    form = _Packed.of(d)
    x = x.astype(jnp.float32).reshape(m, form.pieces, form.lanes)
    x = jnp.pad(x, ((0, 0), (0, form.sublanes - form.pieces), (0, 0)))
    return x.reshape(form.shape(m))


def _turned(vector):
    """(1, n) -> (n, 1) or back, by the diagonal of its broadcast (a
    lane vector for the sublanes, which no transpose of one row gives)."""
    n, along = max(vector.shape), vector.shape.index(1)
    eye = lax.broadcasted_iota(jnp.int32, (n, n), 0) == lax.broadcasted_iota(
        jnp.int32, (n, n), 1
    )
    return jnp.sum(
        jnp.where(eye, vector, jnp.zeros_like(vector)), axis=1 - along,
        keepdims=True,
    )


def _compilable(form: _Packed, interpret: bool):
    if not interpret and form.lanes != 128:
        raise ValueError(
            "more than one expert a token: the rows' movers are compiled "
            f"for widths made of 128 lanes, not {form.lanes * form.pieces}"
        )


def _take_kernel(nt_ref, index_ref, x_hbm, token_ref, scale_ref, *refs,
                 tokens: int, dot: bool):
    *refs, buf, sem = refs
    other_ref, out_ref, dot_ref = refs if dot else (None, *refs, None)
    rows, d = out_ref.shape
    form = _Packed.of(d)
    lanes = form.lanes

    @pl.when(pl.program_id(0) < nt_ref[0])
    def _tile_in_use():
        def issue(r, count):
            token = index_ref[0, 0, r]
            real = token < tokens

            @pl.when(real)
            def _():
                form.fetch(x_hbm, token, buf, r, sem).start()

            return count + real.astype(jnp.int32)

        form.wait(x_hbm, buf, sem, lax.fori_loop(0, rows, issue, 0))
        keep = _turned(token_ref[0]) < tokens  # a row of padding: zeros
        scale = _turned(scale_ref[0])
        inner = jnp.zeros((rows, lanes), jnp.float32)
        for s in range(form.pieces):
            piece = jnp.where(keep, form.piece(buf, s, rows), 0.0)
            out_ref[:, s * lanes:(s + 1) * lanes] = (piece * scale).astype(
                out_ref.dtype
            )
            if dot:
                inner += piece * form.piece(other_ref, s, rows)
        if dot:
            dot_ref[0] = _turned(jnp.sum(inner, axis=1, keepdims=True))


@functools.partial(
    jax.jit, static_argnames=("width", "block_rows", "out_dtype", "interpret")
)
def _rows_take(x, row_token, n_tiles, scale=None, other=None, *, width,
               block_rows, out_dtype, interpret):
    """Row side: `out[r] = scale[r] x[row_token[r]]` over the tiles in
    use, zeros for a row of padding; tiles past `n_tiles` are left alone.
    x: [N, `width`] packed, left in HBM and fetched a row at a time;
    `scale` [rows] float32 (ones where None). With `other` ([rows,
    width] packed) also `<other[r], x[row_token[r]]>` [rows]."""
    d, form = width, _Packed.of(width)
    _compilable(form, interpret)
    tokens = x.shape[0] // form.sublanes
    rows = row_token.shape[0]
    tiles = rows // block_rows
    by_tile = lambda v: v.reshape(tiles, 1, block_rows)
    if scale is None:
        scale = jnp.ones((rows,), jnp.float32)
    lane_vector = pl.BlockSpec(
        (1, 1, block_rows), lambda t, nt: (_live(t, nt), 0, 0)
    )
    dot = other is not None
    out = pl.pallas_call(
        functools.partial(_take_kernel, tokens=tokens, dot=dot),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(tiles,),
            in_specs=[
                pl.BlockSpec(
                    (1, 1, block_rows), lambda t, nt: (_live(t, nt), 0, 0),
                    memory_space=pltpu.SMEM,
                ),
                pl.BlockSpec(memory_space=pl.ANY),
                lane_vector, lane_vector,
            ] + [
                pl.BlockSpec(
                    form.shape(block_rows), lambda t, nt: (_live(t, nt), 0)
                )
            ] * dot,
            out_specs=[
                pl.BlockSpec((block_rows, d), lambda t, nt: (_live(t, nt), 0))
            ] + [lane_vector] * dot,
            scratch_shapes=[
                pltpu.VMEM(form.shape(block_rows), jnp.float32),
                pltpu.SemaphoreType.DMA(()),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((rows, d), out_dtype)] + [
            jax.ShapeDtypeStruct((tiles, 1, block_rows), jnp.float32)
        ] * dot,
        compiler_params=_params(("arbitrary",)),
        interpret=interpret,
        name="moe_rows_take",
    )(n_tiles, by_tile(row_token), x, by_tile(row_token), by_tile(scale),
      *([other] * dot))
    return (out[0], out[1].reshape(rows)) if dot else out[0]


def _sum_tokens(tokens: int, slots: int, d: int) -> int:
    """Tokens a grid step of `moe_rows_sum` takes: the most, up to
    `_SUM_TOKENS` and in whole sublane tiles, that divide `tokens` and
    keep the gathered rows inside `_SUM_VMEM`."""
    for t in (_SUM_TOKENS, 128, 64, 32, 16, 8):
        if tokens % t == 0 and slots * t * d * 4 <= _SUM_VMEM:
            return t
    return tokens


def _sum_kernel(index_ref, count_ref, rows_hbm, held_ref, *refs,
                weighted: bool):
    *w_ref, out_ref, buf, acc, sem = refs
    tokens, d = out_ref.shape
    form = _Packed.of(d)
    lanes = form.lanes
    slots = buf.shape[0]

    def per_token(n, carry):
        issued, most = carry
        count = count_ref[0, 0, n]

        def per_slot(j, _):
            form.fetch(rows_hbm, index_ref[0, j, n], buf.at[j], n, sem).start()
            return _

        lax.fori_loop(0, count, per_slot, 0)
        return issued + count, jnp.maximum(most, count)

    issued, most = lax.fori_loop(0, tokens, per_token, (0, 0))
    form.wait(rows_hbm, buf.at[0], sem, issued)
    held = _turned(held_ref[0])
    acc[...] = jnp.zeros_like(acc)
    for j in range(slots):

        @pl.when(j < most)  # a token's slots fill from 0: most are empty
        def _slot():
            has = held > j
            weight = _turned(w_ref[0][0, j:j + 1, :]) if weighted else None
            for s in range(form.pieces):
                piece = jnp.where(has, form.piece(buf.at[j], s, tokens), 0.0)
                acc[:, s * lanes:(s + 1) * lanes] += (
                    piece * weight if weighted else piece
                )

    out_ref[...] = acc[...].astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("width", "out_dtype", "interpret")
)
def _rows_sum(rows, token_rows, token_count, weight=None, *, width, out_dtype,
              interpret):
    """Token side: `y[n] = sum_j weight[j, n] rows[token_rows[j, n]]` in
    float32 over the `token_count[n]` slots token n has (weights of one
    where None). rows: [rows, `width`] packed, left in HBM; only rows the
    plan names are fetched."""
    slots, tokens = token_rows.shape
    d, form = width, _Packed.of(width)
    _compilable(form, interpret)
    step = _sum_tokens(tokens, slots, d)
    by_step = lambda v: v.reshape(-1, tokens // step, step).swapaxes(0, 1)
    weighted = weight is not None
    return pl.pallas_call(
        functools.partial(_sum_kernel, weighted=weighted),
        grid=(tokens // step,),
        in_specs=[
            pl.BlockSpec(
                (1, slots, step), lambda i: (i, 0, 0), memory_space=pltpu.SMEM
            ),
            pl.BlockSpec(
                (1, 1, step), lambda i: (i, 0, 0), memory_space=pltpu.SMEM
            ),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, 1, step), lambda i: (i, 0, 0)),
        ] + [pl.BlockSpec((1, slots, step), lambda i: (i, 0, 0))] * weighted,
        out_specs=pl.BlockSpec((step, d), lambda i: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((slots, *form.shape(step)), jnp.float32),
            pltpu.VMEM((step, d), jnp.float32),
            pltpu.SemaphoreType.DMA(()),
        ],
        out_shape=jax.ShapeDtypeStruct((tokens, d), out_dtype),
        compiler_params=_params(("arbitrary",)),
        interpret=interpret,
        name="moe_rows_sum",
    )(by_step(token_rows), by_step(token_count), rows, by_step(token_count),
      *([by_step(weight)] if weighted else []))


# -- the plan's rows at k > 1 --------------------------------------------------


def _by_bytes(bits, parts: int, product):
    """`product` of int32 `bits` taken a byte at a time and put together
    again: whole numbers under 256 are exact in bfloat16, so a product
    with zeros and a single one moves any 32 bits, a float's too, to the
    bit."""
    return functools.reduce(jnp.bitwise_or, (
        product(
            ((bits >> (8 * p)) & 255).astype(jnp.float32).astype(jnp.bfloat16)
        ).astype(jnp.int32) << (8 * p)
        for p in range(parts)
    ))


def _plan_kernel(te_ref, nt_ref, *refs, weighted: bool, parts: int):
    del nt_ref  # the grid's length
    first_ref, index_ref, table_ref, out_ref = refs if not weighted else (
        None, *refs
    )
    t = pl.program_id(0)
    rows = out_ref.shape[2]
    lines, lanes = table_ref.shape[1:]
    table = table_ref[0]
    step = min(rows, _PLAN_ROWS)
    for r in range(0, rows, step):
        if weighted:  # the rows' tokens, along the lanes: a token's line
            token = _turned(index_ref[0, :, r:r + step])
            line, lane = token // lanes, token % lanes
        else:  # the expert's count at each line's end: the lines before
            number = (t - first_ref[te_ref[t]]) * rows + r + (
                lax.broadcasted_iota(jnp.int32, (step, 1), 0)
            )
            line = jnp.sum(
                (index_ref[0] <= number).astype(jnp.int32), axis=1, keepdims=True
            )
        its = (
            lax.broadcasted_iota(jnp.int32, (step, lines), 1) == line
        ).astype(jnp.float32).astype(jnp.bfloat16)  # no line: zeros
        picked = _by_bytes(table, parts, lambda plane: jnp.dot(
            its, plane, preferred_element_type=jnp.float32
        ))
        if weighted:
            at = lax.broadcasted_iota(jnp.int32, (step, lanes), 1) == lane
            found = jnp.sum(jnp.where(at, picked, 0), axis=1, keepdims=True)
        else:
            found = jnp.minimum(line * lanes + jnp.sum(
                (picked <= number).astype(jnp.int32), axis=1, keepdims=True
            ), lines * lanes)
        out_ref[0, :, r:r + step] = _turned(found)


def _interpreted(interpret: bool | None) -> bool:
    """The kernels' form where the caller leaves it open: compiled
    wherever the backend compiles them (`ops/flash.kernels_compiled`)."""
    return not kernels_compiled() if interpret is None else interpret


def _token_lines(tokens: int, interpret: bool) -> tuple[int, int]:
    """(lines, lanes) of an expert's tokens as the plan's kernels hold
    them, `[N / 128, 128]` (one line where N is not made of them, which
    only the interpreter takes)."""
    if not interpret and tokens % 128:
        raise ValueError(
            "more than one expert a token: the plan's rows are compiled "
            f"for tokens in whole lines of 128, not {tokens}"
        )
    lanes = 128 if tokens % 128 == 0 else tokens
    return tokens // lanes, lanes


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _plan_rows(table, tile_expert, n_tiles, tile_first=None, row_token=None, *,
               block_rows, interpret):
    """What each row in use finds in its held expert's line of `table`
    [n_held, N]; tiles past `n_tiles` are not visited (the grid ends
    there). `table` the expert's running count of its tokens, with
    `tile_first` [n_held]: the row's token, row i of expert e being
    `#{n : count[e, n] <= i}` (N where e has no such row): the whole
    lines of `lanes` tokens that end at or under i, counted from the
    lines' last counts, and of the next line, picked by a product with a
    one at its number, the tokens at or under i. `table` float32 with
    `row_token` [rows]: its value at the row's token, found the same way
    (zero for a row of padding). [rows] of the table's dtype; nothing is
    sized by more than the rows in use times a line."""
    n_held, tokens = table.shape
    lines, lanes = _token_lines(tokens, interpret)
    tiles = tile_expert.shape[0]
    weighted = row_token is not None
    by_expert = lambda *block: pl.BlockSpec(
        (1, *block), lambda t, te, *_: (te[t], 0, 0)
    )
    by_tile = pl.BlockSpec((1, 1, block_rows), lambda t, *_: (t, 0, 0))
    bits = lax.bitcast_convert_type(table, jnp.int32)
    if weighted:
        scalars = (tile_expert, n_tiles)
        index, index_spec = row_token.reshape(tiles, 1, block_rows), by_tile
    else:
        scalars = (tile_expert, n_tiles, tile_first)
        index = lax.slice_in_dim(  # the lines' last counts
            table.reshape(n_held, lines, lanes), lanes - 1, lanes, axis=2
        ).reshape(n_held, 1, lines)
        index_spec = by_expert(1, lines)
    out = pl.pallas_call(
        functools.partial(
            _plan_kernel, weighted=weighted,
            parts=4 if weighted else -(-tokens.bit_length() // 8),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(n_tiles[0],),
            in_specs=[index_spec, by_expert(lines, lanes)],
            out_specs=by_tile,
        ),
        out_shape=jax.ShapeDtypeStruct((tiles, 1, block_rows), jnp.int32),
        compiler_params=_params(("arbitrary",)),
        interpret=interpret,
        name="moe_plan_weights" if weighted else "moe_plan_rows",
    )(*scalars, index, bits.reshape(n_held, lines, lanes))
    return lax.bitcast_convert_type(out.reshape(tiles * block_rows), table.dtype)


def rows_values(plan, value, interpret: bool | None = None):
    """`value[e, n]` [n_held, N] float32 at each row in use, e its
    tile's expert and n its token (zero for a row of padding); tiles
    past `n_tiles` are left alone. No element is scattered or gathered
    one by one (`_plan_rows`)."""
    te, row_token = plan["tile_expert"], plan["row_token"]
    return _plan_rows(
        value, te, plan["n_tiles"], row_token=row_token,
        block_rows=row_token.shape[0] // te.shape[0],
        interpret=_interpreted(interpret),
    )


def _tokens_kernel(te_ref, nt_ref, token_ref, value_ref, out_ref):
    del nt_ref  # the grid's length
    t = pl.program_id(0)
    rows = token_ref.shape[2]
    lines, lanes = out_ref.shape[1:]

    @pl.when((t == 0) | (te_ref[jnp.maximum(t - 1, 0)] != te_ref[t]))
    def _an_experts_first_tile():
        out_ref[...] = jnp.zeros_like(out_ref)

    step = min(rows, _PLAN_ROWS)
    for r in range(0, rows, step):
        token = _turned(token_ref[0, :, r:r + step])
        bits = _turned(value_ref[0, :, r:r + step])
        its = (  # a row of padding (token N) has no line: zeros
            lax.broadcasted_iota(jnp.int32, (step, lines), 1) == token // lanes
        ).astype(jnp.float32).astype(jnp.bfloat16)
        at = lax.broadcasted_iota(jnp.int32, (step, lanes), 1) == token % lanes
        # a token is one row's: each place is a sum of one term
        out_ref[0] |= _by_bytes(
            jnp.where(at, bits, 0), 4, lambda plane: lax.dot_general(
                its, plane, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ),
        )


@functools.partial(jax.jit, static_argnames=("n_held", "tokens", "interpret"))
def _plan_tokens(value, row_token, tile_expert, n_tiles, *, n_held, tokens,
                 interpret):
    """`_plan_rows`' transpose: `value` [rows] float32 at each held
    expert's tokens, [n_held, N], zeros where token n is not e's. A row
    in use puts its value at its token's place in its expert's `[N / 128,
    128]` block, which stays in VMEM over the expert's tiles: a product
    of the rows' lines with their values at their lanes, a byte a
    product."""
    lines, lanes = _token_lines(tokens, interpret)
    tiles = tile_expert.shape[0]
    by_tile = pl.BlockSpec(
        (1, 1, row_token.shape[0] // tiles), lambda t, *_: (t, 0, 0)
    )
    out = pl.pallas_call(
        _tokens_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_tiles[0],),
            in_specs=[by_tile, by_tile],
            out_specs=pl.BlockSpec(
                (1, lines, lanes), lambda t, te, nt: (te[t], 0, 0)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((n_held, lines, lanes), jnp.int32),
        compiler_params=_params(("arbitrary",)),
        interpret=interpret,
        name="moe_plan_tokens",
    )(tile_expert, n_tiles, row_token.reshape(tiles, 1, -1),
      lax.bitcast_convert_type(value, jnp.int32).reshape(tiles, 1, -1))
    return lax.bitcast_convert_type(out, value.dtype).reshape(n_held, tokens)


def tokens_values(plan, value, interpret: bool | None = None):
    """`rows_values`' transpose: `value` [rows] float32, a number a row,
    at each held expert's tokens: [n_held, N], `value[r]` where row r is
    expert e's and token n's, zeros where token n is not routed to e
    (`_plan_tokens`)."""
    _, n_held, tokens = plan["hit"].shape
    return _plan_tokens(
        value, plan["row_token"], plan["tile_expert"], plan["n_tiles"],
        n_held=n_held, tokens=tokens, interpret=_interpreted(interpret),
    )


# -- dispatch, matmuls, combine ------------------------------------------------


class _How(NamedTuple):
    """What the two halves of `expert_mlp` are built from: static."""

    block_rows: int
    interpret: bool
    by_pairs: bool  # k > 1: the rows move by `moe_rows_*`, packed


def _take(x, index):
    return jnp.take(x, index, axis=0, mode="fill", fill_value=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _experts_in(x, weights, plan, how: _How):
    """x [N, d] to its rows, then the expert's activation of `rows @
    w[e]` for each w of `weights` ([experts, d, f], any float dtype; one
    for `relu(.)^2`, gate and up for `silu(a) b`): [rows, f]."""
    return _experts_in_fwd(x, weights, plan, how)[0]


def _experts_in_fwd(x, weights, plan, how):
    te, nt = plan["tile_expert"], plan["n_tiles"]
    kernel = dict(block_rows=how.block_rows, interpret=how.interpret)
    with jax.named_scope("moe.dispatch"):
        if how.by_pairs:
            rows = _rows_take(
                _pack(x), plan["row_token"], nt, width=x.shape[1],
                out_dtype=x.dtype, **kernel,
            )
        else:
            rows = _take(x, plan["src"])
    with jax.named_scope("moe.experts"):
        *w_gate, w_in = weights
        gate = [_gmm(rows, w, te, nt, **kernel) for w in w_gate]
        hidden, pre = _gmm(
            rows, w_in, te, nt, gate=gate[0] if gate else None, act=True,
            **kernel,
        )
    return hidden, (rows, weights, plan, (*gate, pre))


def _experts_in_bwd(how, res, g):
    rows, weights, plan, saved = res
    te, nt = plan["tile_expert"], plan["n_tiles"]
    kernel = dict(block_rows=how.block_rows, interpret=how.interpret)
    with jax.named_scope("moe.experts"):
        d_rows = None  # each matrix's part is added onto the one before
        for wrt, w in enumerate(weights):
            d_rows = _gmm(
                g, w, te, nt, saved, onto=d_rows, wrt=wrt,
                transpose_rhs=True, packed=how.by_pairs, **kernel,
            )
        d_weights = tuple(
            _gmm_dw(
                rows, g, te, nt, saved, wrt=wrt, n_experts=w.shape[0],
                out_dtype=w.dtype, **kernel,
            )
            for wrt, w in enumerate(weights)
        )
    with jax.named_scope("moe.dispatch"):
        if how.by_pairs:
            dx = _rows_sum(
                d_rows, plan["token_rows"], plan["token_count"],
                width=rows.shape[1], out_dtype=rows.dtype,
                interpret=how.interpret,
            )
        else:
            dx = _take(d_rows, plan["dst"])
    return dx, d_weights, None


_experts_in.defvjp(_experts_in_fwd, _experts_in_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _experts_out(hidden, w_down, weight, plan, how: _How):
    """`hidden @ w_down[e]` [rows, d], then each token's rows by their
    weights (`weight` float32: [N], or [n_held, N] by held expert, zeros
    where a token is not routed to it), added up in float32: [N, d]."""
    return _experts_out_fwd(hidden, w_down, weight, plan, how)[0]


def _experts_out_fwd(hidden, w_down, weight, plan, how):
    with jax.named_scope("moe.experts"):
        out = _gmm(
            hidden, w_down, plan["tile_expert"], plan["n_tiles"],
            block_rows=how.block_rows, packed=how.by_pairs,
            interpret=how.interpret,
        )
    if how.by_pairs:
        with jax.named_scope("moe.plan"):
            by_slot = _by_slot(weight, plan)
    with jax.named_scope("moe.combine"):
        if how.by_pairs:
            y = _rows_sum(
                out, plan["token_rows"], plan["token_count"], by_slot,
                width=w_down.shape[2], out_dtype=hidden.dtype,
                interpret=how.interpret,
            )
        else:
            out = _take(out, plan["dst"])  # a token's row: what is kept
            y = (out * weight[:, None]).astype(hidden.dtype)
    return y, (hidden, w_down, weight, plan, out)


def _experts_out_bwd(how, res, g):
    hidden, w_down, weight, plan, out = res
    te, nt = plan["tile_expert"], plan["n_tiles"]
    kernel = dict(block_rows=how.block_rows, interpret=how.interpret)
    if how.by_pairs:
        with jax.named_scope("moe.plan"):  # a row's weight: its token's
            by_row = rows_values(plan, weight, how.interpret)
        with jax.named_scope("moe.combine"):
            d_out, inner = _rows_take(
                _pack(g), plan["row_token"], nt, by_row, out,
                width=g.shape[1], out_dtype=hidden.dtype, **kernel,
            )
        with jax.named_scope("moe.plan"):  # and a token's, its row's product
            d_weight = tokens_values(plan, inner, how.interpret)
    else:
        with jax.named_scope("moe.combine"):
            g = g.astype(jnp.float32)
            d_weight = jnp.sum(g * out, axis=1)
            d_out = _take(
                (g * weight[:, None]).astype(hidden.dtype), plan["src"]
            )
    with jax.named_scope("moe.experts"):
        d_hidden = _gmm(d_out, w_down, te, nt, transpose_rhs=True, **kernel)
        d_w = _gmm_dw(
            hidden, d_out, te, nt, n_experts=w_down.shape[0],
            out_dtype=w_down.dtype, **kernel,
        )
    return d_hidden, d_w, d_weight.astype(weight.dtype), None


_experts_out.defvjp(_experts_out_fwd, _experts_out_bwd)


def expert_mlp(
    x, expert, gate, weights, lo, *,
    block_rows: int = BLOCK_ROWS, interpret: bool | None = None,
):
    """What the experts held here add for the tokens routed to them.

    x: [N, d] tokens; expert: [N] or [N, k] int32 over all experts; gate:
    the same shape; `weights` of the `weights[0].shape[0]` experts from
    `lo` on, `(w_gate, w_up, w_down)` for a gated expert, `silu(x @
    w_gate[e]) * (x @ w_up[e]) @ w_down[e]`, or `(w_in, w_out)` for
    `relu(x @ w_in[e])^2 @ w_out[e]`. Returns [N, d]: the sum over a
    token's experts e that are held of `gate * expert_e(x)`, zeros for a
    token with none.
    """
    interpret = _interpreted(interpret)
    if len(weights) not in _FORMS:
        raise ValueError(f"an expert of {len(weights)} matrices: {_FORMS}")
    *into, w_down = weights
    how = _How(block_rows, interpret, expert.ndim == 2)
    with jax.named_scope("moe.plan"):
        plan = plan_dispatch(expert, lo, w_down.shape[0], block_rows, interpret)
        weight = held_weights(gate, plan) if how.by_pairs else gate
    hidden = _experts_in(x, tuple(into), plan, how)
    return _experts_out(hidden, w_down, weight, plan, how)


def expert_mlp_on_mesh(mesh: Mesh | None, x, expert, gate, weights, first: int):
    """`expert_mlp` for x [B, S, d] on a mesh (or off it, `mesh` None):
    `weights` of the experts held from `first` on (three matrices an
    expert or two, as `expert_mlp` takes them); `expert` and `gate`
    [B, S] or [B, S, k]. Tokens stay where the batch and `sp` axes put
    them; every `ep` shard holds a run of the experts and adds their
    part for the tokens routed to them, every `tp` shard a slice of each expert's width, and
    the partial results are summed over both. One shard: no exchange."""

    def local(x, expert, gate, weights, lo):
        pairs = lambda u: u.reshape(-1, *u.shape[2:])
        out = expert_mlp(
            x.reshape(-1, x.shape[-1]), pairs(expert), pairs(gate), weights, lo
        )
        return out.reshape(x.shape)

    if mesh is None:
        return local(x, expert, gate, weights, first)
    size = lambda a: mesh.shape.get(a, 1)
    batch = batch_axes(mesh)
    rows = 1
    for a in batch:
        rows *= size(a)
    held, _, width = weights[0].shape
    if (
        x.shape[0] % rows or x.shape[1] % size("sp")
        or held % size("ep") or width % size("tp")
    ):
        raise ValueError(
            f"the expert layer on mesh {dict(mesh.shape)} needs batch "
            f"{x.shape[0]}, sequence {x.shape[1]}, experts held {held} and "
            f"expert width {width} to divide over dp·fsdp, sp, ep and tp"
        )
    seq, ep, tp = (a if size(a) > 1 else None for a in ("sp", "ep", "tp"))
    partial_over = tuple(a for a in (ep, tp) if a)

    def shard(x, expert, gate, *weights):
        lo = first
        if ep:
            lo = first + lax.axis_index("ep") * weights[0].shape[0]
        out = local(x, expert, gate, weights, lo)
        return lax.psum(out, partial_over) if partial_over else out

    tokens = P(batch, seq, *([None] * (expert.ndim - 2)))
    into, out_of = P(ep, None, tp), P(ep, tp, None)
    return jax.shard_map(
        shard,
        mesh=mesh,
        in_specs=(
            P(batch, seq, None), tokens, tokens,
            *([into] * (len(weights) - 1)), out_of,
        ),
        out_specs=P(batch, seq, None),
        check_vma=False,
    )(x, expert, gate, *weights)
