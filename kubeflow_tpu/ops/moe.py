"""Dropless top-k expert dispatch and grouped matmuls (Pallas TPU).

What `models/transformer.ExpertLayer` runs on the experts it holds: a
token has one row for each of its k experts; the rows are ordered by
expert into a row buffer, grouped matmuls (the expert MLP: gated, three
matrices, or `relu(.)^2`, two) run over it, and each token's rows are
gathered back and added up by their weights. No capacity and no dropped
token at any imbalance; the work follows the rows routed here, not
`experts x capacity`.

- **The plan** (`plan_dispatch`). Each held expert's tokens occupy a run
  of whole row tiles (`block_rows` rows; an expert with no token still
  gets one tile of zero rows, so every expert's weight gradient is
  written). The buffer is sized for the worst case: a token's k experts
  differ, so at most `min(k, experts)` of its rows are held here, and
  `ceil(N min(k, experts) / block_rows) + experts` tiles hold them;
  `tile_expert[t]` names tile t's expert and `n_tiles` how many tiles
  are in use. Pairs routed to experts held elsewhere get no row. A tile never straddles two experts, so the kernels need no
  masks: a grouped matmul is a tiled matmul whose weight block is picked
  by a scalar-prefetched table.
- **`moe_gmm_fwd` / `moe_gmm_dlhs`**: `out[rows of e] = lhs[rows of e] @
  w[e]` (or `@ w[e].T` for the operand's gradient). Grid (column tiles,
  row tiles, contraction tiles); consecutive row tiles of one expert keep
  the weight block's index, so an expert's weights are fetched once a
  column tile. Tiles past `n_tiles` cost a grid step, no matmul and no
  fetch (their indices are clamped to the last tile in use), and write
  zeros.
- **`moe_gmm_dw`**: `dw[e] = lhs[rows of e].T @ g[rows of e]`, accumulated
  in float32 over an expert's tiles and written once.
- **`take_rows`**: a row gather whose transpose is the inverse gather
  (a row names one token-expert pair and a pair at most one row), so
  neither direction scatters. **`spread_rows`** is the dispatch at k > 1,
  `rows[r] = x[token of r]`: its transpose is the sum of a token's
  gathers. The combine is `take_rows` the other way, then the weighted
  sum over a token's rows. Both read `min(k, experts held)` rows a
  token, the most it can have here (`plan_dispatch`'s `token_rows`).

`grouped_matmul` ties the three kernels together with a `custom_vjp`; the
weights go in at their own dtype (float32 parameters) and are cast for
the kernels in the forward and again in the backward, so no bfloat16 copy
of an expert's weights is kept as a residual. On the CPU backend the
kernels run under the Pallas interpreter (tests); anywhere else they are
compiled (`ops/flash.kernels_compiled`). Every `pallas_call` has a
`name=` starting `moe_gmm_`: what a device trace keys their time on.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from kubeflow_tpu.ops.flash import kernels_compiled
from kubeflow_tpu.parallel.sharding import batch_axes

BLOCK_ROWS = 256
# Tile caps: a weight block of 2048 x 2048 bf16 is 8 MiB (16 double-
# buffered), which keeps an expert of this width to one fetch a pass.
_TILE_CONTRACT = 2048
_TILE_COLS = 2048
_TILE_DW_ROWS = 1024  # dw's float32 accumulator is (this, _TILE_COLS)
_VMEM_LIMIT = 48 * 1024 * 1024


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


def plan_dispatch(expert, lo, n_held: int, block_rows: int = BLOCK_ROWS):
    """Where each token-expert pair's row is, from the expert ids.

    expert: [N] or [N, k] int32 (a token's k experts all differ), ids
    over ALL experts; this shard holds `lo .. lo + n_held - 1`. Pair
    `j * N + n` is token n's j-th expert: the pairs of one j lie
    together, so that [k·N, d] rows of pairs split into [k, N, d] with
    no relayout (on the TPU an array's two minor dimensions are tiled:
    [N·k, d] -> [N, k, d] is a copy, 55 ms a step in the Nemotron cell).
    Returns a dict: `dst` [k·N] (the pair's row, or `rows` = out of range
    where its expert is held elsewhere), `src` [rows] (the row's pair, or
    k·N for a row of padding), `tile_expert` [tiles] (local expert of
    each row tile), `n_tiles` [1] (tiles in use). For [N, k] also a
    token's rows side by side: of its k pairs at most `h = min(k,
    n_held)` are held here, so `token_rows` [h, N] lists them (`rows`
    where it has fewer), `token_pair` [h, N] the pair each came from
    (k·N where none), `row_token` [rows] a row's token (N for padding)
    and `row_place` [rows] its place in `token_rows` (h·N for padding):
    the gathers back to tokens read h rows a token, not k (8 for 22 in
    the Nemotron cell).
    """
    by_pairs = expert.ndim == 2
    k = expert.shape[1] if by_pairs else 1
    expert = expert.T.reshape(-1) if by_pairs else expert
    n = expert.shape[0]
    tiles = -(-(n // k) * min(k, n_held) // block_rows) + n_held
    rows = tiles * block_rows
    local = expert - lo
    held = (local >= 0) & (local < n_held)
    onehot = (
        local[:, None] == jnp.arange(n_held, dtype=local.dtype)[None, :]
    ).astype(jnp.int32)
    running = jnp.cumsum(onehot, axis=0)
    counts = running[-1]
    safe = jnp.clip(local, 0, n_held - 1)
    rank = jnp.take_along_axis(running, safe[:, None], axis=1)[:, 0] - 1
    group_tiles = jnp.maximum(-(-counts // block_rows), 1)
    ends = jnp.cumsum(group_tiles)
    first_row = (ends - group_tiles) * block_rows
    dst = jnp.where(held, first_row[safe] + rank, rows).astype(jnp.int32)
    src = jnp.full((rows,), n, jnp.int32).at[dst].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop"
    )
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(tiles), side="right"), n_held - 1
    ).astype(jnp.int32)
    plan = {
        "dst": dst, "src": src, "tile_expert": tile_expert,
        "n_tiles": ends[-1:].astype(jnp.int32),
    }
    if by_pairs:
        h, tokens = min(k, n_held), n // k
        here = held.reshape(k, tokens).astype(jnp.int32)
        # A pair's rank among its token's held pairs; h = none (dropped).
        slot = jnp.where(here > 0, jnp.cumsum(here, axis=0) - here, h)
        token = jnp.broadcast_to(jnp.arange(tokens, dtype=jnp.int32), slot.shape)
        table = lambda fill, values: jnp.full((h, tokens), fill, jnp.int32).at[
            slot, token
        ].set(values.reshape(k, tokens), mode="drop")
        row_slot = jnp.take(slot.reshape(-1), src, mode="fill", fill_value=h)
        real = row_slot < h
        plan.update(
            token_rows=table(rows, dst),
            token_pair=table(n, jnp.arange(n, dtype=jnp.int32)),
            row_token=jnp.where(real, src % tokens, tokens),
            row_place=jnp.where(real, row_slot * tokens + src % tokens, h * tokens),
        )
    return plan


@jax.custom_vjp
def take_rows(x, index, inverse):
    """`out[r] = x[index[r]]`, zeros where `index[r]` is out of range.
    `inverse[n]` is the r with `index[r] == n` (out of range where there
    is none; `index` names no row twice), which makes the transpose the
    same gather the other way round."""
    del inverse
    return jnp.take(x, index, axis=0, mode="fill", fill_value=0)


def _take_rows_fwd(x, index, inverse):
    return take_rows(x, index, inverse), (index, inverse)


def _take_rows_bwd(res, g):
    index, inverse = res
    return take_rows(g, inverse, index), None, None


take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@jax.custom_vjp
def spread_rows(x, row_token, token_rows):
    """`rows[r] = x[row_token[r]]`: a token's row once for each of its
    pairs that has one (`plan_dispatch`'s tables); zeros for a row of
    padding. The transpose adds a token's rows up, by gathers."""
    del token_rows
    return jnp.take(x, row_token, axis=0, mode="fill", fill_value=0)


def _spread_rows_fwd(x, row_token, token_rows):
    return spread_rows(x, row_token, token_rows), token_rows


def _spread_rows_bwd(token_rows, g):
    mine = jnp.take(g, token_rows, axis=0, mode="fill", fill_value=0)
    return jnp.sum(mine.astype(jnp.float32), axis=0).astype(g.dtype), None, None


spread_rows.defvjp(_spread_rows_fwd, _spread_rows_bwd)


# -- kernels -----------------------------------------------------------------


def _gmm_kernel(te_ref, nt_ref, lhs_ref, rhs_ref, out_ref, acc, *,
                transpose_rhs: bool):
    del te_ref
    t, c = pl.program_id(1), pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(t < nt_ref[0])
    def _compute():
        dims = (((1,), (1,)), ((), ())) if transpose_rhs else (
            ((1,), (0,)), ((), ())
        )
        acc[...] += lax.dot_general(
            lhs_ref[...], rhs_ref[0], dims,
            preferred_element_type=jnp.float32,
        )

    @pl.when(c == pl.num_programs(2) - 1)
    def _write():
        out_ref[...] = acc[...].astype(out_ref.dtype)


def _dw_kernel(te_ref, nt_ref, lhs_ref, g_ref, out_ref, acc):
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
        acc[...] += lax.dot_general(
            lhs_ref[...], g_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(used & last)
    def _write():
        out_ref[0] = acc[...].astype(out_ref.dtype)


def _params(semantics):
    return pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=_VMEM_LIMIT
    )


@functools.partial(
    jax.jit, static_argnames=("block_rows", "transpose_rhs", "interpret")
)
def _gmm(lhs, rhs, tile_expert, n_tiles, *, block_rows, transpose_rhs,
         interpret):
    rows, contract = lhs.shape
    cols = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tc, to = _tile(contract, _TILE_CONTRACT), _tile(cols, _TILE_COLS)
    # Tiles past the ones in use re-address the last one: no fetch.
    live = lambda t, nt: jnp.minimum(t, nt[0] - 1)
    if transpose_rhs:
        rhs_spec = pl.BlockSpec(
            (1, to, tc), lambda o, t, c, te, nt: (te[live(t, nt)], o, c)
        )
    else:
        rhs_spec = pl.BlockSpec(
            (1, tc, to), lambda o, t, c, te, nt: (te[live(t, nt)], c, o)
        )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(cols // to, rows // block_rows, contract // tc),
            in_specs=[
                pl.BlockSpec(
                    (block_rows, tc),
                    lambda o, t, c, te, nt: (live(t, nt), c),
                ),
                rhs_spec,
            ],
            out_specs=pl.BlockSpec(
                (block_rows, to), lambda o, t, c, te, nt: (t, o)
            ),
            scratch_shapes=[pltpu.VMEM((block_rows, to), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((rows, cols), lhs.dtype),
        compiler_params=_params(("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="moe_gmm_dlhs" if transpose_rhs else "moe_gmm_fwd",
    )(tile_expert, n_tiles, lhs, rhs)


@functools.partial(
    jax.jit,
    static_argnames=("n_experts", "block_rows", "out_dtype", "interpret"),
)
def _gmm_dw(lhs, g, tile_expert, n_tiles, *, n_experts, block_rows,
            out_dtype, interpret):
    rows, k = lhs.shape
    cols = g.shape[1]
    tk, to = _tile(k, _TILE_DW_ROWS), _tile(cols, _TILE_COLS)
    live = lambda t, nt: jnp.minimum(t, nt[0] - 1)
    return pl.pallas_call(
        _dw_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(k // tk, cols // to, rows // block_rows),
            in_specs=[
                pl.BlockSpec(
                    (block_rows, tk),
                    lambda i, o, t, te, nt: (live(t, nt), i),
                ),
                pl.BlockSpec(
                    (block_rows, to),
                    lambda i, o, t, te, nt: (live(t, nt), o),
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, tk, to), lambda i, o, t, te, nt: (te[t], i, o)
            ),
            scratch_shapes=[pltpu.VMEM((tk, to), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n_experts, k, cols), out_dtype),
        compiler_params=_params(("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="moe_gmm_dw",
    )(tile_expert, n_tiles, lhs, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def grouped_matmul(lhs, w, tile_expert, n_tiles, block_rows, interpret):
    """`out[rows of expert e] = lhs[rows of e] @ w[e]` over a row buffer
    laid out by `plan_dispatch`. lhs: [rows, K]; w: [experts, K, N] in any
    float dtype (cast to lhs's for the MXU, float32 accumulation)."""
    return _gmm(
        lhs, w.astype(lhs.dtype), tile_expert, n_tiles,
        block_rows=block_rows, transpose_rhs=False, interpret=interpret,
    )


def _grouped_fwd(lhs, w, tile_expert, n_tiles, block_rows, interpret):
    out = grouped_matmul(lhs, w, tile_expert, n_tiles, block_rows, interpret)
    return out, (lhs, w, tile_expert, n_tiles)


def _grouped_bwd(block_rows, interpret, res, g):
    lhs, w, tile_expert, n_tiles = res
    d_lhs = _gmm(
        g, w.astype(g.dtype), tile_expert, n_tiles,
        block_rows=block_rows, transpose_rhs=True, interpret=interpret,
    )
    d_w = _gmm_dw(
        lhs, g, tile_expert, n_tiles, n_experts=w.shape[0],
        block_rows=block_rows, out_dtype=w.dtype, interpret=interpret,
    )
    return d_lhs, d_w, None, None


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)


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
    if interpret is None:
        interpret = not kernels_compiled()
    n_held = weights[0].shape[0]
    one = expert.ndim == 1  # a row a token: the gathers are each other's inverse
    with jax.named_scope("moe.dispatch"):
        plan = plan_dispatch(expert, lo, n_held, block_rows)
        if one:
            rows = take_rows(x, plan["src"], plan["dst"])
        else:
            rows = spread_rows(x, plan["row_token"], plan["token_rows"])
    mm = lambda a, w: grouped_matmul(
        a, w, plan["tile_expert"], plan["n_tiles"], block_rows, interpret
    )
    with jax.named_scope("moe.experts"):
        if len(weights) == 3:
            w_gate, w_up, w_down = weights
            hidden = jax.nn.silu(mm(rows, w_gate)) * mm(rows, w_up)
        else:
            w_in, w_down = weights
            hidden = jnp.square(jax.nn.relu(mm(rows, w_in)))
        out = mm(hidden, w_down)
    with jax.named_scope("moe.combine"):
        if one:
            back = take_rows(out, plan["dst"], plan["src"])
            return (back * gate[:, None]).astype(x.dtype)
        back = take_rows(out, plan["token_rows"].reshape(-1), plan["row_place"])
        back = back.reshape(*plan["token_rows"].shape, back.shape[-1])
        weight = jnp.take(
            gate.T.reshape(-1), plan["token_pair"], mode="fill", fill_value=0
        )
        return jnp.sum(back * weight[:, :, None], axis=0).astype(x.dtype)


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
