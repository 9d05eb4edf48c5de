"""Dropless top-1 expert dispatch and grouped matmuls (Pallas TPU).

What `models/transformer.ExpertLayer` runs on the experts it holds: tokens
are ordered by expert into a row buffer, three grouped matmuls (the gated
expert MLP) run over it, and each token's row is gathered back. No
capacity and no dropped token at any imbalance; the work follows the
tokens routed here, not `experts x capacity`.

- **The plan** (`plan_dispatch`). Each held expert's tokens occupy a run
  of whole row tiles (`block_rows` rows; an expert with no token still
  gets one tile of zero rows, so every expert's weight gradient is
  written). The buffer is sized for the worst case, `ceil(N / block_rows)
  + experts` tiles; `tile_expert[t]` names tile t's expert and `n_tiles`
  how many tiles are in use. Tokens routed to experts held elsewhere get
  no row. A tile never straddles two experts, so the kernels need no
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
  (each token has at most one row), so neither direction scatters.

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
    """The largest tile <= cap that divides `dim` (halving from cap)."""
    t = min(dim, cap)
    while dim % t:
        t //= 2
    return t


def plan_dispatch(expert, lo, n_held: int, block_rows: int = BLOCK_ROWS):
    """Where each token's row is, from its expert id.

    expert: [N] int32, ids over ALL experts; this shard holds
    `lo .. lo + n_held - 1`. Returns a dict: `dst` [N] (the token's row,
    or `rows` = out of range where its expert is held elsewhere), `src`
    [rows] (the row's token, or N for a row of padding), `tile_expert`
    [tiles] (local expert of each row tile), `n_tiles` [1] (tiles in use).
    """
    n = expert.shape[0]
    tiles = -(-n // block_rows) + n_held
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
    return {
        "dst": dst, "src": src, "tile_expert": tile_expert,
        "n_tiles": ends[-1:].astype(jnp.int32),
    }


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
    x, expert, gate, w_gate, w_up, w_down, lo, *,
    block_rows: int = BLOCK_ROWS, interpret: bool | None = None,
):
    """What the experts held here add for the tokens routed to them.

    x: [N, d] tokens; expert: [N] int32 over all experts; gate: [N]; the
    weights of the `w_gate.shape[0]` experts from `lo` on. Returns [N, d]:
    `gate * (silu(x @ w_gate[e]) * (x @ w_up[e])) @ w_down[e]` for a token
    whose expert e is held, zeros for the others.
    """
    if interpret is None:
        interpret = not kernels_compiled()
    n_held = w_gate.shape[0]
    with jax.named_scope("moe.dispatch"):
        plan = plan_dispatch(expert, lo, n_held, block_rows)
        rows = take_rows(x, plan["src"], plan["dst"])
    mm = lambda a, w: grouped_matmul(
        a, w, plan["tile_expert"], plan["n_tiles"], block_rows, interpret
    )
    with jax.named_scope("moe.experts"):
        hidden = jax.nn.silu(mm(rows, w_gate)) * mm(rows, w_up)
        out = mm(hidden, w_down)
    with jax.named_scope("moe.combine"):
        back = take_rows(out, plan["dst"], plan["src"])
        return (back * gate[:, None]).astype(x.dtype)


def expert_mlp_on_mesh(mesh: Mesh | None, x, expert, gate, weights, first: int):
    """`expert_mlp` for x [B, S, d] on a mesh (or off it, `mesh` None):
    `weights` = (w_gate, w_up, w_down) of the experts held from `first`
    on. Tokens stay where the batch and `sp` axes put them; every `ep`
    shard holds a run of the experts and adds their part for the tokens
    routed to them, every `tp` shard a slice of each expert's width, and
    the partial results are summed over both. One shard: no exchange."""

    def local(x, expert, gate, w_gate, w_up, w_down, lo):
        out = expert_mlp(
            x.reshape(-1, x.shape[-1]), expert.reshape(-1), gate.reshape(-1),
            w_gate, w_up, w_down, lo,
        )
        return out.reshape(x.shape)

    if mesh is None:
        return local(x, expert, gate, *weights, first)
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

    def shard(x, expert, gate, w_gate, w_up, w_down):
        lo = first
        if ep:
            lo = first + lax.axis_index("ep") * w_gate.shape[0]
        out = local(x, expert, gate, w_gate, w_up, w_down, lo)
        return lax.psum(out, partial_over) if partial_over else out

    tokens = P(batch, seq)
    return jax.shard_map(
        shard,
        mesh=mesh,
        in_specs=(
            P(batch, seq, None), tokens, tokens,
            P(ep, None, tp), P(ep, None, tp), P(ep, tp, None),
        ),
        out_specs=P(batch, seq, None),
        check_vma=False,
    )(x, expert, gate, *weights)
