"""The recurrent mixers' short convolution, `silu` and a head's unit norm
as one kernel pair (Pallas TPU).

What a delta-rule mixer does to each of q, k and v between its projections
and the delta rule, and a state-space mixer to xBC between its
in-projection and the scan, over u [B, S, W] in bfloat16:

    m_t = sum_j w_j u_(t-j) (+ bias)     a causal depthwise convolution of
                                         `taps` along the sequence, zeros
                                         before a sequence's first token,
                                         summed in float32
    a_t = silu(round(m_t))               the sum rounded to `sum_dtype`
                                         where `silu` reads it
    y_t = scale a_t / sqrt(sum_head(a_t^2) + eps)   where a head's norm is
                                         asked for: over each `head_dim`
                                         lanes, float32; else y = a

XLA makes elementwise passes of this: a float32 copy of u, four shifted
copies summed, `silu`, the norm's two products with the heads' indicator,
and backward each of them again and their gradients (the kimi cell: 51.6 ms
of a 280.3 ms step for bytes the memory moves in 4.9, PERF.md §5, PR 41).
Here it is ONE pass forward and ONE backward over blocks of `[rows, lanes]`
of u, every float32 array of it in VMEM:

- `shortconv_fwd` reads a block of u with the rows before it (a second
  block of `_HALO` rows over the same array, zeros in a sequence's first
  block: never another sequence's rows), widens it into a float32 scratch
  and takes tap j as that scratch read j rows up; writes y.
- `shortconv_bwd` reads u with the rows before AND after the block and
  dy with the rows after (`du_t = sum_j w_j dm_(t+j)`: the gradient of the
  sum j rows DOWN; zeros past a sequence's last token), forms m, `silu`,
  its slope and the norm again in VMEM, writes du once and accumulates
  the taps' and the bias's gradients in float32 over the row blocks (the
  grid's sequential axes; their block stays resident, eight partial rows
  a tap that XLA sums at the end).

The backward's only reads of HBM are u and dy: with u kept (`kda_proj`,
`ssm_in_proj`) and y kept (`kda_conv`, `ssm_conv`: what the NEXT kernel's
backward reads) no forward kernel runs again under `remat_policy="flash"`.

`short_conv` is the one entry and decides which form runs, from what the
program can see (`kernels_apply`): the pair, or `short_conv_plain`, XLA's
passes, which the CPU, float32 and a mesh of several devices run and the
tests hold the kernels to.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh

from kubeflow_tpu.ops import flash

_LANES = flash._LANES
# Rows of a block's halo: one bfloat16 tile.
_HALO = 16
# Rows the kernels' loops take at a time: enough independent tiles to fill
# the vector unit's slots between one tile's dependent steps (64, 128 and
# 256 rows took 0.79, 0.54 and 0.42 ms forward at [8192, 4096] on the v5e,
# PERF.md §6, PR 42).
_CHUNK = 256
# The most rows of a block (a sequence of fewer rows, or of a number they
# do not divide, takes the largest power of two that does, 128 at least:
# `kernels_apply`).
_ROWS = 2048
# Rows of the taps' table [8, W] float32 the kernels read: the taps, then
# the bias, then the norm's scale.
_TABLE = 8


def kernels_apply(
    u, taps: int, head_dim: int, mesh: Mesh | None, compiled: bool | None = None
) -> bool:
    """Whether the convolution over u [B, S, W] runs as the kernel pair:
    `flash.row_blocks_apply` over whole heads (lane tiles without), and
    the taps within the table (and their reach inside the halo)."""
    return (
        flash.row_blocks_apply(u, head_dim or _LANES, mesh, compiled)
        and 1 <= taps <= _TABLE - 2
    )


def shift(x, steps: int):
    """x[t - steps] at position t along the sequence axis (1), zeros
    before the first token: a causal tap."""
    if steps == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[1] = (steps, 0)
    return jnp.pad(x, pad)[:, : x.shape[1]]


def _causal_conv(x, w):
    """A causal depthwise convolution along the sequence of x [B, S, W] by
    w [taps, W]: tap j multiplies the value j tokens back. float32 out."""
    x = x.astype(jnp.float32)
    return sum(w[j] * shift(x, j) for j in range(w.shape[0]))


def head_sums(squares, head_dim: int):
    """A head's sum of `squares` [.., H·d] on every lane of the head, as
    products with the heads' indicator [H·d, H]: [.., H·d] -> [.., H, d]
    is a relayout on the TPU (a copy a pass), a product of H columns not."""
    width = squares.shape[-1]
    lanes_of = (
        jnp.arange(width)[:, None] // head_dim
        == jnp.arange(width // head_dim)[None]
    ).astype(jnp.float32)
    dot = functools.partial(jnp.dot, precision=lax.Precision.HIGH)
    return dot(dot(squares, lanes_of), lanes_of.T)


def short_conv_plain(
    u, w, bias=None, sum_dtype=jnp.float32, head_dim: int = 0,
    scale: float = 1.0, eps: float = 0.0, name: str | None = None,
):
    """`short_conv` as XLA's passes (module docstring's three lines).
    `name`: what `silu`'s slope reads, the rounded sum."""
    m = _causal_conv(u, w)
    if bias is not None:
        m = m + bias
    m = m.astype(sum_dtype)
    if name is not None:
        m = checkpoint_name(m, name)
    a = jax.nn.silu(m.astype(jnp.float32))
    if head_dim:
        a = a * (scale * lax.rsqrt(head_sums(a * a, head_dim) + eps))
    return a.astype(u.dtype)


def _block(seq: int, head_dim: int) -> tuple[int, int, int]:
    """(rows, lanes, rows a loop step) of a block: one head or one lane
    tile wide (a row of the float32 scratch is read whole at any row; a
    lane slice of it only at whole tiles of eight), the rows a power of
    two that divides the sequence."""
    rows = math.gcd(seq, _ROWS)
    return rows, head_dim or _LANES, math.gcd(rows, _CHUNK)


def _chunks(rows: int, chunk: int, body, carry=None):
    """`body(first row, carry)` over a block's rows, `chunk` at a time."""
    def step(r, carry):
        return body(pl.multiple_of(r * chunk, chunk), carry)

    return lax.fori_loop(0, rows // chunk, step, carry)


def _widen(u_ref, xs_ref, chunk: int):
    """A block of u into the float32 scratch, from row `_HALO` on."""
    def body(first, carry):
        xs_ref[pl.ds(_HALO + first, chunk), :] = (
            u_ref[pl.ds(first, chunk), :].astype(jnp.float32)
        )
        return carry

    _chunks(u_ref.shape[0], chunk, body)


def _shifted(xs_ref, first, rows: int, j: int):
    """u j tokens back of rows `first`.. of a block, from the scratch
    (whose row `_HALO` is the block's first)."""
    return xs_ref[pl.ds(_HALO + first - j, rows), :]


def _activation(xs_ref, w_ref, first, rows, *, taps, bias, head, sum_dtype,
                eps):
    """Rows `first`.. of a block through the taps, the rounding and `silu`:
    (sigmoid(m), a = silu(m), `1 / sqrt(sum a² + eps)` a row or None)."""
    f32 = jnp.float32
    m = sum(
        w_ref[j:j + 1, :] * _shifted(xs_ref, first, rows, j)
        for j in range(taps)
    )
    if bias:
        m = m + w_ref[taps:taps + 1, :]
    m = m.astype(sum_dtype).astype(f32)
    # sigmoid by tanh: one pass of the transcendental unit and three of
    # the vector unit's, where 1 / (1 + e^-m) is an exact division, a
    # dozen (of the forward's 28 a tile; PERF.md §6, PR 42).
    half = 0.5 * m
    t = jnp.tanh(half)
    s = 0.5 * t + 0.5
    a = half * t + half
    r = None
    if head:
        r = lax.rsqrt(jnp.sum(a * a, axis=1, keepdims=True) + eps)
    return s, a, r


def _fwd_kernel(u_ref, before_ref, w_ref, y_ref, xs_ref, *, chunk, taps, bias,
                head, sum_dtype, eps):
    f32 = jnp.float32
    first_block = pl.program_id(2) == 0
    xs_ref[0:_HALO, :] = jnp.where(first_block, 0.0, before_ref[...].astype(f32))
    _widen(u_ref, xs_ref, chunk)

    def body(first, carry):
        _, a, r = _activation(
            xs_ref, w_ref, first, chunk, taps=taps, bias=bias, head=head,
            sum_dtype=sum_dtype, eps=eps,
        )
        if head:
            a = a * (w_ref[taps + 1:taps + 2, :] * r)
        y_ref[pl.ds(first, chunk), :] = a.astype(y_ref.dtype)
        return carry

    _chunks(u_ref.shape[0], chunk, body)


def _bwd_kernel(u_ref, before_ref, after_ref, dy_ref, dy_after_ref, w_ref,
                du_ref, dw_ref, xs_ref, dm_ref, *, chunk, taps, bias, head,
                sum_dtype, eps):
    f32 = jnp.float32
    rows, lanes = u_ref.shape
    first_block = pl.program_id(2) == 0
    last_block = pl.program_id(2) == pl.num_programs(2) - 1

    @pl.when((pl.program_id(1) == 0) & first_block)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    xs_ref[0:_HALO, :] = jnp.where(first_block, 0.0, before_ref[...].astype(f32))
    _widen(u_ref, xs_ref, chunk)
    xs_ref[_HALO + rows:2 * _HALO + rows, :] = after_ref[...].astype(f32)

    def through(first, n, dy):
        """dm of the `n` rows from `first`, into its scratch."""
        s, a, r = _activation(
            xs_ref, w_ref, first, n, taps=taps, bias=bias, head=head,
            sum_dtype=sum_dtype, eps=eps,
        )
        da = dy
        if head:
            # y = c a r, r = (sum a² + eps)^-1/2: da = c r (dy - a r² <dy, a>)
            dot = jnp.sum(dy * a, axis=1, keepdims=True)
            da = (w_ref[taps + 1:taps + 2, :] * r) * (dy - a * (r * r * dot))
        dm = da * (s + a * (1.0 - s))
        dm_ref[pl.ds(first, n), :] = dm
        return dm

    def eighths(p):
        return sum(p[k:k + 8] for k in range(0, p.shape[0], 8))

    def block_rows(first, sums):
        dm = through(first, chunk, dy_ref[pl.ds(first, chunk), :].astype(f32))
        against = [_shifted(xs_ref, first, chunk, j) for j in range(taps)]
        # a sum a tap and, where there is a bias, dm's own after them
        return [
            acc + eighths(dm if x is None else dm * x)
            for acc, x in zip(sums, [*against, None])
        ]

    tables = taps + bool(bias)
    sums = _chunks(
        rows, chunk, block_rows, [jnp.zeros((8, lanes), f32)] * tables
    )
    for j, acc in enumerate(sums):
        dw_ref[8 * j:8 * j + 8, :] += acc
    # The rows after the block: zeros past the sequence's last token.
    through(
        rows, _HALO,
        jnp.where(last_block, 0.0, dy_after_ref[...].astype(f32)),
    )

    def down(first, carry):
        du = sum(
            w_ref[j:j + 1, :] * dm_ref[pl.ds(first + j, chunk), :]
            for j in range(taps)
        )
        du_ref[pl.ds(first, chunk), :] = du.astype(du_ref.dtype)
        return carry

    _chunks(rows, chunk, down)


def _table(w, bias, scale):
    """The taps [taps, W], the bias [W] or None and the norm's scale as
    the [8, W] float32 table the kernels read."""
    taps, width = w.shape
    f32 = jnp.float32
    return jnp.concatenate([
        w.astype(f32),
        (jnp.zeros((width,), f32) if bias is None else bias.astype(f32))[None],
        jnp.full((1, width), scale, f32),
        jnp.zeros((_TABLE - taps - 2, width), f32),
    ])


# Under `jit`: a stack's layers (and q and k, which differ by a row of the
# table) trace and lower each body once a program.
_pass = functools.partial(jax.jit, static_argnames=(
    "taps", "bias", "head", "sum_dtype", "eps", "interpret",
))


def _specs(u, head):
    batch, seq, width = u.shape
    rows, lanes, chunk = _block(seq, head)
    per = rows // _HALO  # halo blocks a row block
    block = pl.BlockSpec((None, rows, lanes), lambda l, b, i: (b, i, l))
    before = pl.BlockSpec(
        (None, _HALO, lanes), lambda l, b, i: (b, jnp.maximum(i * per - 1, 0), l)
    )
    after = pl.BlockSpec(
        (None, _HALO, lanes),
        lambda l, b, i: (b, jnp.minimum((i + 1) * per, seq // _HALO - 1), l),
    )
    table = lambda n: pl.BlockSpec((n, lanes), lambda l, b, i: (0, l))
    grid = (width // lanes, batch, seq // rows)
    return grid, (rows, lanes, chunk), block, before, after, table


@_pass
def _fwd(u, table, *, taps, bias, head, sum_dtype, eps, interpret):
    grid, (rows, lanes, chunk), block, before, _, tab = _specs(u, head)
    return pl.pallas_call(
        functools.partial(
            _fwd_kernel, chunk=chunk, taps=taps, bias=bias, head=head,
            sum_dtype=sum_dtype, eps=eps,
        ),
        grid=grid,
        in_specs=[block, before, tab(_TABLE)],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype),
        scratch_shapes=[pltpu.VMEM((_HALO + rows, lanes), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=interpret,
        name="shortconv_fwd",
    )(u, u, table)


@_pass
def _bwd(u, dy, table, *, taps, bias, head, sum_dtype, eps, interpret):
    """-> (du, the taps' gradient [taps, W], the bias's [W] or None)."""
    grid, (rows, lanes, chunk), block, before, after, tab = _specs(u, head)
    tables = taps + bool(bias)
    du, partial = pl.pallas_call(
        functools.partial(
            _bwd_kernel, chunk=chunk, taps=taps, bias=bias, head=head,
            sum_dtype=sum_dtype, eps=eps,
        ),
        grid=grid,
        in_specs=[block, before, after, block, after, tab(_TABLE)],
        out_specs=[block, tab(8 * tables)],
        out_shape=[
            jax.ShapeDtypeStruct(u.shape, u.dtype),
            jax.ShapeDtypeStruct((8 * tables, u.shape[-1]), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((2 * _HALO + rows, lanes), jnp.float32),
            pltpu.VMEM((_HALO + rows, lanes), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="shortconv_bwd",
    )(u, u, u, dy, dy, table)
    sums = partial.reshape(tables, 8, -1).sum(axis=1)
    return du, sums[:taps], sums[taps] if bias else None


def short_conv(
    u, w, bias=None, *, sum_dtype=jnp.float32, head_dim: int = 0,
    scale: float = 1.0, eps: float = 0.0, name: str | None = None,
    mesh: Mesh | None = None, interpret: bool | None = None,
):
    """u [B, S, W] through the causal depthwise convolution by `w`
    [taps, W] (tap j multiplies the value j tokens back) and `bias` [W] or
    None, the sum rounded to `sum_dtype`, `silu` and, with `head_dim`,
    `scale` times each head's unit vector (`eps` under the root). The
    kernel pair where `kernels_apply` says so (or under the interpreter
    when `interpret` is True, as `ssd_scan` reads it), `short_conv_plain`
    anywhere else. `name`: a `checkpoint_name` on what this op's backward
    reads of it: the pair's result, the plain form's rounded sum."""
    if interpret is None and not kernels_apply(u, w.shape[0], head_dim, mesh):
        return short_conv_plain(u, w, bias, sum_dtype, head_dim, scale, eps, name)
    y = _short_conv(
        u, w, bias, jnp.dtype(sum_dtype), head_dim, float(scale), float(eps),
        flash._auto_interpret(interpret),
    )
    return y if name is None else checkpoint_name(y, name)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _short_conv(u, w, bias, sum_dtype, head, scale, eps, interpret):
    return _short_conv_fwd(u, w, bias, sum_dtype, head, scale, eps, interpret)[0]


def _short_conv_fwd(u, w, bias, sum_dtype, head, scale, eps, interpret):
    y = _fwd(
        u, _table(w, bias, scale), taps=w.shape[0], bias=bias is not None,
        head=head, sum_dtype=sum_dtype, eps=eps, interpret=interpret,
    )
    return y, (u, w, bias)


def _short_conv_bwd(sum_dtype, head, scale, eps, interpret, residuals, dy):
    u, w, bias = residuals
    du, dw, dbias = _bwd(
        u, dy, _table(w, bias, scale), taps=w.shape[0], bias=bias is not None,
        head=head, sum_dtype=sum_dtype, eps=eps, interpret=interpret,
    )
    return (
        du, dw.astype(w.dtype),
        None if bias is None else dbias.astype(bias.dtype),
    )


_short_conv.defvjp(_short_conv_fwd, _short_conv_bwd)
