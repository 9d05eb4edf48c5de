"""The residual streams' maps and mixes (mHC, arXiv 2512.24880).

A stack with n residual streams carries X [B, S, n·d] and takes every
sublayer round three maps a token (`models/transformer.StreamMaps`): the
sublayer reads `h = sum_i Hp[i] X[i]` and the streams leave as `X'[i] =
sum_j Hr[i, j] X[j] + Ho[i] y`. The maps come from `RMSNorm(X) phi`, so a
sublayer's round is a handful of weighted sums and dot products over the
SAME [S, n·d] array, each of them a reduction over a token's row or a sum
a token: a block of whole token rows needs no second pass.

`mix_in` and `mix_out` are the round's two entries, and decide between
two forms of it from what the program can see (`kernels_apply`, the same
answer for both: x passes from one to the other unchanged):

- **The plain form** (`mixed_in`, `mixed_out`): XLA's fusions, which read
  X once for the norm, once for the product, once a mix and, backward,
  once a stream a map (4.3 times the least traffic in the xing cell,
  PERF.md §5, PR 39). What the CPU, float32 streams and a mesh of
  several devices run, and what the tests hold the kernels to.
- **The row-block kernels** (`hc_*` in a trace): Pallas
  passes over blocks of 128 whole token rows, with hand-written backward
  rules so that each [S, n·d] array is read once a pass and dX is
  written once:

    forward   `hc_pre_fwd`   X -> the norm's scalar, the raw product
                             `X split3(phi)` in one MXU pass, Hp, h
              `hc_post_fwd`  X, y, Hr, Ho -> X'
    backward  `hc_post_bwd`  X, dX', y -> dy and the 20 dot products
                             `<dX'[i], X[j]>`, `<dX'[i], y>` that are
                             Hr's and Ho's cotangents
              (the maps' backward from those, XLA: 24 numbers a token)
              `hc_pre_bwd`   X, dX', dh -> `<dh, X[j]>` and through Hp's
                             sigmoid, then dX, summed in float32 over
                             dX' (the block it is written over) and
                             rounded once

  A sublayer lies between the two mixes, so they are two `custom_vjp`
  rules that share one write of dX: `mix_in` hands X on untouched (its
  second result, for `mix_out` alone), `mix_out`'s rule returns dX'
  ITSELF as that copy's cotangent, and `mix_in`'s rule, which holds Hr,
  forms `sum_i Hr[i, j] dX'[i]` with everything else dX is made of. The
  pair is only a gradient together: nothing but `mix_out` may read the
  copy (`models/transformer.Block._mixed` is the one caller).

The maps stay [B, n, S] with the sequence in the lanes between the
passes (a trailing axis of 4 pads a tile 32 times); a kernel wants a
factor a token as a column against its row block. XLA only stacks the
few numbers a token into one [B, 128, S] array a pass (`_Lanes`: 0.5 KB a
token beside rows of 28 KB) and slices what comes back; a kernel turns a
block [128, 128] in VMEM. (XLA's own transposes to [tokens, 128] columns
took 0.2 ms each, 11 ms a step: PERF.md §6, PR 40.)

`kernels_apply` is `flash.row_blocks_apply` over streams of whole lane
tiles, and phi's three pieces within a tile's lanes (`_Lanes.fit`).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh

from kubeflow_tpu.ops import flash

# `jax.checkpoint_name` of what the maps are made from: the RAW product
# with phi [B, n² + 2n, S] and the norm's scalar [B, S], float32
# (`models/transformer.SAVED_RESULTS`).
CHECKPOINT_MAPS_NAME = "hc_maps"

_LANES = flash._LANES
# Token rows of a block. A whole row of 4 x 3,584 bfloat16 is 28 KB, 128
# rows 3.7 MB, double-buffered; 64, 128 and 256 rows ran alike on the v5e
# (PERF.md §6, PR 40), and 128 is one lane tile of the maps' arrays,
# whose blocks are turned whole.
_ROWS = 128
# Rows the kernels' inner loops take at a time: one bfloat16 tile.
_CHUNK = 16
# Of a v5e's 128 MiB of VMEM (the compiler's default scope is 16): the
# backward holds X, dX' and dX double-buffered, phi and a float32 product.
_VMEM_LIMIT = 64 * 1024 * 1024


class Maps(NamedTuple):
    """The numbers of a round: n streams of d lanes; the Sinkhorn
    normalisation's iterations, clamp and eps; the norm's eps."""

    n: int
    d: int
    iters: int
    clamp: float
    eps: float
    norm_eps: float

    @property
    def maps(self) -> int:
        return self.n * self.n + 2 * self.n


# -- the plain form ------------------------------------------------------------


def sinkhorn(m, iters: int, eps: float):
    """m [B, n, n, S] made doubly stochastic: every row divided by (its
    sum + eps), then every column, `iters` times."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=2, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
    return m


def split3(w, axis: int = 1):
    """A float32 array as three bfloat16 pieces whose sum is it (24 bits
    of mantissa in three times 8), side by side along `axis`."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    hi = w.astype(bf16)
    rest = w - hi.astype(f32)
    mid = rest.astype(bf16)
    low = (rest - mid.astype(f32)).astype(bf16)
    return jnp.concatenate([hi, mid, low], axis=axis)


def thirds(t, axis: int):
    return sum(jnp.split(t, 3, axis=axis))


@jax.custom_vjp
def exact_product(x, phi):
    """`einsum("kc,bsk->bcs", phi, x)` at full precision for x in
    bfloat16 and phi in float32, in ONE pass of the MXU: x is exact in
    bfloat16 already, so only phi is split in three (`split3`), and its
    pieces ride the output's lanes, where 3 x 24 columns cost what 24 do
    (a tile is 128). `precision=HIGHEST` splits both operands, six
    passes: 0.92 ms against 0.15 at [8192, 14336] x [14336, 24] by the
    MXU's peak. Backward the same way: phi's gradient from the
    cotangent split in three, exact; x's in one plain pass, since it is
    rounded to bfloat16 where it lands."""
    return _exact_product_fwd(x, phi)[0]


def _exact_product_fwd(x, phi):
    t = jnp.einsum(
        "kc,bsk->bcs", split3(phi), x, preferred_element_type=jnp.float32
    )
    return thirds(t, 1), (x, phi)


def _phi_gradient(dt, x):
    """phi's gradient [n·d, maps] from the raw product's cotangent
    [B, maps, S]: exact, the cotangent split in three."""
    dphi = jnp.einsum(
        "bcs,bsk->kc", split3(dt), x, preferred_element_type=jnp.float32
    )
    return thirds(dphi, 1)


def _exact_product_bwd(residuals, dt):
    x, phi = residuals
    dx = jnp.einsum(
        "bcs,kc->bsk", dt.astype(x.dtype), phi.astype(x.dtype),
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)
    return dx, _phi_gradient(dt, x)


exact_product.defvjp(_exact_product_fwd, _exact_product_bwd)


def maps_of_products(t, a, bias, spec: Maps):
    """The three maps from the normalised products t [B, n² + 2n, S]:
    `Hp = sigmoid(a_pre t_pre + b_pre)` [B, n, S], `Ho = 2 sigmoid(a_post
    t_post + b_post)` [B, n, S], `Hr = sinkhorn(exp(clip(a_res t_res +
    b_res, +-clamp)))` [B, n, n, S]; float32, the sequence in the lanes."""
    n = spec.n
    z = jnp.repeat(a, jnp.array([n, n, n * n]), total_repeat_length=spec.maps)
    z = z[:, None] * t + bias[:, None]
    pre, post, res = z[:, :n], z[:, n:2 * n], z[:, 2 * n:]
    m = jnp.exp(jnp.clip(res, -spec.clamp, spec.clamp))
    m = sinkhorn(m.reshape(-1, n, n, m.shape[-1]), spec.iters, spec.eps)
    return jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post), m


@functools.partial(jax.jit, static_argnames="spec")
def _maps_of_raw(t, inv_rms, a, bias, spec: Maps):
    """... from the RAW product and the norm's scalar [B, S]: the norm is
    a scalar a token, so it multiplies the product, not the streams.
    Under `jit`, as every pass below: a stack's sublayers are one
    function each, traced and lowered once a program and not once a
    sublayer (the 2·`iters` unrolled normalisations and the kernels'
    bodies are what a step's lowering is made of: 1.35 s a round against
    0.34 s otherwise, PERF.md §6, PR 40)."""
    return maps_of_products(t * inv_rms[:, None, :], a, bias, spec)


def mixed_in(x, phi, a, bias, spec: Maps):
    """`mix_in`, the plain form. bfloat16 streams take the product in one
    MXU pass (`exact_product`), others at `highest`; what is named
    (`CHECKPOINT_MAPS_NAME`) is the raw product and the norm's scalar;
    the iterations sit in a checkpoint of their own, so their backward
    forms them again and saves none of the 2·`iters` intermediates."""
    n, d, f32 = spec.n, spec.d, jnp.float32
    with jax.named_scope("hc.maps"):
        x32 = x.astype(f32)
        inv_rms = lax.rsqrt(jnp.mean(x32 * x32, axis=-1) + spec.norm_eps)
        if x.dtype == jnp.bfloat16:
            t = exact_product(x, phi)
        else:
            t = jnp.einsum(
                "kc,bsk->bcs", phi, x32, precision=lax.Precision.HIGHEST
            )
        t, inv_rms = checkpoint_name((t, inv_rms), CHECKPOINT_MAPS_NAME)
        hp, ho, hr = jax.checkpoint(
            functools.partial(_maps_of_raw, spec=spec)
        )(t, inv_rms, a, bias)
    with jax.named_scope("hc.pre"):
        h = sum(
            hp[:, i, :, None] * x[..., i * d:(i + 1) * d].astype(f32)
            for i in range(n)
        ).astype(x.dtype)
    return h, x, ho, hr


def mixed_out(x, y, hr, ho, spec: Maps):
    """`mix_out`, the plain form: the sums in float32."""
    n, d, f32 = spec.n, spec.d, jnp.float32
    column = lambda m: m[..., None]  # [B, S] -> a factor a token
    with jax.named_scope("hc.post"):
        one = [x[..., j * d:(j + 1) * d].astype(f32) for j in range(n)]
        y = y.astype(f32)
        return jnp.concatenate([
            (
                sum(column(hr[:, i, j]) * one[j] for j in range(n))
                + column(ho[:, i]) * y
            ).astype(x.dtype)
            for i in range(n)
        ], axis=-1)


# -- which form runs -----------------------------------------------------------


def kernels_apply(
    streams, spec: Maps, mesh: Mesh | None, compiled: bool | None = None
) -> bool:
    """Whether a round over `streams` [B, S, n·d] runs as the row-block
    kernels: `flash.row_blocks_apply` over the n streams, and everything
    a token's few numbers need within a tile's lanes."""
    return (
        flash.row_blocks_apply(streams, spec.d, mesh, compiled)
        and streams.shape[-1] == spec.n * spec.d
        and _Lanes(spec).fit
    )


# -- a few numbers a token, as the kernels read and write them ---------------------


class _Lanes:
    """Where each of a token's few numbers lies in the 128-row arrays the
    kernels exchange with XLA ([B, 128, S] float32: the maps' own layout,
    the sequence in the lanes, so XLA only stacks and slices them; a
    kernel turns a block [128, rows] to columns [rows, 128] in VMEM and
    its results back).

    `hc_pre_fwd` writes the raw products at 0 and the norm's scalar at
    `inv_out`. `hc_post_fwd` reads Hr (row i·n + j) at 0 and Ho at `ho`;
    `hc_post_bwd` reads Ho at 0 and writes Hr's and Ho's cotangents as
    `hc_post_fwd` reads them. `hc_pre_bwd` reads the raw products'
    cotangent from Ho and Hr at 0 (where phi's bfloat16 piece lies in the
    operand it shares with `hc_pre_fwd`), Hr at `hr`, Hp at `hp`, the
    norm's scalar at `inv`, its cotangent from Ho and Hr at `d_inv`,
    `a_pre` times the scalar at `a_inv` and times the raw pre products at
    `a_t`; it writes the cotangent of Hp's pre-activation at `dz` and the
    norm's term at `c`."""

    def __init__(self, spec: Maps):
        n, maps = spec.n, spec.maps
        self.inv_out = maps
        self.ho = n * n
        self.hr = maps
        self.hp = self.hr + n * n
        self.inv = self.hp + n
        self.d_inv, self.a_inv, self.a_t = (self.inv + k for k in (1, 2, 3))
        self.dz = self.a_t + n
        self.c = self.dz + n
        # phi's three pieces side by side, and everything in its place
        self.fit = 3 * maps <= _LANES and self.c < _LANES


def _stacked(parts):
    """{first row: array [B, k, S], [B, n, n, S] or [B, S]} as one
    [B, 128, S] float32 array, zeros between."""
    some = next(iter(parts.values()))
    batch, seq = some.shape[0], some.shape[-1]
    pieces, row = [], 0
    for first, part in sorted(parts.items()):
        part = part.astype(jnp.float32).reshape(batch, -1, seq)
        pieces += [jnp.zeros((batch, first - row, seq), jnp.float32), part]
        row = first + part.shape[1]
    pieces.append(jnp.zeros((batch, _LANES - row, seq), jnp.float32))
    return jnp.concatenate(pieces, axis=1)


def _column(tile, k: int):
    """Column k of a [rows, 128] tile, [rows, 1]: a factor a token."""
    return tile[:, k:k + 1]


def _placed(tile, columns):
    """`tile` [rows, 128] with {lane: [rows, 1] column} written over it."""
    lane = lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    for k, column in columns.items():
        tile = jnp.where(lane == k, column, tile)
    return tile


def _chunks(ref, body):
    """`body(rows)` over a block's rows, `_CHUNK` at a time."""
    def step(r, carry):
        body(pl.ds(pl.multiple_of(r * _CHUNK, _CHUNK), _CHUNK))
        return carry

    lax.fori_loop(0, ref.shape[0] // _CHUNK, step, 0)


def _dot_rows(u, v):
    """<u, v> a row, [rows, 1], float32."""
    return jnp.sum(u * v, axis=1, keepdims=True)


# -- the kernels -----------------------------------------------------------------


def _pre_fwd_kernel(
    x_ref, phi_ref, ab_ref, out_ref, h_ref, t3_ref, cols_ref, *, spec: Maps
):
    """A block of rows of X: the product with phi's three pieces in one
    MXU pass (their sum is the exact product: the pieces lie `maps` lanes
    apart, so two lane rotations bring them over each other), the norm's
    scalar, Hp and the sublayer's input."""
    n, d, maps, at = spec.n, spec.d, spec.maps, _Lanes(spec)
    f32 = jnp.float32
    t3_ref[...] = jnp.dot(
        x_ref[...], phi_ref[...], preferred_element_type=f32
    )
    a_pre, b_pre = ab_ref[0:1, :], ab_ref[1:2, :]

    def body(rows):
        stream = lambda i: x_ref[rows, i * d:(i + 1) * d].astype(f32)
        squares = sum(_dot_rows(xi, xi) for xi in map(stream, range(n)))
        inv_rms = lax.rsqrt(squares / (n * d) + spec.norm_eps)
        t3 = t3_ref[rows, :]
        t = (
            t3 + pltpu.roll(t3, _LANES - maps, 1)
            + pltpu.roll(t3, _LANES - 2 * maps, 1)
        )
        lane = lax.broadcasted_iota(jnp.int32, t.shape, 1)
        cols_ref[rows, :] = _placed(
            jnp.where(lane < maps, t, 0.0), {at.inv_out: inv_rms}
        )
        hp = jax.nn.sigmoid(a_pre * (t * inv_rms) + b_pre)
        h = sum(_column(hp, i) * stream(i) for i in range(n))
        h_ref[rows, :] = h.astype(h_ref.dtype)

    _chunks(x_ref, body)
    out_ref[...] = cols_ref[...].T


def _post_fwd_kernel(x_ref, y_ref, f_ref, o_ref, cols_ref, *, spec: Maps):
    """`X'[i] = sum_j Hr[i, j] X[j] + Ho[i] y`, a lane tile at a time so
    that each of the five tiles is widened to float32 once."""
    n, d, at = spec.n, spec.d, _Lanes(spec)
    f32 = jnp.float32
    cols_ref[...] = f_ref[...].T

    def body(rows):
        f = cols_ref[rows, :]
        hr = [[_column(f, i * n + j) for j in range(n)] for i in range(n)]
        ho = [_column(f, at.ho + i) for i in range(n)]
        for c in range(0, d, _LANES):
            tile = lambda ref, i: ref[rows, i * d + c:i * d + c + _LANES].astype(f32)
            x = [tile(x_ref, j) for j in range(n)]
            y = tile(y_ref, 0)
            for i in range(n):
                out = ho[i] * y
                for j in range(n):
                    out += hr[i][j] * x[j]
                o_ref[rows, i * d + c:i * d + c + _LANES] = out.astype(o_ref.dtype)

    _chunks(x_ref, body)


def _post_bwd_kernel(
    x_ref, dxo_ref, y_ref, f_ref, dy_ref, out_ref, cols_ref, *, spec: Maps
):
    """dX' against X and y, a lane tile at a time (each of the nine tiles
    widened once): `dy = sum_i Ho[i] dX'[i]`, and the dot products
    `<dX'[i], X[j]>` and `<dX'[i], y>` that are Hr's and Ho's cotangents."""
    n, d, at = spec.n, spec.d, _Lanes(spec)
    f32 = jnp.float32
    cols_ref[...] = f_ref[...].T

    def body(rows):
        f = cols_ref[rows, :]
        ho = [_column(f, i) for i in range(n)]
        dots = [jnp.zeros((_CHUNK, _LANES), f32) for _ in range(n * n + n)]
        for c in range(0, d, _LANES):
            tile = lambda ref, i: ref[rows, i * d + c:i * d + c + _LANES].astype(f32)
            x = [tile(x_ref, j) for j in range(n)]
            y = tile(y_ref, 0)
            dy = jnp.zeros((_CHUNK, _LANES), f32)
            for i in range(n):
                dxo = tile(dxo_ref, i)
                dy += ho[i] * dxo
                for j in range(n):
                    dots[i * n + j] += dxo * x[j]
                dots[at.ho + i] += dxo * y
            dy_ref[rows, c:c + _LANES] = dy.astype(dy_ref.dtype)
        cols_ref[rows, :] = _placed(jnp.zeros_like(f), {
            k: jnp.sum(dot, axis=1, keepdims=True) for k, dot in enumerate(dots)
        })

    _chunks(x_ref, body)
    out_ref[...] = cols_ref[...].T


def _pre_bwd_kernel(
    x_ref, dxo_ref, dh_ref, f_ref, phi_ref, dx_ref, out_ref, cols_ref,
    prod_ref, *, spec: Maps,
):
    """`dX[j] = sum_i Hr[i, j] dX'[i] + Hp[j] dh + (g phi^T)[j] + c X[j]`,
    summed in float32 and written once, in two sweeps over the block's
    rows in VMEM. The first takes `<dh, X[j]>`, Hp's cotangent, through
    the sigmoid (24 numbers a token are XLA's but these four: they hang
    on dh, which only this pass reads) and with it completes g, the raw
    products' cotangent, and the norm's term c; g in bfloat16 then meets
    phi's bfloat16 copy on the MXU (`phi_ref` is `hc_pre_fwd`'s operand:
    the first of phi's three pieces IS that copy, and g's lanes beyond
    it are zeros); the second sums."""
    n, d, maps, at = spec.n, spec.d, spec.maps, _Lanes(spec)
    f32 = jnp.float32
    cols_ref[...] = f_ref[...].T

    def through_hp(rows):
        f = cols_ref[rows, :]
        dh = dh_ref[rows, :].astype(f32)
        d_inv, placed = _column(f, at.d_inv), {}
        for j in range(n):
            hp = _column(f, at.hp + j)
            xj = x_ref[rows, j * d:(j + 1) * d].astype(f32)
            dz = _dot_rows(dh, xj) * hp * (1.0 - hp)
            d_inv += dz * _column(f, at.a_t + j)
            placed[j] = dz * _column(f, at.a_inv)
            placed[at.dz + j] = dz
        # inv_rms = (mean(x²) + eps)^-1/2: its cotangent reaches x as c·x.
        placed[at.c] = -d_inv * _column(f, at.inv) ** 3 / (n * d)
        cols_ref[rows, :] = _placed(f, placed)

    _chunks(x_ref, through_hp)
    cols = cols_ref[...]
    lane = lax.broadcasted_iota(jnp.int32, cols.shape, 1)
    prod_ref[...] = lax.dot_general(
        jnp.where(lane < maps, cols, 0.0).astype(phi_ref.dtype), phi_ref[...],
        (((1,), (1,)), ((), ())), preferred_element_type=f32,
    )

    def sums(rows):
        f = cols_ref[rows, :]
        hr = [[_column(f, at.hr + i * n + j) for j in range(n)] for i in range(n)]
        hp = [_column(f, at.hp + j) for j in range(n)]
        c_norm = _column(f, at.c)
        for c in range(0, d, _LANES):
            tile = lambda ref, i: ref[rows, i * d + c:i * d + c + _LANES]
            dxo = [tile(dxo_ref, i).astype(f32) for i in range(n)]
            dh = tile(dh_ref, 0).astype(f32)
            for j in range(n):
                dx = tile(prod_ref, j) + hp[j] * dh
                dx += c_norm * tile(x_ref, j).astype(f32)
                for i in range(n):
                    dx += hr[i][j] * dxo[i]
                dx_ref[rows, j * d + c:j * d + c + _LANES] = dx.astype(
                    dx_ref.dtype
                )

    _chunks(x_ref, sums)
    out_ref[...] = cols_ref[...].T


def _call(kernel, name, spec, by_rows, stacked, whole, outs, scratch, interpret,
          onto=None):
    """One pass over the tokens in blocks of `_ROWS` rows: `by_rows`
    arrays [B, S, width] travel a block [rows, width] a step, `stacked`
    arrays [B, 128, S] a block [128, rows], `whole` arrays stay; `outs`:
    (width, dtype) a result [B, S, width], (None, dtype) one [B, 128, S];
    `scratch` (width, dtype): a block's temporaries [rows, width];
    `onto` {operand: result}: a result written over an operand's blocks
    (each is read before its place is written)."""
    batch, seq, _ = by_rows[0].shape
    rows = lambda width: pl.BlockSpec(
        (None, _ROWS, width), lambda b, i: (b, i, 0)
    )
    lanes = pl.BlockSpec((None, _LANES, _ROWS), lambda b, i: (b, 0, i))
    return pl.pallas_call(
        functools.partial(kernel, spec=spec),
        grid=(batch, seq // _ROWS),
        in_specs=[
            *(rows(u.shape[-1]) for u in by_rows),
            *(lanes for _ in stacked),
            *(pl.BlockSpec(u.shape, lambda b, i: (0, 0)) for u in whole),
        ],
        out_specs=[
            lanes if width is None else rows(width) for width, _ in outs
        ],
        out_shape=[
            jax.ShapeDtypeStruct(
                (batch, _LANES, seq) if width is None else (batch, seq, width),
                dtype,
            )
            for width, dtype in outs
        ],
        scratch_shapes=[
            pltpu.VMEM((_ROWS, width), dtype) for width, dtype in scratch
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        input_output_aliases=onto or {},
        interpret=interpret,
        name=name,
    )(*by_rows, *stacked, *whole)


# -- the passes: a kernel and what XLA stacks and slices round it --------------------

_F32_COLS = (_LANES, jnp.float32)  # a block's numbers a token, as columns
_pass = functools.partial(jax.jit, static_argnames=("spec", "interpret"))


def _phi_pieces(phi):
    """phi's three bfloat16 pieces side by side in 128 lanes, [n·d, 128]:
    the forward's operand, and (its first piece) the backward's."""
    pieces = split3(phi)
    return jnp.pad(pieces, ((0, 0), (0, _LANES - pieces.shape[1])))


@_pass
def _pre_fwd(x, phi, a, bias, spec: Maps, interpret: bool):
    """-> (raw products [B, maps, S], the norm's scalar [B, S], h)."""
    n, at = spec.n, _Lanes(spec)
    ab = jnp.zeros((2, _LANES), jnp.float32)
    ab = ab.at[0, :n].set(a[0]).at[1, :n].set(bias[:n])
    out, h = _call(
        _pre_fwd_kernel, "hc_pre_fwd", spec, [x], [], [_phi_pieces(phi), ab],
        [(None, jnp.float32), (spec.d, x.dtype)], [_F32_COLS, _F32_COLS],
        interpret,
    )
    return out[:, :spec.maps], out[:, at.inv_out], h


@_pass
def _post_fwd(x, y, hr, ho, spec: Maps, interpret: bool):
    (mixed,) = _call(
        _post_fwd_kernel, "hc_post_fwd", spec, [x, y],
        [_stacked({0: hr, _Lanes(spec).ho: ho})], [],
        [(x.shape[-1], x.dtype)], [_F32_COLS], interpret,
    )
    return mixed


@_pass
def _post_bwd(x, dxo, y, ho, spec: Maps, interpret: bool):
    """-> (dy, Hr's cotangent [B, n, n, S], Ho's [B, n, S])."""
    n, at = spec.n, _Lanes(spec)
    dy, out = _call(
        _post_bwd_kernel, "hc_post_bwd", spec, [x, dxo, y],
        [_stacked({0: ho})], [],
        [(spec.d, y.dtype), (None, jnp.float32)], [_F32_COLS], interpret,
    )
    dhr = out[:, :at.ho].reshape(out.shape[0], n, n, out.shape[-1])
    return dy, dhr, out[:, at.ho:at.ho + n]


@_pass
def _pre_bwd(x, dxo, dh, phi, a, t, inv_rms, hr, hp, dt, d_inv,
             spec: Maps, interpret: bool):
    """-> (dX, the cotangent of Hp's pre-activation [B, n, S]). `dt` and
    `d_inv`: the raw products' and the norm's scalar's cotangents from Ho
    and Hr alone."""
    n, at = spec.n, _Lanes(spec)
    factors = _stacked({
        0: dt, at.hr: hr, at.hp: hp, at.inv: inv_rms, at.d_inv: d_inv,
        at.a_inv: a[0] * inv_rms, at.a_t: a[0] * t[:, :n],
    })
    dx, out = _call(
        _pre_bwd_kernel, "hc_pre_bwd", spec, [x, dxo, dh], [factors],
        [_phi_pieces(phi)], [(x.shape[-1], x.dtype), (None, jnp.float32)],
        [_F32_COLS, (x.shape[-1], jnp.float32)], interpret,
        onto={1: 0},  # dX over dX', which nothing reads after this
    )
    return dx, out[:, at.dz:at.dz + n]


@_pass
def _maps_backward(t, inv_rms, a, bias, dho, dhr, spec: Maps, interpret: bool):
    """Hp and Hr again, and the cotangents Ho's and Hr's send to the raw
    products, the norm's scalar, a and the bias. The iterations run again
    here and keep nothing beyond; in a checkpoint of their own, as XLA's
    code has them: differentiated plain, the step's temporaries grow by
    0.1 GB (PERF.md §6, PR 40). Hp's own cotangent waits for the pass
    that reads dh."""
    del interpret
    (hp, _, hr), maps_vjp = jax.vjp(
        jax.checkpoint(functools.partial(_maps_of_raw, spec=spec)),
        t, inv_rms, a, bias,
    )
    return (hp, hr, *maps_vjp((jnp.zeros_like(hp), dho, dhr)))


# -- the two rules ---------------------------------------------------------------


def mix_in(x, phi, a, bias, spec: Maps, mesh: Mesh | None = None,
           interpret: bool | None = None):
    """The mix into a sublayer: streams x [B, S, n·d], `phi`
    [n·d, n² + 2n], `a` [3] and `bias` [n² + 2n] float32 -> (h [B, S, d]
    = `sum_i Hp[i] X[i]`, x itself for `mix_out` and nothing else (module
    docstring), Ho [B, n, S], Hr [B, n, n, S]). The kernels where
    `kernels_apply` says so (or under the interpreter when `interpret` is
    True, as `ssd_scan` reads it), `mixed_in` anywhere else."""
    if interpret is None and not kernels_apply(x, spec, mesh):
        return mixed_in(x, phi, a, bias, spec)
    return _mix_in(x, phi, a, bias, spec, flash._auto_interpret(interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _mix_in(x, phi, a, bias, spec, interpret):
    return _mix_in_fwd(x, phi, a, bias, spec, interpret)[0]


def _mix_in_fwd(x, phi, a, bias, spec, interpret):
    with jax.named_scope("hc.pre"):
        t, inv_rms, h = _pre_fwd(x, phi, a, bias, spec=spec, interpret=interpret)
    with jax.named_scope("hc.maps"):
        # Named where the backward reads them: with the name kept nothing
        # of the maps waits on the pass over X.
        t, inv_rms = checkpoint_name((t, inv_rms), CHECKPOINT_MAPS_NAME)
        _, ho, hr = _maps_of_raw(t, inv_rms, a, bias, spec=spec)
    return (h, x, ho, hr), (x, phi, a, bias, t, inv_rms)


def _mix_in_bwd(spec, interpret, residuals, cotangents):
    x, phi, a, bias, t, inv_rms = residuals
    dh, dxo, dho, dhr = cotangents  # dxo: dX' itself, by `mix_out`'s rule
    n = spec.n
    with jax.named_scope("hc.maps"):
        hp, hr, dt, d_inv, da, dbias = _maps_backward(
            t, inv_rms, a, bias, dho, dhr, spec=spec, interpret=interpret
        )
    with jax.named_scope("hc.pre"):
        dx, dz = _pre_bwd(
            x, dxo, dh, phi, a, t, inv_rms, hr, hp, dt, d_inv,
            spec=spec, interpret=interpret,
        )
    with jax.named_scope("hc.maps"):
        # What the pass found of Hp = sigmoid(a_pre t_pre inv_rms + b_pre).
        dt = dt.at[:, :n].add(dz * (a[0] * inv_rms)[:, None])
        da = da.at[0].add(jnp.sum(dz * t[:, :n] * inv_rms[:, None]))
        dbias = dbias.at[:n].add(jnp.sum(dz, axis=(0, 2)))
    with jax.named_scope("hc.pre"):
        dphi = _phi_gradient(dt, x)
    return dx, dphi, da, dbias


_mix_in.defvjp(_mix_in_fwd, _mix_in_bwd)


def mix_out(x, y, hr, ho, spec: Maps, mesh: Mesh | None = None,
            interpret: bool | None = None):
    """The mix out of a sublayer, `X'[i] = sum_j Hr[i, j] X[j] + Ho[i] y`:
    `x` is `mix_in`'s second result, y [B, S, d] the sublayer's output, Hr
    and Ho `mix_in`'s; the form `mix_in` took, by the same `mesh` and
    `interpret`. The kernels' rule hands dX' back as x's cotangent:
    `mix_in`'s rule applies Hr to it."""
    if interpret is None and not kernels_apply(x, spec, mesh):
        return mixed_out(x, y, hr, ho, spec)
    return _mix_out(x, y, hr, ho, spec, flash._auto_interpret(interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _mix_out(x, y, hr, ho, spec, interpret):
    return _mix_out_fwd(x, y, hr, ho, spec, interpret)[0]


def _mix_out_fwd(x, y, hr, ho, spec, interpret):
    with jax.named_scope("hc.post"):
        mixed = _post_fwd(x, y, hr, ho, spec=spec, interpret=interpret)
    return mixed, (x, y, hr, ho)


def _mix_out_bwd(spec, interpret, residuals, dxo):
    x, y, hr, ho = residuals
    with jax.named_scope("hc.post"):
        dy, dhr, dho = _post_bwd(x, dxo, y, ho, spec=spec, interpret=interpret)
    return dxo, dy, dhr, dho


_mix_out.defvjp(_mix_out_fwd, _mix_out_bwd)
