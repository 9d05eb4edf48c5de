"""Rotary embeddings over the array the projections write.

The attention kernels read q and k as [B, S, H·D], heads folded into the
lanes (`ops/flash.py`, "block specs"), so rope has to turn them there: a
[B, S, H, D] array on the way is a relayout on the TPU, where an array's
two minor dimensions are tiled (8, 128) and H·D does not split for free.
Folded, a head's lanes pair with each other (lane i of the turned part
with lane i ± turned/2), and the cos / sin tables are [B, S, D], one head
wide, the same for every head.

- **`rope_folded`** is the plain form: rolls of the whole last axis, which
  bring a lane's partner beside it without leaving the head. XLA fuses
  it, but only over tables as wide as x ([B, S, H·D] each, float32:
  materialised, and read again by every layer), so it is the CPU's form
  and the reference the kernel is held to.
- **`rope_turn`** (`rope_turn_fwd` / `rope_turn_bwd` in a trace) is the
  Pallas kernel: a block of rows with every head in it, the tables' block
  fetched once and used for each head's 128-lane column in turn, the
  partner by a lane rotation. Elementwise, so bound by HBM: x read once,
  y written once. Its VJP is the same kernel with the angle negated.

`rope` picks: the kernel where kernels compile, D is whole lanes (or
divides a lane tile while H·D is whole tiles: latent attention's rope part
of 64 a head, two heads a tile under tables tiled twice) and the sequence
tiles; `rope_folded` anywhere else (`ops/flash.kernels_compiled`, as
`ops/attention.attend` does), a head-less key [B, S, 64] among them. A
Pallas call does not partition itself under `jit`, so with a mesh the
kernel runs in `shard_map` over the batch axes and, for the heads, `tp`.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from kubeflow_tpu.ops import flash
from kubeflow_tpu.parallel.sharding import batch_axes

# Rows of a block: 256 x (16 heads x 128) bf16 is 1 MiB, in and out
# double-buffered 4 MiB of VMEM. A wider row halves them until a block is
# at most `_BLOCK_BYTES` (72 heads x 128: 64 rows; at 256 the four buffers
# are 18 MiB, past the compiler's 16 MiB of scoped VMEM).
_BLOCK_ROWS = 256
_BLOCK_BYTES = 2 * 1024 * 1024


def _block_rows(width: int, itemsize: int) -> int:
    rows = _BLOCK_ROWS
    while rows > 8 and rows * width * itemsize > _BLOCK_BYTES:
        rows //= 2
    return rows


def yarn_inv_freq(
    theta: float, turned: int, *, factor: float, original_max: int,
    beta_fast: float = 32.0, beta_slow: float = 1.0,
):
    """The `turned / 2` frequencies of a yarn-scaled rotation, as the
    published `rope_type: yarn` computes them: pair t turns by
    `theta^(-2t/turned)` where it makes more than `beta_fast` rotations
    over the `original_max` positions the model was trained at
    (extrapolated: kept), by that over `factor` where it makes fewer than
    `beta_slow` (interpolated: stretched), and by a linear blend between
    the two pairs where those counts fall. A vector for `rope_tables`:
    yarn is data, the kernels turn by whatever tables they are handed."""
    pair = jnp.arange(0, turned, 2, dtype=jnp.float32)
    extrapolated = 1.0 / theta ** (pair / turned)
    interpolated = extrapolated / factor
    at = lambda rotations: turned * math.log(
        original_max / (rotations * 2 * math.pi)
    ) / (2 * math.log(theta))
    low = max(math.floor(at(beta_fast)), 0)
    high = min(math.ceil(at(beta_slow)), turned - 1)
    if low == high:
        high += 0.001  # the published guard against a ramp of no width
    ramp = jnp.clip((pair / 2 - low) / (high - low), 0.0, 1.0)
    return interpolated * ramp + extrapolated * (1.0 - ramp)


def rope_tables(
    positions, theta: float, head_dim: int, fraction: float = 1.0, *,
    inv_freq=None, scale: float = 1.0,
):
    """(cos, sin) [B, S, D] float32 over one head's lanes, so that
    `x * cos + partner(x) * sin` turns the first `fraction * D` lanes and
    keeps the rest: cos is 1 and sin 0 on lanes that stay, and sin carries
    the sign of the pair's first half. The turned pairs' frequencies are
    `inv_freq` (a vector, one a pair: `yarn_inv_freq`) where given, else
    the plain `theta^(-2t/turned)`; `scale` multiplies cos and sin of the
    lanes that turn (yarn's attention factor), never the lanes that stay."""
    turned = int(head_dim * fraction)
    if inv_freq is None:
        freqs = 1.0 / theta ** (
            jnp.arange(0, turned, 2, dtype=jnp.float32) / turned
        )
    else:
        freqs = jnp.asarray(inv_freq, jnp.float32)
        if freqs.shape != (turned // 2,):
            raise ValueError(
                f"rope: {freqs.shape} frequencies for {turned} turned lanes "
                f"of a head of {head_dim}: one a pair is {turned // 2}"
            )
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, t/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    still = jnp.zeros((*angles.shape[:-1], head_dim - turned), jnp.float32)
    return (
        jnp.concatenate([cos, cos, still + 1.0], axis=-1),
        jnp.concatenate([-sin, sin, still], axis=-1),
    )


def _partner(x, half: int, d: int, lane, roll):
    """Lane i's pair: lane i + half in a head's first `half` lanes, lane
    i - half in the next `half` (beyond them sin is 0). `roll` rotates
    the last axis towards higher lanes, by a non-negative amount."""
    down = roll(x, half)
    if 2 * half == d == x.shape[-1]:
        return down  # one head, all of it turned: the same roll either way
    return jnp.where(lane % d < half, roll(x, x.shape[-1] - half), down)


def rope_folded(x, cos, sin, half: int):
    """x [B, S, H·D] turned by tables [B, S, D], `half` lanes to a
    pair's other lane: the plain form."""
    d = cos.shape[-1]
    heads = x.shape[-1] // d
    x32 = x.astype(jnp.float32)
    partner = _partner(
        x32, half, d, jnp.arange(x.shape[-1]),
        lambda u, shift: jnp.roll(u, shift, axis=-1),
    )
    wide = lambda t: jnp.tile(t, heads)
    return (x32 * wide(cos) + partner * wide(sin)).astype(x.dtype)


def _turn_kernel(
    x_ref, cos_ref, sin_ref, o_ref, *, half: int, sign: float, d: int
):
    """A column of the tables' width at a time: one head of `d` lanes,
    or, where `d` divides a lane tile, the heads of one tile (the tables
    then hold a head's lanes that many times)."""
    width = cos_ref.shape[-1]
    cos = cos_ref[0]
    sin = sin_ref[0] * sign
    lane = jax.lax.broadcasted_iota(jnp.int32, cos.shape, 1)
    roll = lambda u, shift: pltpu.roll(u, shift, 1)
    for at in range(0, x_ref.shape[-1], width):
        xh = x_ref[0, :, at:at + width].astype(jnp.float32)
        o_ref[0, :, at:at + width] = (
            xh * cos + _partner(xh, half, d, lane, roll) * sin
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("half", "sign", "interpret", "name")
)
def _turn(x, cos, sin, half: int, sign: float, interpret: bool, name: str):
    b, s, width = x.shape
    head = cos.shape[-1]
    if head < flash._LANES:  # heads that share a lane tile
        tile = lambda t: jnp.tile(t, flash._LANES // head)
        cos, sin = tile(cos), tile(sin)
    d = cos.shape[-1]
    rows = flash._pick_block(_block_rows(width, x.dtype.itemsize), s)
    at = lambda i, j: (i, j, 0)
    return pl.pallas_call(
        functools.partial(_turn_kernel, half=half, sign=sign, d=head),
        grid=(b, s // rows),
        in_specs=[
            pl.BlockSpec((1, rows, width), at),
            pl.BlockSpec((1, rows, d), at),
            pl.BlockSpec((1, rows, d), at),
        ],
        out_specs=pl.BlockSpec((1, rows, width), at),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
        name=name,
    )(x, cos, sin)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def rope_turn(x, cos, sin, half: int, interpret: bool):
    """The kernel: x [B, S, H·D] turned by tables [B, S, D], D whole
    lanes, S in blocks of whole sublanes. No gradient reaches the tables
    (they come from integer positions)."""
    return _rope_turn_fwd(x, cos, sin, half, interpret)[0]


def _rope_turn_fwd(x, cos, sin, half, interpret):
    y = _turn(x, cos, sin, half, 1.0, interpret, "rope_turn_fwd")
    return y, (cos, sin)


def _rope_turn_bwd(half, interpret, tables, dy):
    # y_i = x_i c_i + x_p(i) s_i with s_p(i) = -s_i: the transpose is the
    # same turn by the negated angle.
    cos, sin = tables
    dx = _turn(dy, cos, sin, half, -1.0, interpret, "rope_turn_bwd")
    return dx, jnp.zeros_like(cos), jnp.zeros_like(sin)


rope_turn.defvjp(_rope_turn_fwd, _rope_turn_bwd)


def rope(
    x,
    positions,
    theta: float,
    fraction: float = 1.0,
    *,
    head_dim: int,
    mesh: Mesh | None = None,
    interpret: bool | None = None,
    inv_freq=None,
    scale: float = 1.0,
):
    """Rotary embeddings of x [B, S, H·D] (`head_dim` = D; H = 1 is a
    head-less key [B, S, D]) at `positions` [B, S]; with `fraction` < 1
    only the first `fraction * D` lanes of a head turn; `inv_freq` and
    `scale` as `rope_tables` takes them. The kernel or the plain form,
    from the shapes and the backend (module docstring); `interpret` forces
    the kernel, as `flash_attention`'s does."""
    cos, sin = rope_tables(
        positions, theta, head_dim, fraction, inv_freq=inv_freq, scale=scale
    )
    half = int(head_dim * fraction) // 2
    b, s, width = x.shape
    whole_lanes = head_dim % flash._LANES == 0 or (
        flash._LANES % head_dim == 0 and width % flash._LANES == 0
    )
    use_kernel = (
        (flash.kernels_compiled() if interpret is None else True)
        and whole_lanes
        and flash.flash_kernel_tileable(s, _BLOCK_ROWS)
    )
    heads = None
    if use_kernel and mesh is not None:
        # Stricter than `jit`'s own partitioning, as in `attend`: where
        # the batch or the heads do not divide, the plain form runs.
        bsz = math.prod(mesh.shape[a] for a in batch_axes(mesh))
        tp = mesh.shape.get("tp", 1)
        heads = "tp" if tp > 1 else None
        use_kernel = b % bsz == 0 and (width // head_dim) % tp == 0
    if not use_kernel:
        return rope_folded(x, cos, sin, half)
    interp = flash._auto_interpret(interpret)
    # nondiff custom_vjp args must be positional, so no partial().
    turn = lambda x_, cos_, sin_: rope_turn(x_, cos_, sin_, half, interp)
    if mesh is None:
        return turn(x, cos, sin)
    rows = batch_axes(mesh)
    return jax.shard_map(
        turn,
        mesh=mesh,
        in_specs=(P(rows, None, heads), P(rows, None, None), P(rows, None, None)),
        out_specs=P(rows, None, heads),
        check_vma=False,
    )(x, cos, sin)
