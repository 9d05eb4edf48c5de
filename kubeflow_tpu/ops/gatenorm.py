"""The recurrent mixers' gated norm as one kernel pair (Pallas TPU).

What a state-space mixer does between its scan and its out-projection, and
a delta-rule mixer between the delta rule and its own, over o [B, S, W]
and a gate [B, S, W] in bfloat16, a group of `group` lanes (a head, or a
state-space group's channels), a learned scale [W] and `eps`, in either
order and under either activation `act` of the gate, `silu` or `sigmoid`:

    s = o + d * x                   the skip, where there is one: x [B, S, W]
                                    bfloat16, d [W], the sum float32, never
                                    rounded
    gate first:   t = s act(gate)
                  out = scale t / sqrt(mean_group(t^2) + eps)
    norm first:   out = scale s / sqrt(mean_group(s^2) + eps) * act(gate)

(Mamba-2: gate first under `silu`; Kimi's delta mixer: norm first under
`sigmoid`; Gated DeltaNet: norm first under `silu`.)

XLA makes float32 elementwise passes over [tokens, W] of this: the widened
operands, the activation, the squares' sum and its way back over the
lanes, and backward each of them again and their gradients (the nemotron
cell: `ssm.gate_norm` 28.8 ms of a 378.9 ms step for bytes the memory
moves in 4.5, PERF.md §5, PR 42). Here it is ONE pass forward and ONE
backward over blocks of `[rows, group]`, every float32 array of it in
VMEM:

- `gatenorm_fwd` reads each operand's block once, widens a few rows at a
  time, sums a group's squares over its lane tiles and then across the
  lanes, and writes `out` in bfloat16.
- `gatenorm_bwd` reads the same operands and `out`'s cotangent, forms the
  activation, its slope and the norm again in VMEM, writes each operand's
  gradient once in bfloat16 (the skip's two, `ds` and `d * ds`, each
  rounded once from the float32 `ds`) and accumulates the scale's and d's
  gradients in float32 over the row blocks (the grid's sequential axes;
  their block stays resident, eight partial rows each that XLA sums at
  the end).

The state-space mixer's z and x are lane slices of its in-projection's
and its convolution's results, which XLA copies out for a Pallas call:
read through the blocks' index maps where they lie the step was no
faster (PERF.md §6, PR 43), so the kernels take what they are handed.

`gated_norm` is the one entry and decides which form runs, from what the
program can see (`kernels_apply`): the pair, or `gated_norm_plain`, XLA's
passes, which the CPU, float32 and a mesh of several devices run and the
tests hold the kernels to.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh

from kubeflow_tpu.ops import flash
from kubeflow_tpu.ops.shortconv import _chunks, head_sums

_LANES = flash._LANES
# The most rows of a block at a group of one lane tile, and the most
# elements of one: a wider group takes as many rows fewer (a block of each
# operand and result lies in VMEM twice over, seven of them backward).
_ROWS = 2048
_BLOCK = _ROWS * _LANES
# Elements the kernels' loops take at a time: 512 rows of one lane tile (128,
# 256 and 512 took 0.361, 0.346 and 0.326 ms forward at [8192, 4096] in
# groups of 128 lanes on the v5e, 0.446, 0.405 and 0.407 in groups of
# 1,024, the backward 0.51 and 0.72 at each; PERF.md §6, PR 43).
_CHUNK = 512 * _LANES
# The widest group: its block of 128 rows is `_BLOCK` elements.
_MAX_GROUP = _BLOCK // _LANES
# Rows of the vectors' table [8, W] float32 the kernels read: the norm's
# scale, then the skip's d.
_TABLE = 8


def kernels_apply(
    o, gate, group: int, mesh: Mesh | None, compiled: bool | None = None,
) -> bool:
    """Whether the gated norm over o and gate [B, S, W] runs as the kernel
    pair: `flash.row_blocks_apply` over whole groups no wider than a
    block, and a bfloat16 gate."""
    return (
        0 < group <= _MAX_GROUP
        and flash.row_blocks_apply(o, group, mesh, compiled)
        and gate.dtype == jnp.bfloat16
    )


ACTIVATIONS = {"silu": jax.nn.silu, "sigmoid": jax.nn.sigmoid}


def _activation_of(gate_first: bool, act: str | None) -> str:
    """`act`, or the one the order's published mixer has."""
    act = act or ("silu" if gate_first else "sigmoid")
    if act not in ACTIVATIONS:
        raise ValueError(
            f"the gate's activation {act!r}: expected one of {sorted(ACTIVATIONS)}"
        )
    return act


def gated_norm_plain(
    o, gate, scale, group: int, eps: float, gate_first: bool, skip=None,
    act: str | None = None,
):
    """`gated_norm` as XLA's passes (module docstring's two orders), every
    array float32 from the operands' casts to the result's."""
    f32 = jnp.float32
    s, gate = o.astype(f32), gate.astype(f32)
    a = ACTIVATIONS[_activation_of(gate_first, act)]
    if skip is not None:
        x, d = skip
        s = s + d * x.astype(f32)
    t = s * a(gate) if gate_first else s
    out = t * lax.rsqrt(head_sums(t * t, group) / group + eps) * scale
    if not gate_first:
        out = out * a(gate)
    return out.astype(o.dtype)


def _halved(n: int, least: int, room: int) -> int:
    """`n` (a power of two times `least`) halved until it is within `room`."""
    while n > least and n > room:
        n //= 2
    return n


def _block(seq: int, group: int) -> tuple[int, int]:
    """(rows of a block, rows a loop step): a block is one group wide, its
    rows a power of two that divides the sequence."""
    rows = _halved(math.gcd(seq, _ROWS), _LANES, _BLOCK // group)
    return rows, _halved(rows, 16, max(_CHUNK // group, 16))


def _normed(o_ref, gate_ref, x_ref, w_ref, rows, *, gate_first, act, inv, eps):
    """Rows of a block up to the norm: (s, sigmoid(gate), silu(gate), t =
    what the norm reads, r = `1 / sqrt(mean t² + eps)` a row)."""
    f32 = jnp.float32
    s = o_ref[rows, :].astype(f32)
    if x_ref is not None:
        s = s + w_ref[1:2, :] * x_ref[rows, :].astype(f32)
    # sigmoid by tanh: one pass of the transcendental unit and three of
    # the vector unit's where 1 / (1 + e^-m) is an exact division
    # (`ops/shortconv._activation`; PERF.md §6, PR 42).
    half = 0.5 * gate_ref[rows, :].astype(f32)
    tanh = jnp.tanh(half)
    sig, silu = 0.5 * tanh + 0.5, half * tanh + half
    t = s * (silu if act == "silu" else sig) if gate_first else s
    # a group's lane tiles added tile-wise, then one reduction a row group
    r = lax.rsqrt(jnp.sum(t * t, axis=1, keepdims=True) * inv + eps)
    return s, sig, silu, t, r


def _fwd_kernel(*refs, chunk, skip, gate_first, act, inv, eps):
    o_ref, gate_ref, x_ref, w_ref, out_ref = (
        refs if skip else (*refs[:2], None, *refs[2:])
    )

    def body(first, carry):
        rows = pl.ds(first, chunk)
        _, sig, silu, t, r = _normed(
            o_ref, gate_ref, x_ref, w_ref, rows, gate_first=gate_first,
            act=act, inv=inv, eps=eps,
        )
        out = t * (r * w_ref[0:1, :])
        if not gate_first:
            out = out * (silu if act == "silu" else sig)
        out_ref[rows, :] = out.astype(out_ref.dtype)
        return carry

    _chunks(o_ref.shape[0], chunk, body)


def _bwd_kernel(*refs, chunk, skip, gate_first, act, inv, eps):
    f32 = jnp.float32
    if skip:
        (o_ref, gate_ref, x_ref, dout_ref, w_ref,
         do_ref, dgate_ref, dx_ref, dw_ref) = refs
    else:
        o_ref, gate_ref, dout_ref, w_ref, do_ref, dgate_ref, dw_ref = refs
        x_ref = dx_ref = None
    lanes = o_ref.shape[1]

    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def eighths(p):
        return sum(p[k:k + 8] for k in range(0, p.shape[0], 8))

    def body(first, sums):
        rows = pl.ds(first, chunk)
        s, sig, silu, t, r = _normed(
            o_ref, gate_ref, x_ref, w_ref, rows, gate_first=gate_first,
            act=act, inv=inv, eps=eps,
        )
        # the gate's activation and, where it is asked for, its slope
        a, slope = (
            (silu, lambda: sig + silu * (1.0 - sig)) if act == "silu"
            else (sig, lambda: sig * (1.0 - sig))
        )
        dout = dout_ref[rows, :].astype(f32)
        scale = w_ref[0:1, :]
        dn = dout if gate_first else dout * a  # the norm's cotangent
        # n = c t r, r = (mean t² + eps)^-1/2: dt = r (c dn - t r² mean(c dn t))
        g = scale * dn
        dot = jnp.sum(g * t, axis=1, keepdims=True) * inv
        dt = r * (g - t * (r * r * dot))
        if gate_first:
            ds = dt * a
            dgate = dt * s * slope()
        else:
            ds = dt
            dgate = dout * (t * (r * scale)) * slope()
        do_ref[rows, :] = ds.astype(do_ref.dtype)
        dgate_ref[rows, :] = dgate.astype(dgate_ref.dtype)
        parts = [dn * t * r]  # the scale's gradient a row, then d's
        if skip:
            dx_ref[rows, :] = (w_ref[1:2, :] * ds).astype(dx_ref.dtype)
            parts.append(ds * x_ref[rows, :].astype(f32))
        return [acc + eighths(p) for acc, p in zip(sums, parts)]

    sums = _chunks(
        o_ref.shape[0], chunk, body, [jnp.zeros((8, lanes), f32)] * (1 + skip)
    )
    for j, acc in enumerate(sums):
        dw_ref[8 * j:8 * j + 8, :] += acc


def _table(scale, d):
    """The norm's scale [W] and the skip's d [W] or None as the [8, W]
    float32 table the kernels read."""
    f32 = jnp.float32
    width, = scale.shape
    return jnp.concatenate([
        scale.astype(f32)[None],
        (jnp.zeros((width,), f32) if d is None else d.astype(f32))[None],
        jnp.zeros((_TABLE - 2, width), f32),
    ])


# Under `jit`: a stack's layers trace and lower each body once a program.
_pass = functools.partial(jax.jit, static_argnames=(
    "group", "gate_first", "act", "eps", "interpret",
))


def _specs(o, group):
    batch, seq, width = o.shape
    rows, chunk = _block(seq, group)
    block = pl.BlockSpec((None, rows, group), lambda l, b, i: (b, i, l))
    table = lambda n: pl.BlockSpec((n, group), lambda l, b, i: (0, l))
    return (width // group, batch, seq // rows), chunk, block, table


@_pass
def _fwd(o, gate, x, table, *, group, gate_first, act, eps, interpret):
    grid, chunk, block, tab = _specs(o, group)
    operands = [o, gate] + ([] if x is None else [x])
    return pl.pallas_call(
        functools.partial(
            _fwd_kernel, chunk=chunk, skip=x is not None,
            gate_first=gate_first, act=act, inv=1.0 / group, eps=eps,
        ),
        grid=grid,
        in_specs=[block] * len(operands) + [tab(_TABLE)],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(o.shape, o.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=interpret,
        name="gatenorm_fwd",
    )(*operands, table)


@_pass
def _bwd(o, gate, x, dout, table, *, group, gate_first, act, eps, interpret):
    """-> (do, dgate, dx or None, the scale's gradient [W], d's or None)."""
    grid, chunk, block, tab = _specs(o, group)
    skip = x is not None
    operands = [o, gate] + ([x] if skip else []) + [dout]
    wide = jax.ShapeDtypeStruct(o.shape, o.dtype)
    *grads, partial = pl.pallas_call(
        functools.partial(
            _bwd_kernel, chunk=chunk, skip=skip, gate_first=gate_first,
            act=act, inv=1.0 / group, eps=eps,
        ),
        grid=grid,
        in_specs=[block] * len(operands) + [tab(_TABLE)],
        out_specs=[block] * (2 + skip) + [tab(8 * (1 + skip))],
        out_shape=[wide] * (2 + skip) + [
            jax.ShapeDtypeStruct((8 * (1 + skip), o.shape[-1]), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="gatenorm_bwd",
    )(*operands, table)
    sums = partial.reshape(1 + skip, 8, -1).sum(axis=1)
    do, dgate, dx = (*grads, None)[:3]
    return do, dgate, dx, sums[0], sums[1] if skip else None


def gated_norm(
    o, gate, scale, *, group: int, eps: float, gate_first: bool,
    act: str | None = None, skip=None, mesh: Mesh | None = None,
    interpret: bool | None = None,
):
    """o [B, S, W] through the gated norm a group of `group` lanes with
    the learned `scale` [W]. `gate_first`: the gate's activation then the
    norm (Mamba-2's order), else the norm then the gate's (the delta
    mixers'). `act`: the gate's activation, "silu" or "sigmoid" (None: the
    order's published mixer's, `silu` gate first, else `sigmoid`).
    `skip` = (x [B, S, W], d [W]) adds `d * x` to o in float32 first. The
    kernel pair where `kernels_apply` says so (or under the interpreter
    when `interpret` is True, as `ssd_scan` reads it), `gated_norm_plain`
    anywhere else."""
    act = _activation_of(gate_first, act)
    if interpret is None and not kernels_apply(o, gate, group, mesh):
        return gated_norm_plain(o, gate, scale, group, eps, gate_first, skip, act)
    x, d = skip or (None, None)
    return _gated_norm(
        o, gate, x, d, scale, group, float(eps), (bool(gate_first), act),
        flash._auto_interpret(interpret),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _gated_norm(o, gate, x, d, scale, group, eps, how, interpret):
    """`how` = (gate first, the gate's activation)."""
    return _gated_norm_fwd(o, gate, x, d, scale, group, eps, how, interpret)[0]


def _gated_norm_fwd(o, gate, x, d, scale, group, eps, how, interpret):
    out = _fwd(
        o, gate, x, _table(scale, d), group=group, gate_first=how[0],
        act=how[1], eps=eps, interpret=interpret,
    )
    return out, (o, gate, x, d, scale)


def _gated_norm_bwd(group, eps, how, interpret, residuals, dout):
    o, gate, x, d, scale = residuals
    do, dgate, dx, dscale, dd = _bwd(
        o, gate, x, dout, _table(scale, d), group=group,
        gate_first=how[0], act=how[1], eps=eps, interpret=interpret,
    )
    return (
        do, dgate, dx, None if d is None else dd.astype(d.dtype),
        dscale.astype(scale.dtype),
    )


_gated_norm.defvjp(_gated_norm_fwd, _gated_norm_bwd)
