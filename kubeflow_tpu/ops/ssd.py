"""Chunked state-space scan (Mamba-2's SSD), forward and backward (Pallas TPU).

What `models/transformer.StateSpaceMixer` runs between its convolution and
its gate. Per head h of P channels, in group g of the G groups that share
B and C, with a state s [P, N] that starts at zero:

    s_t = exp(dt_t a) s_(t-1) + dt_t x_t (x) B_t        y_t = s_t C_t

(`a` < 0 a head, `dt` > 0 a head and position; the skip `D x` is the
caller's). The recurrence is computed a chunk of Q positions at a time.
With `cum_t` the sum of `dt_r a` over the chunk's positions up to t:

- inside a chunk `Y = ((C B^T) o L o dt_s) X`, `L_ts = exp(cum_t - cum_s)`
  for s <= t, else 0;
- the state that enters the chunk adds `exp(cum_t) C_t S_prev`;
- the state that leaves it is `exp(cum_Q) S_prev + X^T diag(w) B`,
  `w_s = exp(cum_Q - cum_s) dt_s`.

**Layout.** x and y are [B, S, H·P], heads folded into the lanes as the
projections write them (`ops/flash.py`, "block specs"); B and C are
[B, S, G·N]. One program holds one chunk of one group: `C B^T` is formed
once for the group's H/G heads. Heads narrower than a vreg's 128 lanes
are taken `128 // P` at a time (a lane tile): the matmuls against the
state, [Q, N] x [N, 128], serve the tile's heads at once, and only the
intra-chunk product, whose matrix differs by head, runs once a head over
the whole tile with the other heads' lanes dropped (on a 128-wide MXU a
64-column product costs what a 128-column one does). The state lives
transposed, [N, H·P] float32, in VMEM scratch along the sequential chunk
axis; batch and group are parallel. `dt` and `cum` are tiny ([B, S, H]
float32) and go in twice, time along sublanes ([B, G, S, H/G]) and along
lanes ([B, G, H/G, S]), so that `L` needs no transpose in the kernel;
the two copies are two arguments with a gradient each, and XLA adds them
through the transposes outside. `cum` itself (a cumulative sum over 128
positions of a 2 MB array) is XLA's, as is its transpose in the backward.

**Kernels** (`pallas_call` names, what a device trace keys their time on):
`ssd_fwd` writes y and the state entering every chunk (bfloat16: it is a
matmul operand wherever it is read); `ssd_bwd` walks the chunks backwards
with the state's gradient in scratch and writes dx, dB, dC (summed over
the group's heads in the kernel) and the four small gradients. It reads
the saved states and never recomputes the forward recurrence.

**Recomputation.** y and the states are both primal outputs and residuals
and carry `jax.checkpoint_name`s (`CHECKPOINT_OUT_NAME`,
`CHECKPOINT_STATES_NAME`): under `remat_policy="flash"` they are saved and
`ssd_fwd` is dead code in the backward, as `flash_fwd` is.

On the CPU backend (`ops/flash.kernels_compiled`) `ssd_scan` runs
`ssd_chunked`, the same chunked arithmetic in plain `jax.numpy` with
`jax`'s own gradient; `interpret=True` runs the kernels under the Pallas
interpreter (tests).

Alone on a v5e at the Nemotron-3-Super cell's shape (B = 1, S = 8192,
64 heads of 64 in 4 groups, N = 128, chunk 128, bfloat16; my chip run,
PR 32; device time from a trace of ten calls): `ssd_fwd` 0.460 ms a call,
`ssd_bwd` 2.479 ms; with XLA's cumulative sums, transposes and the sum of
the twin gradients round them, forward 0.615 ms and forward + backward
3.243 ms on the host's clock (min of 4 rounds of 25). By
`benchmarks/lib/flops_hybrid.ssd_call_cost` (memory-bound: 0.187 and 0.292
ms at 819 GB/s) that is 41 % and 12 % of the roofline. Against the plain
form on the chip at S = 1024: y within 1.7e-3, gradients within 4e-4 to
3.3e-3 (relative L2; both bfloat16 operands, different orders of sums).
The backward's bundles are bound by the cross-lane unit (20,073 XLU
operations in 11,657 bundles a grid step, the compile for a described
v5e): column vectors broadcast along lanes and row sums, a head at a
time, and 4,000 spills; not tuned yet.
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
from jax.sharding import Mesh, PartitionSpec as P

from kubeflow_tpu.ops.flash import _dot_nn, _dot_nt, _dot_tn, kernels_compiled
from kubeflow_tpu.parallel.sharding import batch_axes

CHECKPOINT_OUT_NAME = "ssd_out"
CHECKPOINT_STATES_NAME = "ssd_states"
_LANES = 128


def _heads_a_tile(heads_a_group: int, head_dim: int) -> int:
    """Heads taken together as one lane tile: as many as fill 128 lanes,
    and a divisor of the group's heads."""
    return math.gcd(heads_a_group, max(1, _LANES // head_dim))


def ssd_schedule(
    seq_len: int, *, heads: int, head_dim: int, groups: int, state: int,
    chunk: int, batch: int = 1, dtype_bytes: int = 2,
) -> dict:
    """Static accounting of the calls `ssd_scan` makes, for tests and
    benches: the grid, a program's heads, and what the forward saves for
    the backward."""
    chunks = -(-seq_len // chunk)
    hg = heads // groups
    return {
        "chunks": chunks,
        "padded_seq_len": chunks * chunk,
        "grid": (batch, groups, chunks),
        "heads_a_block": hg,
        "heads_a_lane_tile": _heads_a_tile(hg, head_dim),
        "saved_bytes_a_call": batch * chunks * (
            chunk * heads * head_dim + state * heads * head_dim
        ) * dtype_bytes,
        "state_scratch_bytes": state * hg * head_dim * 4,
    }


# -- the plain form ----------------------------------------------------------


def ssd_chunked(x, dt, a, b, c, *, groups: int, chunk: int):
    """The chunked scan in plain `jax.numpy`: x [B, S, H, P], dt [B, S, H]
    float32, a [H], b, c [B, S, G, N]; S a multiple of `chunk`. Returns y
    [B, S, H, P] float32. Matmul operands in x's dtype, float32
    accumulation, decays and the carried state in float32, as the
    kernels."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    nc, hg = s // chunk, h // groups
    f32 = jnp.float32
    xc = x.reshape(bsz, nc, chunk, groups, hg, p)
    bc = b.reshape(bsz, nc, chunk, groups, n)
    cc = c.reshape(bsz, nc, chunk, groups, n)
    dtc = dt.reshape(bsz, nc, chunk, groups, hg)
    cum = jnp.cumsum(dtc * a.reshape(groups, hg), axis=2)
    dot = functools.partial(jnp.einsum, preferred_element_type=f32)
    g = dot("bctgn,bcsgn->bcgts", cc, bc)
    gap = cum[:, :, :, None] - cum[:, :, None, :]  # [b, c, t, s, g, h]
    seen = jnp.tril(jnp.ones((chunk, chunk), bool))[None, None, :, :, None, None]
    lam = jnp.exp(jnp.where(seen, gap, -jnp.inf))
    m = (
        jnp.moveaxis(g, 2, 4)[..., None] * lam * dtc[:, :, None]
    ).astype(x.dtype)  # [b, c, t, s, g, h]
    y = dot("bctsgh,bcsghp->bctghp", m, xc)
    w = jnp.exp(cum[:, :, -1:] - cum) * dtc
    add = dot(
        "bcsghp,bcsgn->bcghpn", (xc.astype(f32) * w[..., None]).astype(x.dtype), bc
    )
    keep = jnp.exp(cum[:, :, -1])  # [b, c, g, h]

    def carry(state, step):
        add_c, keep_c = step
        return keep_c[..., None, None] * state + add_c, state

    _, entering = lax.scan(
        carry, jnp.zeros((bsz, groups, hg, p, n), f32),
        (jnp.moveaxis(add, 1, 0), jnp.moveaxis(keep, 1, 0)),
    )
    entering = jnp.moveaxis(entering, 0, 1).astype(x.dtype)
    y = y + jnp.exp(cum)[..., None] * dot(
        "bctgn,bcghpn->bctghp", cc, entering
    )
    return y.reshape(bsz, s, h, p)


# -- kernels -----------------------------------------------------------------


def _lane_scalars(cumc_ref, dtc_ref, tile: int, hp: int, p: int, q: int):
    """For one lane tile, [Q, W] and [1, W] arrays that hold each lane's
    head's `exp(cum_t)`, `w_t` and `exp(cum_Q)`."""
    w = hp * p
    lane_head = lax.broadcasted_iota(jnp.int32, (q, w), 1) // p
    decay_in = jnp.zeros((q, w), jnp.float32)
    weight = jnp.zeros((q, w), jnp.float32)
    keep = jnp.zeros((1, w), jnp.float32)
    for j in range(hp):
        h = tile * hp + j
        cc = cumc_ref[0, 0, :, h:h + 1]
        last = cumc_ref[0, 0, q - 1:q, h:h + 1]
        sel = lane_head == j
        decay_in = jnp.where(sel, jnp.exp(cc), decay_in)
        weight = jnp.where(
            sel, jnp.exp(last - cc) * dtc_ref[0, 0, :, h:h + 1], weight
        )
        keep = jnp.where(sel[:1], jnp.exp(last), keep)
    return lane_head, decay_in, weight, keep


def _decay_matrix(cumc_ref, cumr_ref, h: int, seen):
    """L [Q, Q]: exp(cum_t - cum_s) on and below the diagonal, else 0."""
    gap = cumc_ref[0, 0, :, h:h + 1] - cumr_ref[0, 0, h:h + 1, :]
    return jnp.exp(jnp.where(seen, gap, -jnp.inf))


def _fwd_kernel(
    x_ref, dtc_ref, dtr_ref, cumc_ref, cumr_ref, b_ref, c_ref, y_ref, st_ref,
    state, *, p: int, hp: int,
):
    q = x_ref.shape[1]
    w = hp * p
    tiles = x_ref.shape[2] // w

    @pl.when(pl.program_id(2) == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    bm, cm = b_ref[0], c_ref[0]
    g = _dot_nt(cm, bm)
    seen = (
        lax.broadcasted_iota(jnp.int32, (q, q), 0)
        >= lax.broadcasted_iota(jnp.int32, (q, q), 1)
    )
    st_ref[0, 0] = state[...].astype(st_ref.dtype)
    for t in range(tiles):
        cols = slice(t * w, (t + 1) * w)
        xt = x_ref[0, :, cols]
        lane_head, decay_in, weight, keep = _lane_scalars(
            cumc_ref, dtc_ref, t, hp, p, q
        )
        y = jnp.zeros((q, w), jnp.float32)
        for j in range(hp):
            h = t * hp + j
            lam = _decay_matrix(cumc_ref, cumr_ref, h, seen)
            m = (g * lam * dtr_ref[0, 0, h:h + 1, :]).astype(xt.dtype)
            y = jnp.where(lane_head == j, _dot_nn(m, xt), y)
        old = state[:, cols]
        y = y + decay_in * _dot_nn(cm, old.astype(cm.dtype))
        y_ref[0, :, cols] = y.astype(y_ref.dtype)
        xw = (xt.astype(jnp.float32) * weight).astype(xt.dtype)
        state[:, cols] = keep * old + _dot_tn(bm, xw)


def _bwd_kernel(
    x_ref, dtc_ref, dtr_ref, cumc_ref, cumr_ref, b_ref, c_ref, st_ref, dy_ref,
    dx_ref, ddtc_ref, ddtr_ref, dcumc_ref, dcumr_ref, db_ref, dc_ref,
    dstate, *, p: int, hp: int,
):
    q = x_ref.shape[1]
    w = hp * p
    tiles = x_ref.shape[2] // w
    hg = tiles * hp
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _init():
        dstate[...] = jnp.zeros_like(dstate)

    bm, cm = b_ref[0], c_ref[0]
    b32 = bm.astype(f32)
    g = _dot_nt(cm, bm)
    row = lax.broadcasted_iota(jnp.int32, (q, q), 0)
    seen = row >= lax.broadcasted_iota(jnp.int32, (q, q), 1)
    is_last = lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    head_lane = lax.broadcasted_iota(jnp.int32, (q, hg), 1)
    db = jnp.zeros(bm.shape, f32)
    dc = jnp.zeros(cm.shape, f32)
    ddtc = jnp.zeros((q, hg), f32)
    dcumc = jnp.zeros((q, hg), f32)
    for t in range(tiles):
        cols = slice(t * w, (t + 1) * w)
        xt, dyt = x_ref[0, :, cols], dy_ref[0, :, cols]
        entered = st_ref[0, 0, :, cols]          # S_prev^T [N, W]
        d_new = dstate[:, cols]                  # dS_new^T [N, W] float32
        d_new_lo = d_new.astype(xt.dtype)
        lane_head, decay_in, weight, keep = _lane_scalars(
            cumc_ref, dtc_ref, t, hp, p, q
        )
        state_lane = lax.broadcasted_iota(jnp.int32, d_new.shape, 1) // p
        from_state = _dot_nn(cm, entered)        # C S_prev^T [Q, W]
        dy_in = dyt.astype(f32) * from_state     # dY o (C S_prev^T)
        carried = d_new * entered.astype(f32)    # dS_new o S_prev
        dx = jnp.zeros((q, w), f32)
        for j in range(hp):
            h = t * hp + j
            sel = lane_head == j
            cc = cumc_ref[0, 0, :, h:h + 1]
            last = cumc_ref[0, 0, q - 1:q, h:h + 1]
            dr = dtr_ref[0, 0, h:h + 1, :]
            own = lambda u: u if hp == 1 else jnp.where(sel, u, jnp.zeros_like(u))
            xh, dyh = own(xt), own(dyt)
            lam = _decay_matrix(cumc_ref, cumr_ref, h, seen)
            gl = g * lam
            dm = _dot_nt(dyh, xh)                # dY_h X_h^T [Q, Q]
            jm = dm * gl
            ddt_row = jnp.sum(jm, axis=0, keepdims=True)
            ddtr_ref[0, 0, h:h + 1, :] = ddt_row
            dcumr_ref[0, 0, h:h + 1, :] = -ddt_row * dr
            dcum_col = jnp.sum(jm * dr, axis=1, keepdims=True)
            dg = (dm * lam * dr).astype(xt.dtype)
            dc = dc + _dot_nn(dg, bm)
            db = db + _dot_tn(dg, cm)
            dx = jnp.where(sel, _dot_tn((gl * dr).astype(xt.dtype), dyt), dx)
            # Through the state: what entered the chunk, and what leaves it.
            e_in = jnp.exp(cc)
            dc = dc + _dot_nt((dyh.astype(f32) * e_in).astype(xt.dtype), entered)
            x_ds = _dot_nt(xh, d_new_lo)         # X_h dS_new [Q, N]
            out_w = jnp.exp(last - cc)
            wt = out_w * dtc_ref[0, 0, :, h:h + 1]
            db = db + wt * x_ds
            r = jnp.sum(b32 * x_ds, axis=1, keepdims=True)
            dcum_col = dcum_col + e_in * jnp.sum(
                own(dy_in), axis=1, keepdims=True
            ) - wt * r
            total = lambda u: jnp.sum(
                jnp.sum(u, axis=1, keepdims=True), axis=0, keepdims=True
            )
            tail = total(wt * r) + jnp.exp(last) * total(
                jnp.where(state_lane == j, carried, 0.0)
            )
            dcum_col = dcum_col + jnp.where(is_last, tail, 0.0)
            ddtc = jnp.where(head_lane == h, out_w * r, ddtc)
            dcumc = jnp.where(head_lane == h, dcum_col, dcumc)
        dx = dx + weight * _dot_nn(bm, d_new_lo)
        dx_ref[0, :, cols] = dx.astype(dx_ref.dtype)
        dstate[:, cols] = keep * d_new + _dot_tn(
            cm, (dyt.astype(f32) * decay_in).astype(xt.dtype)
        )
    db_ref[0] = db.astype(db_ref.dtype)
    dc_ref[0] = dc.astype(dc_ref.dtype)
    ddtc_ref[0, 0] = ddtc
    dcumc_ref[0, 0] = dcumc


def _specs(q: int, hg: int, p: int, n: int, chunk_of):
    """Block specs by kind of operand; `chunk_of(c)` is the chunk a grid
    step holds (the backward walks them from the last)."""
    return {
        "x": pl.BlockSpec((1, q, hg * p), lambda b, g, c: (b, chunk_of(c), g)),
        "col": pl.BlockSpec((1, 1, q, hg), lambda b, g, c: (b, g, chunk_of(c), 0)),
        "row": pl.BlockSpec((1, 1, hg, q), lambda b, g, c: (b, g, 0, chunk_of(c))),
        "bc": pl.BlockSpec((1, q, n), lambda b, g, c: (b, chunk_of(c), g)),
        "state": pl.BlockSpec(
            (1, 1, n, hg * p), lambda b, g, c: (b, chunk_of(c), 0, g)
        ),
    }


_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)


@functools.partial(jax.jit, static_argnames=("p", "n", "q", "interpret"))
def _ssd_fwd(x, dtc, dtr, cumc, cumr, b, c, *, p, n, q, interpret):
    bsz, s, width = x.shape
    groups, hg = dtc.shape[1], dtc.shape[3]
    nc = s // q
    spec = _specs(q, hg, p, n, lambda c_: c_)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, p=p, hp=_heads_a_tile(hg, p)),
        grid=(bsz, groups, nc),
        in_specs=[spec[k] for k in ("x", "col", "row", "col", "row", "bc", "bc")],
        out_specs=[spec["x"], spec["state"]],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((bsz, nc, n, width), x.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((n, hg * p), jnp.float32)],
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name="ssd_fwd",
    )(x, dtc, dtr, cumc, cumr, b, c)


@functools.partial(jax.jit, static_argnames=("p", "n", "q", "interpret"))
def _ssd_bwd(x, dtc, dtr, cumc, cumr, b, c, states, dy, *, p, n, q, interpret):
    bsz, s, _ = x.shape
    groups, hg = dtc.shape[1], dtc.shape[3]
    nc = s // q
    spec = _specs(q, hg, p, n, lambda c_: nc - 1 - c_)
    small = lambda like: jax.ShapeDtypeStruct(like.shape, jnp.float32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, p=p, hp=_heads_a_tile(hg, p)),
        grid=(bsz, groups, nc),
        in_specs=[spec[k] for k in (
            "x", "col", "row", "col", "row", "bc", "bc", "state", "x"
        )],
        out_specs=[spec[k] for k in (
            "x", "col", "row", "col", "row", "bc", "bc"
        )],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            small(dtc), small(dtr), small(cumc), small(cumr),
            jax.ShapeDtypeStruct(b.shape, b.dtype),
            jax.ShapeDtypeStruct(c.shape, c.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((n, hg * p), jnp.float32)],
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name="ssd_bwd",
    )(x, dtc, dtr, cumc, cumr, b, c, states, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _ssd_core(x, dtc, dtr, cumc, cumr, b, c, p, n, q, interpret):
    """(y, states): y in x's layout, `states` [B, chunks, N, H·P] the
    state entering each chunk. `states` carries no cotangent path."""
    return _ssd_vjp_fwd(x, dtc, dtr, cumc, cumr, b, c, p, n, q, interpret)[0]


def _ssd_vjp_fwd(x, dtc, dtr, cumc, cumr, b, c, p, n, q, interpret):
    y, states = _ssd_fwd(
        x, dtc, dtr, cumc, cumr, b, c, p=p, n=n, q=q, interpret=interpret
    )
    # Named values that are both outputs and residuals: a policy that
    # saves them drops the forward kernel from the backward (flash.py).
    y = checkpoint_name(y, CHECKPOINT_OUT_NAME)
    states = checkpoint_name(states, CHECKPOINT_STATES_NAME)
    return (y, states), (x, dtc, dtr, cumc, cumr, b, c, states)


def _ssd_vjp_bwd(p, n, q, interpret, residuals, cts):
    dy, _ = cts
    return tuple(_ssd_bwd(
        *residuals, dy, p=p, n=n, q=q, interpret=interpret
    ))


_ssd_core.defvjp(_ssd_vjp_fwd, _ssd_vjp_bwd)


def _ssd_kernels(x, dt, a, b, c, *, groups, chunk, interpret):
    """The kernels over a sequence of whole chunks."""
    bsz, s, width = x.shape
    h = dt.shape[-1]
    p, n, hg = width // h, b.shape[-1] // groups, h // groups
    step = (dt * a).reshape(bsz, s // chunk, chunk, h)
    cum = jnp.cumsum(step, axis=2).reshape(bsz, s, h)
    col = lambda u: u.reshape(bsz, s, groups, hg).transpose(0, 2, 1, 3)
    row = lambda u: u.reshape(bsz, s, groups, hg).transpose(0, 2, 3, 1)
    y, _ = _ssd_core(
        x, col(dt), row(dt), col(cum), row(cum), b, c, p, n, chunk, interpret
    )
    return y


def _ssd_plain(x, dt, a, b, c, *, groups, chunk):
    """`ssd_chunked` over the folded arrays, y named as the kernels'."""
    bsz, s, _ = x.shape
    heads = lambda u, k: u.reshape(bsz, s, k, -1)
    y = ssd_chunked(
        heads(x, dt.shape[-1]), dt, a, heads(b, groups), heads(c, groups),
        groups=groups, chunk=chunk,
    )
    return checkpoint_name(
        y.reshape(bsz, s, -1).astype(x.dtype), CHECKPOINT_OUT_NAME
    )


def ssd_scan(
    x, dt, a, b, c, *, groups: int, chunk: int, mesh: Mesh | None = None,
    interpret: bool | None = None,
):
    """y [B, S, H·P] of the scan over x [B, S, H·P] with dt [B, S, H]
    (float32, positive), a [H] (float32, negative) and b, c [B, S, G·N].
    The kernels wherever they compile (or under the interpreter when
    `interpret` is True), the plain chunked form on the CPU. A Pallas
    call does not partition itself under `jit`, so with a mesh the
    kernels run in `shard_map` over the batch axes and, where `tp`
    divides the groups, whole groups over `tp`."""
    h = dt.shape[-1]
    if h % groups or x.shape[-1] % h or b.shape[-1] % groups:
        raise ValueError(
            f"{h} heads over {groups} groups, x {x.shape}, b {b.shape}: "
            "heads must divide into groups and the last axes into heads"
        )
    dt, a = dt.astype(jnp.float32), a.astype(jnp.float32)
    s = x.shape[1]
    pad = -s % chunk
    if pad:
        # dt = 0 past the end: the state neither decays nor takes input.
        grow = lambda u: jnp.pad(u, ((0, 0), (0, pad), (0, 0)))
        x, dt, b, c = grow(x), grow(dt), grow(b), grow(c)
    if interpret is None and not kernels_compiled():
        y = _ssd_plain(x, dt, a, b, c, groups=groups, chunk=chunk)
    elif mesh is None:
        y = _ssd_kernels(
            x, dt, a, b, c, groups=groups, chunk=chunk, interpret=bool(interpret)
        )
    else:
        rows = batch_axes(mesh)
        bsz = math.prod(mesh.shape[ax] for ax in rows)
        tp = mesh.shape.get("tp", 1)
        if x.shape[0] % bsz or groups % tp:
            raise ValueError(
                f"the scan on mesh {dict(mesh.shape)} needs batch "
                f"{x.shape[0]} to divide over dp·fsdp and {groups} groups "
                "over tp"
            )
        over = "tp" if tp > 1 else None
        wide = P(rows, None, over)
        y = jax.shard_map(
            functools.partial(
                _ssd_kernels, groups=groups // tp, chunk=chunk,
                interpret=bool(interpret),
            ),
            mesh=mesh,
            in_specs=(wide, wide, P(over), wide, wide),
            out_specs=wide,
            check_vma=False,
        )(x, dt, a, b, c)
    return y[:, :s] if pad else y
