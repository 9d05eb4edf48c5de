"""Chunked gated delta rule in two forms, forward and backward (Pallas TPU).

What `models/transformer.DeltaMixer` runs between its convolutions and its
gated norm: ONE rule, `kda_scan` its one entry, in the form the decay's
shape asks for. A decay a key CHANNEL (Kimi Delta Attention) is the form
described first; a decay a HEAD over grouped key heads (Gated DeltaNet) is
the same rule with `diag(a_t) = a_t I`, whose chunk simplifies: see "the
decay a head" below, `gdn_chunked` and the kernels `gdn_fwd` / `gdn_bwd`.

Per head of `d` key and `d` value channels, with a state S
[d, d] that starts at zero, a decay `a_t = exp(g_t)` a CHANNEL of the key
(`g` < 0) and a step `b_t` in (0, 1) a head:

    S_t = (I - b_t k_t k_t^T) diag(a_t) S_(t-1) + b_t k_t v_t^T
    o_t = S_t^T q_t

(q arrives scaled and q, k L2-normalised: the caller's). The state is
first decayed, then corrected by the key it is about to write: the delta
rule. The recurrence is computed a chunk of C positions at a time in the
WY / UT form. With `G` the cumulative sum of g inside the chunk, `S` the
state entering it, `Kd = K e^G`, `Qd = Q e^G`, `Kr = K e^(G_C - G)`:

    A = strict_lower(diag(b) (K e^G)(K e^-G)^T)    M = lower((Q e^G)(K e^-G)^T)
    T = (I + A)^-1 diag(b)
    Vn = T (V - Kd S)                              (= U - W S, U = T V, W = T Kd)
    O  = Qd S + M Vn
    S' = diag(e^(G_C)) S + Kr^T Vn

**Decay is a channel's** and can pass e^-100 inside a chunk, so `e^(-G_s)`
over a chunk overflows. Only differences of G go through `exp`: A and M are
formed a sub-block of `SUB` rows at a time against the columns up to it,
with the sub-block's first row as the reference, `(K_t e^(G_t - G_ref)) .
(K_s e^(G_ref - G_s))`: the first exponent is never positive, the second is
positive only inside the diagonal sub-block (at most `SUB` - 1 steps of
decay), where it is clipped at `_CLIP`: a channel that loses more than
e^-80 within 16 positions reads as fully decayed across them. Every other
exponent (`G`, `G_C - G`) is never positive.

**Kernels** (`pallas_call` names, what a device trace keys their time
on). `kda_fwd` holds one chunk of `_HEADS_A_STEP` heads a program and walks
the chunks with the state transposed, [d_v, d_k] float32, in VMEM scratch
(the decay a key channel then multiplies along the lanes). In VMEM it forms
G (g's running sum from the chunk's first row, a product with a triangle of
ones over g as three bfloat16 addends: float32's 24 bits), the two
triangular products a sub-block row, the in-chunk inverse (block
elimination by halves, `_inverses`: `[[P, 0], [R, Q]]^-1 = [[P^-1, 0],
[-Q^-1 R P^-1, Q^-1]]`, two products a level on the whole [C, C] matrix,
no power of A formed), T and M, and the chunk's products against the
state; it writes o and the state entering every chunk (bfloat16: a matmul
operand wherever it is read). `kda_bwd` walks the chunks backwards from the
saved states with the state's gradient in scratch, forms G, T, M and Vn
again and writes dq, dk, dv, dg and db: through the recurrence, through the
inverse (`dA = -X^T dX X^T`) and through the sub-block products, where the
gradient of G is taken against the operands AS ROUNDED, so that what a
pair of positions adds at its row and takes at its column cancels exactly
past the pair (the decay's gradient is a sum over later positions of
terms that mostly cancel; XLA's gradient of the same products, each side
rounded on its own, read 0.18 off the reference at one leaf on the chip,
PERF.md §6, PR 41), then summed back along the chunk to g's own. It never
runs the forward recurrence again. q, k, v, g and o stay [B, S, H·d] as
the projections write them: a head is one lane tile. b goes in twice, time
along sublanes and along lanes (two small transposes, XLA's). `_intra` and
`_unit_lower_inverse` are the same arithmetic in `jax.numpy` for the plain
form, float32 at full precision.

**Recomputation.** o and the states are both primal outputs and residuals
and carry `jax.checkpoint_name`s (`CHECKPOINT_OUT_NAME`,
`CHECKPOINT_STATES_NAME`): under `remat_policy="flash"` they are saved and
`kda_fwd` is dead code in the backward.

On the CPU backend (`ops/flash.kernels_compiled`) `kda_scan` runs
`kda_chunked`, the same chunked arithmetic in plain `jax.numpy` with
`jax`'s own gradient; `interpret=True` runs the kernels under the Pallas
interpreter (tests).

**The decay a head** (`g` [B, S, H] float32, q and k of H_k key heads, value
head h reading key head h // (H / H_k)). A head's scalar factors out of `K
K^T`: `A = strict_lower(b_t (k_t . k_s) e^(G_t - G_s))` is ONE [C, C]
product and a mask of differences that are never positive (no sub-block,
no clip), `e^G` scales whole rows of the products against the state, and
the raw `K K^T` and `Q K^T` are a KEY head's, shared by the value heads
that read it. `gdn_fwd` / `gdn_bwd` hold one chunk of `_HEADS_A_STEP` value
heads and their key heads a program: q and k are read as they are stored
([B, S, H_k·d], never repeated), g and b as one [C, 2·heads] float32 block
(never widened to a head's lanes; a column becomes a row by a select
against the diagonal), and the decay's gradient is taken from ONE array of
pairs, added at a pair's row and taken at its column, then summed back
along the chunk. o and the states are named as the channel form's, so
`remat_policy="flash"` drops `gdn_fwd` from the backward likewise.
`gdn_chunked` is the same arithmetic in `jax.numpy` (the CPU, float32, a
mesh of several devices).
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

CHECKPOINT_OUT_NAME = "kda_out"
CHECKPOINT_STATES_NAME = "kda_states"
SUB = 16            # rows of a sub-block: one reference row each
_CLIP = 80.0        # the largest exponent a diagonal sub-block's keys take
_HEADS_A_STEP = 8   # heads (lane tiles) a grid step holds: 2 / 4 / 8 took
                    # 10.7 / 6.3 / 4.3 ms a layer both ways (PERF.md §6, PR 41)


def _sub(chunk: int) -> int:
    return math.gcd(chunk, SUB)


def _heads_a_step(heads: int) -> int:
    return math.gcd(heads, _HEADS_A_STEP)


def kda_schedule(
    seq_len: int, *, heads: int, head_dim: int, chunk: int, batch: int = 1,
    dtype_bytes: int = 2, key_heads: int | None = None,
) -> dict:
    """Static accounting of the calls `kda_scan` makes, for tests and
    benches: the grid, a program's heads, the sub-blocks of the in-chunk
    products and what the forward saves for the backward. `key_heads`: the
    decay-a-head form over that many key heads (no sub-block: a head's
    decay factors out of the chunk's products)."""
    chunks = -(-seq_len // chunk)
    saved = batch * chunks * (
        chunk * heads * head_dim + head_dim * heads * head_dim
    ) * dtype_bytes
    if key_heads is not None:
        ks, hs = _group_heads(heads, key_heads)
        return {
            "form": "head", "chunks": chunks,
            "padded_seq_len": chunks * chunk,
            "grid": (batch, heads // hs, chunks),
            "heads_a_step": hs, "key_heads_a_step": ks,
            "saved_bytes_a_call": saved,
            "state_scratch_bytes": head_dim * hs * head_dim * 4,
        }
    hs = _heads_a_step(heads)
    n = chunk // _sub(chunk)
    return {
        "chunks": chunks,
        "padded_seq_len": chunks * chunk,
        "grid": (batch, heads // hs, chunks),
        "heads_a_step": hs,
        "sub_block": _sub(chunk),
        "sub_block_pairs": n * (n + 1) // 2,
        "saved_bytes_a_call": saved,
        "state_scratch_bytes": head_dim * hs * head_dim * 4,
    }


# -- the in-chunk part, for the plain form -------------------------------------


def _unit_lower_inverse(a):
    """(I + a)^-1 for a [..., n, n] strictly lower triangular, n a power of
    two, float32 at full precision: block elimination by halves,
    `[[P, 0], [R, Q]]^-1 = [[P^-1, 0], [-Q^-1 R P^-1, Q^-1]]`. x holds the
    inverses of the diagonal blocks of size s, as one block-diagonal
    matrix; the blocks R that join them in pairs are `a` under a mask, so a
    level is `x - x R x` on whole [n, n] matrices (no array with a minor
    axis of 1, 2, 4... lanes). Exact in exact arithmetic and stable as
    forward substitution is; no power of `a` is formed."""
    n = a.shape[-1]
    if n & (n - 1):
        raise ValueError(f"the in-chunk inverse halves its blocks: {n} rows")
    dot = functools.partial(
        jnp.einsum, "...ij,...jk,...kl->...il",
        precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )
    row = lax.broadcasted_iota(jnp.int32, (n, n), 0)
    col = lax.broadcasted_iota(jnp.int32, (n, n), 1)
    x = jnp.broadcast_to(jnp.eye(n, dtype=jnp.float32), a.shape)
    s = 1
    while s < n:
        # rows of an odd block of size s, columns of the even one before it
        joins = (row // s == col // s + 1) & (row // (2 * s) == col // (2 * s))
        x = x - dot(x, jnp.where(joins, a, 0.0), x)
        s *= 2
    return x


def _intra(q, k, g, b, *, chunk: int):
    """The part of a chunk that needs no state: q, k [B, S, H·d], g
    [B, S, H·d] float32, b [B, S, H] float32, S whole chunks. -> G
    [B, S, H·d] float32 (g summed from the chunk's first row), T and M
    [B, chunks, H, C, C] in q's dtype."""
    bsz, s, width = k.shape
    h = b.shape[-1]
    d = width // h
    nc, sub = s // chunk, _sub(chunk)
    n = chunk // sub
    f32 = jnp.float32
    cum = jnp.cumsum(g.reshape(bsz, nc, chunk, width), axis=2)
    rows = lambda u: u.reshape(bsz, nc, n, sub, h, d)
    gs, ks, qs = rows(cum), rows(k).astype(f32), rows(q).astype(f32)
    ref = gs[:, :, :, :1]
    own = jnp.exp(gs - ref)
    left = jnp.stack([ks * own, qs * own], axis=4).astype(k.dtype)
    parts = []
    for i in range(n):
        # The columns up to sub-block i, against its reference row.
        right = (ks[:, :, :i + 1] * jnp.exp(jnp.minimum(
            ref[:, :, i:i + 1] - gs[:, :, :i + 1], _CLIP
        ))).astype(k.dtype)
        raw = jnp.einsum(
            "bctwhd,bcjshd->bcwhtjs", left[:, :, i], right,
            preferred_element_type=f32,
        ).reshape(bsz, nc, 2, h, sub, (i + 1) * sub)
        parts.append(jnp.pad(
            raw, [(0, 0)] * 5 + [(0, chunk - (i + 1) * sub)]
        ))
    raw = jnp.concatenate(parts, axis=4)  # [B, nc, 2, H, C, C]
    t_at = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_at = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    beta = b.reshape(bsz, nc, chunk, h).transpose(0, 1, 3, 2)  # [B, nc, H, C]
    a = jnp.where(t_at > s_at, raw[:, :, 0] * beta[..., None], 0.0)
    m = jnp.where(t_at >= s_at, raw[:, :, 1], 0.0)
    t = _unit_lower_inverse(a) * beta[..., None, :]
    return cum.reshape(bsz, s, width), t.astype(k.dtype), m.astype(k.dtype)


# -- the plain form ------------------------------------------------------------


def _recurrence_plain(q, k, v, cum, t, m, *, chunk: int):
    """The walk over chunks in plain `jax.numpy`: matmul operands in q's
    dtype, float32 accumulation, the state in float32, as the kernels."""
    bsz, s, width = q.shape
    nc, h = t.shape[1], t.shape[2]
    d = width // h
    f32 = jnp.float32
    by_chunk = lambda u: jnp.moveaxis(
        u.reshape(bsz, nc, chunk, h, d), 1, 0
    )  # [nc, B, C, H, d]
    dot = functools.partial(jnp.einsum, preferred_element_type=f32)
    lo = lambda u: u.astype(q.dtype)

    def step(state, xs):  # state [B, H, d_k, d_v] float32
        qc, kc, vc, gc, tc, mc = xs
        eg = jnp.exp(gc)
        last = gc[:, -1:]
        kd, qd = lo(kc.astype(f32) * eg), lo(qc.astype(f32) * eg)
        kr = lo(kc.astype(f32) * jnp.exp(last - gc))
        sb = lo(state)
        r = vc.astype(f32) - dot("bthk,bhkv->bthv", kd, sb)
        vn = lo(dot("bhts,bshv->bthv", tc, lo(r)))
        o = dot("bthk,bhkv->bthv", qd, sb) + dot("bhts,bshv->bthv", mc, vn)
        state = state * jnp.exp(last)[:, 0, :, :, None] + dot(
            "bthk,bthv->bhkv", kr, vn
        )
        return state, o

    _, o = lax.scan(
        step, jnp.zeros((bsz, h, d, d), f32),
        (by_chunk(q), by_chunk(k), by_chunk(v), by_chunk(cum),
         jnp.moveaxis(t, 1, 0), jnp.moveaxis(m, 1, 0)),
    )
    return jnp.moveaxis(o, 0, 1).reshape(bsz, s, width)


def kda_chunked(q, k, v, g, b, *, chunk: int):
    """The chunked delta rule in plain `jax.numpy`: q, k, v [B, S, H·d], g
    [B, S, H·d] float32 (log decay, negative), b [B, S, H] float32; S a
    multiple of `chunk`. Returns o [B, S, H·d] float32."""
    cum, t, m = _intra(q, k, g, b, chunk=chunk)
    return _recurrence_plain(q, k, v, cum, t, m, chunk=chunk)


# -- kernels -------------------------------------------------------------------
#
# A program holds one chunk of `_HEADS_A_STEP` heads. Everything of the
# chunk is formed in VMEM: the cumulative decay (`_running`), the two
# triangular products a sub-block row (`_tri_products`), the inverse
# (`_inverses`), T and M, then the products against the state. Matmul
# operands are q's dtype (bfloat16 in a cell), sums float32. A head's chain
# of small products is bound by the MXU's latency, not its rate, so every
# stage is written for all the program's heads at once (lists over the
# heads): independent products lie next to each other in the program.


def _iotas(c: int):
    return (
        lax.broadcasted_iota(jnp.int32, (c, c), 0),
        lax.broadcasted_iota(jnp.int32, (c, c), 1),
    )


def _each(f, *lists):
    return [f(*args) for args in zip(*lists)]


def _running(u, dot, ones):
    """The running sum of u [C, W] float32 along the chunk as a product
    with `ones` (lower triangular, the diagonal in it): `_dot_nn` sums
    from the chunk's first row down to each row, `_dot_tn` from each row
    down to the last. u goes in as three bfloat16 addends, each exact
    against zeros and ones and summed in float32: u's 24 bits."""
    parts, rest = [], u
    for _ in range(3):
        parts.append(rest.astype(jnp.bfloat16))
        rest = rest - parts[-1].astype(jnp.float32)
    return sum(dot(ones, part) for part in parts)


def _sub_block(qj, kj, gj, i: int, sub: int, lo):
    """Sub-block row i's operands: `left` [2 sub, d] (k's rows then q's,
    decayed from the row's reference) and `right` [C, d] (every key of the
    chunk, decayed TO that reference: past the diagonal the exponent is
    positive and clipped, and the product is masked), both as the matmuls
    read them, with the decays they took."""
    rows = slice(i * sub, (i + 1) * sub)
    ref = gj[i * sub:i * sub + 1, :]
    own = jnp.exp(gj[rows] - ref)
    to_ref = jnp.exp(jnp.minimum(ref - gj, _CLIP))
    left = lo(jnp.concatenate([kj[rows] * own, qj[rows] * own], axis=0))
    return left, lo(kj * to_ref), own, to_ref


def _tri_products(qj, kj, gj, sub: int, lo):
    """(K e^G)(K e^-G)^T and (Q e^G)(K e^-G)^T of one chunk [C, C],
    unmasked, a sub-block row at a time."""
    of_k, of_q = [], []
    for i in range(gj.shape[0] // sub):
        left, right, _, _ = _sub_block(qj, kj, gj, i, sub, lo)
        raw = _dot_nt(left, right)
        of_k.append(raw[:sub])
        of_q.append(raw[sub:])
    return jnp.concatenate(of_k, axis=0), jnp.concatenate(of_q, axis=0)


def _inverses(a, lo):
    """`_unit_lower_inverse` on each [C, C] matrix of the list `a`, in
    VMEM: the first level is `I - a` under its mask, every further one
    two products a matrix. Their operands are rounded as every matmul's
    here; T leaves rounded so anyway."""
    c = a[0].shape[0]
    row, col = _iotas(c)
    pairs = lambda bits: (
        (jnp.right_shift(row, bits) == jnp.right_shift(col, bits) + 1)
        & (jnp.right_shift(row, bits + 1) == jnp.right_shift(col, bits + 1))
    )
    eye = jnp.where(row == col, 1.0, 0.0)
    x = [eye - jnp.where(pairs(0), u, 0.0) for u in a]
    bits = 1
    while (1 << bits) < c:
        joins = pairs(bits)
        x_lo = _each(lo, x)
        rx = _each(lambda u, v: lo(_dot_nn(lo(jnp.where(joins, u, 0.0)), v)), a, x_lo)
        x = _each(lambda u, u_lo, v: u - _dot_nn(u_lo, v), x, x_lo, rx)
        bits += 1
    return x


def _chunks(q_ref, k_ref, g_ref, bc_ref, br_ref, d: int, sub: int, lo):
    """What each head's chunk needs before the state, a list a name over
    the program's heads: the operands in float32, the cumulative decay,
    the raw product K K^T, X = (I + A)^-1, T, M and the decays."""
    f32 = jnp.float32
    size = q_ref.shape[1]
    cols = [slice(j * d, (j + 1) * d) for j in range(q_ref.shape[2] // d)]
    row, col = _iotas(size)
    ones = jnp.where(row >= col, 1.0, 0.0).astype(jnp.bfloat16)
    cum = _running(g_ref[0], _dot_nn, ones)
    c = dict(
        cols=cols, ones=ones,
        q=[q_ref[0, :, at].astype(f32) for at in cols],
        k=[k_ref[0, :, at].astype(f32) for at in cols],
        g=[cum[:, at] for at in cols],
        b_col=[bc_ref[0, 0, 0, :, j:j + 1] for j in range(len(cols))],
        b_row=[br_ref[0, 0, 0, j:j + 1, :] for j in range(len(cols))],
    )
    raws = _each(
        lambda qj, kj, gj: _tri_products(qj, kj, gj, sub, lo),
        c["q"], c["k"], c["g"],
    )
    c["raw_k"] = [raw_k for raw_k, _ in raws]
    c["x"] = _inverses(_each(
        lambda raw_k, b: jnp.where(row > col, raw_k * b, 0.0),
        c["raw_k"], c["b_col"],
    ), lo)
    c["t"] = _each(lambda x, b: lo(x * b), c["x"], c["b_row"])
    c["m"] = [lo(jnp.where(row >= col, raw_q, 0.0)) for _, raw_q in raws]
    c["eg"] = _each(jnp.exp, c["g"])
    c["last"] = [gj[size - 1:, :] for gj in c["g"]]
    c["out"] = _each(lambda last, gj: jnp.exp(last - gj), c["last"], c["g"])
    c["qd"] = _each(jnp.multiply, c["q"], c["eg"])
    c["kd"] = _each(jnp.multiply, c["k"], c["eg"])
    c["kr"] = _each(jnp.multiply, c["k"], c["out"])
    return c


def _fwd_kernel(
    q_ref, k_ref, v_ref, g_ref, bc_ref, br_ref, o_ref, st_ref, state,
    *, d: int, sub: int,
):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    lo = lambda u: u.astype(q_ref.dtype)
    c = _chunks(q_ref, k_ref, g_ref, bc_ref, br_ref, d, sub, lo)
    cols = c["cols"]
    old = [state[:, at] for at in cols]          # S^T [d_v, d_k] float32
    sb = _each(lo, old)
    r = _each(
        lambda at, kd, s: lo(v_ref[0, :, at].astype(jnp.float32) - _dot_nt(lo(kd), s)),
        cols, c["kd"], sb,
    )
    vn = _each(lambda t, u: lo(_dot_nn(t, u)), c["t"], r)
    o = _each(
        lambda qd, s, m, u: _dot_nt(lo(qd), s) + _dot_nn(m, u),
        c["qd"], sb, c["m"], vn,
    )
    new = _each(
        lambda s, last, u, kr: s * jnp.exp(last) + _dot_tn(u, lo(kr)),
        old, c["last"], vn, c["kr"],
    )
    for at, s, u, n in zip(cols, sb, o, new):
        st_ref[0, 0, :, at] = s
        o_ref[0, :, at] = u.astype(o_ref.dtype)
        state[:, at] = n


def _bwd_kernel(
    q_ref, k_ref, v_ref, g_ref, bc_ref, br_ref, st_ref, do_ref,
    dq_ref, dk_ref, dv_ref, dg_ref, dbc_ref, dbr_ref, dstate,
    *, d: int, sub: int,
):
    size = q_ref.shape[1]
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _init():
        dstate[...] = jnp.zeros_like(dstate)

    lo = lambda u: u.astype(q_ref.dtype)
    c = _chunks(q_ref, k_ref, g_ref, bc_ref, br_ref, d, sub, lo)
    cols = c["cols"]
    heads = len(cols)
    row, col = _iotas(size)
    at_row = lax.broadcasted_iota(jnp.int32, (size, 1), 0)
    qd_lo, kd_lo, kr_lo = (_each(lo, c[name]) for name in ("qd", "kd", "kr"))
    sb = [st_ref[0, 0, :, at] for at in cols]    # S^T entering the chunk
    do = [do_ref[0, :, at] for at in cols]
    d_new = [dstate[:, at] for at in cols]       # dS'^T [d_v, d_k] float32
    d_new_lo = _each(lo, d_new)
    # Vn again, from the saved state.
    r = _each(
        lambda at, kd, s: lo(v_ref[0, :, at].astype(f32) - _dot_nt(kd, s)),
        cols, kd_lo, sb,
    )
    vn = _each(lambda t, u: lo(_dot_nn(t, u)), c["t"], r)
    dvn = _each(
        lambda m, u, kr, dn: lo(_dot_tn(m, u) + _dot_nt(kr, dn)),
        c["m"], do, kr_lo, d_new_lo,
    )
    dm = _each(_dot_nt, do, vn)
    dt = _each(_dot_nt, dvn, r)
    dr = _each(_dot_tn, c["t"], dvn)             # = dV [C, d_v]
    dr_lo = _each(lo, dr)
    dqd = _each(_dot_nn, do, sb)
    dkd = _each(lambda u, s: -_dot_nn(u, s), dr_lo, sb)
    dkr = _each(_dot_nn, vn, d_new_lo)
    through = _each(jnp.multiply, dkr, c["kr"])
    keep = _each(jnp.exp, c["last"])
    tail = _each(
        lambda th, kp, dn, s: jnp.sum(th, axis=0, keepdims=True) + kp * jnp.sum(
            dn * s.astype(f32), axis=0, keepdims=True
        ),
        through, keep, d_new, sb,
    )
    dq = _each(jnp.multiply, dqd, c["eg"])
    dk = _each(
        lambda u, eg, w, out: u * eg + w * out, dkd, c["eg"], dkr, c["out"]
    )
    dg = _each(
        lambda u, qd, w, kd, th, tl: u * qd + w * kd - th + jnp.where(
            at_row == size - 1, tl, 0.0
        ),
        dqd, c["qd"], dkd, c["kd"], through, tail,
    )
    d_old = _each(
        lambda dn, kp, u, qd, w, kd: dn * kp + _dot_tn(u, qd) - _dot_tn(w, kd),
        d_new, keep, do, qd_lo, dr_lo, kd_lo,
    )
    # Through T = X diag(b), X = (I + A)^-1, A = diag(b) strict(K K^T)
    # and M = lower(Q K^T), to the sub-block rows' operands.
    x_lo = _each(lo, c["x"])
    dbr = _each(lambda u, x: jnp.sum(u * x, axis=0, keepdims=True), dt, c["x"])
    da = _each(
        lambda x, u, b: lo(_dot_tn(x, lo(u * b))), x_lo, dt, c["b_row"]
    )
    da = _each(lambda u, x: jnp.where(row > col, -_dot_nt(u, x), 0.0), da, x_lo)
    dbc = _each(
        lambda u, raw: jnp.sum(u * raw, axis=1, keepdims=True), da, c["raw_k"]
    )
    draw_k = _each(lambda u, b: lo(u * b), da, c["b_col"])
    draw_q = [lo(jnp.where(row >= col, u, 0.0)) for u in dm]
    dq_rows, dk_rows, dg_rows = ([[] for _ in cols] for _ in range(3))
    for i in range(size // sub):
        rows = slice(i * sub, (i + 1) * sub)
        blocks = _each(
            lambda qj, kj, gj: _sub_block(qj, kj, gj, i, sub, lo),
            c["q"], c["k"], c["g"],
        )
        left, right = [b[0] for b in blocks], [b[1] for b in blocks]
        dleft_k = _each(lambda u, v: _dot_nn(u[rows], v), draw_k, right)
        dleft_q = _each(lambda u, v: _dot_nn(u[rows], v), draw_q, right)
        dright = _each(
            lambda u, w, v: _dot_tn(
                jnp.concatenate([u[rows], w[rows]], axis=0), v
            ),
            draw_k, draw_q, left,
        )
        for j, (_, _, own, to_ref) in enumerate(blocks):
            # Times the operands as the products read them: what a pair
            # adds at its row and takes at its column then cancels exactly
            # past the pair.
            as_read = left[j].astype(f32)
            at_left = dleft_k[j] * as_read[:sub] + dleft_q[j] * as_read[sub:]
            at_right = dright[j] * right[j].astype(f32)
            dq_rows[j].append(dleft_q[j] * own)
            dk_rows[j].append(dleft_k[j] * own)
            dg_rows[j].append(at_left)
            dk[j] = dk[j] + dright[j] * to_ref
            to_reference = jnp.sum(at_right, axis=0, keepdims=True) - jnp.sum(
                at_left, axis=0, keepdims=True
            )
            dg[j] = dg[j] - at_right + jnp.where(
                at_row == i * sub, to_reference, 0.0
            )
    for j, at in enumerate(cols):
        dstate[:, at] = d_old[j]
        dv_ref[0, :, at] = dr[j].astype(dv_ref.dtype)
        dq_ref[0, :, at] = (
            dq[j] + jnp.concatenate(dq_rows[j], axis=0)
        ).astype(dq_ref.dtype)
        dk_ref[0, :, at] = (
            dk[j] + jnp.concatenate(dk_rows[j], axis=0)
        ).astype(dk_ref.dtype)
        # The gradient of the cumulative decay, summed back from each row
        # to the chunk's last: g's own.
        dg_ref[0, :, at] = _running(
            dg[j] + jnp.concatenate(dg_rows[j], axis=0), _dot_tn, c["ones"]
        )
    dbc_ref[0, 0, 0] = jnp.concatenate(dbc, axis=1)
    dbr_ref[0, 0, 0] = jnp.concatenate(dbr, axis=0)


def _specs(c: int, hs: int, d: int, chunk_of):
    """Block specs by kind of operand; `chunk_of(i)` is the chunk a grid
    step holds (the backward walks them from the last)."""
    return {
        "x": pl.BlockSpec((1, c, hs * d), lambda b, h, i: (b, chunk_of(i), h)),
        "col": pl.BlockSpec(
            (1, 1, 1, c, hs), lambda b, h, i: (b, h, chunk_of(i), 0, 0)
        ),
        "row": pl.BlockSpec(
            (1, 1, 1, hs, c), lambda b, h, i: (b, h, chunk_of(i), 0, 0)
        ),
        "state": pl.BlockSpec(
            (1, 1, d, hs * d), lambda b, h, i: (b, chunk_of(i), 0, h)
        ),
    }


_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)


@functools.partial(jax.jit, static_argnames=("d", "chunk", "interpret"))
def _kda_fwd(q, k, v, g, bc, br, *, d, chunk, interpret):
    bsz, s, width = q.shape
    steps, hs = bc.shape[1], bc.shape[4]
    nc = s // chunk
    spec = _specs(chunk, hs, d, lambda i: i)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, d=d, sub=_sub(chunk)),
        grid=(bsz, steps, nc),
        in_specs=[spec[x] for x in ("x", "x", "x", "x", "col", "row")],
        out_specs=[spec["x"], spec["state"]],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bsz, nc, d, width), q.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((d, hs * d), jnp.float32)],
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name="kda_fwd",
    )(q, k, v, g, bc, br)


@functools.partial(jax.jit, static_argnames=("d", "chunk", "interpret"))
def _kda_bwd(q, k, v, g, bc, br, states, do, *, d, chunk, interpret):
    bsz, s, width = q.shape
    steps, hs = bc.shape[1], bc.shape[4]
    nc = s // chunk
    spec = _specs(chunk, hs, d, lambda i: nc - 1 - i)
    like = lambda u: jax.ShapeDtypeStruct(u.shape, u.dtype)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, d=d, sub=_sub(chunk)),
        grid=(bsz, steps, nc),
        in_specs=[spec[x] for x in (
            "x", "x", "x", "x", "col", "row", "state", "x"
        )],
        out_specs=[spec[x] for x in ("x", "x", "x", "x", "col", "row")],
        out_shape=[like(q), like(k), like(v), like(g), like(bc), like(br)],
        scratch_shapes=[pltpu.VMEM((d, hs * d), jnp.float32)],
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name="kda_bwd",
    )(q, k, v, g, bc, br, states, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _kda_core(q, k, v, g, bc, br, d, chunk, interpret):
    """(o, states): o in q's layout, `states` [B, chunks, d, H·d] the
    transposed state entering each chunk. `states` carries no cotangent
    path. `bc` and `br` are b twice, time along sublanes and along lanes
    (`ops/ssd.py` does the same with its steps): two arguments with a
    gradient each, which XLA adds through the transposes outside."""
    return _kda_vjp_fwd(q, k, v, g, bc, br, d, chunk, interpret)[0]


def _kda_vjp_fwd(q, k, v, g, bc, br, d, chunk, interpret):
    o, states = _kda_fwd(
        q, k, v, g, bc, br, d=d, chunk=chunk, interpret=interpret
    )
    # Named values that are both outputs and residuals: a policy that
    # saves them drops the forward kernel from the backward (flash.py).
    o = checkpoint_name(o, CHECKPOINT_OUT_NAME)
    states = checkpoint_name(states, CHECKPOINT_STATES_NAME)
    return (o, states), (q, k, v, g, bc, br, states)


def _kda_vjp_bwd(d, chunk, interpret, residuals, cts):
    do, _ = cts
    return tuple(_kda_bwd(
        *residuals, do, d=d, chunk=chunk, interpret=interpret
    ))


_kda_core.defvjp(_kda_vjp_fwd, _kda_vjp_bwd)


def _kda_kernels(q, k, v, g, b, *, chunk, interpret):
    """The kernels over a sequence of whole chunks; b's two layouts are
    XLA's."""
    bsz, s, width = q.shape
    h = b.shape[-1]
    hs = _heads_a_step(h)
    by_step = b.reshape(bsz, s // chunk, chunk, h // hs, hs)
    o, _ = _kda_core(
        q, k, v, g, by_step.transpose(0, 3, 1, 2, 4),
        by_step.transpose(0, 3, 1, 4, 2), width // h, chunk, interpret,
    )
    return o


# -- the decay a head: the second form ------------------------------------------
#
# `g` [B, S, H]: ONE decay a value head and token, `a_t I` in place of
# `diag(a_t)`, over q and k of H_k heads, value head h reading key head
# h // (H / H_k) (Gated DeltaNet). A scalar factors out of every product of
# the chunk: with `G` the running sum of g and `D[t, s] = e^(G_t - G_s)` for
# t >= s (never a positive exponent, so no sub-block and no clip),
#
#     A = strict_lower(diag(b) (K K^T) * D)        M = lower((Q K^T) * D)
#     Vn = T (V - e^G * (K S))                     O = e^G * (Q S) + M Vn
#     S' = e^(G_C) S + K^T (e^(G_C - G) * Vn)
#
# with `*` a row's scale. K K^T and Q K^T are raw products of a KEY head,
# shared by the value heads that read it, and q, k enter every product as
# they are stored: neither g widened to a head's lanes nor q, k repeated
# is ever formed.


def _group_heads(heads: int, key_heads: int) -> tuple[int, int]:
    """(key heads, value heads) a grid step of the head form holds: whole
    groups, `_HEADS_A_STEP` value heads where they divide."""
    r = heads // key_heads
    ks = math.gcd(key_heads, max(_HEADS_A_STEP // r, 1))
    return ks, ks * r


def gdn_chunked(q, k, v, g, b, *, chunk: int):
    """The decay-a-head form in plain `jax.numpy`: q, k [B, S, H_k·d], v
    [B, S, H·d], g (log decay, negative) and b [B, S, H] float32; S a
    multiple of `chunk`. Matmul operands in q's dtype, sums and the state
    float32, as the kernels. Returns o [B, S, H·d] float32."""
    bsz, s, width = v.shape
    h = b.shape[-1]
    d = width // h
    hk = k.shape[-1] // d
    r, nc = h // hk, s // chunk
    f32 = jnp.float32
    lo = lambda u: u.astype(q.dtype)
    dot = functools.partial(jnp.einsum, preferred_element_type=f32)
    grouped = lambda u: u.reshape(bsz, nc, chunk, hk, r)
    cum = jnp.cumsum(grouped(g), axis=2)                 # [B, nc, C, Hk, r]
    beta = grouped(b)
    qc, kc = (u.reshape(bsz, nc, chunk, hk, d) for u in (q, k))
    t_at, s_at = _iotas(chunk)
    over = lambda u: jnp.moveaxis(u, 2, -1)              # time last
    gap = over(cum)[..., :, None] - over(cum)[..., None, :]
    decay = jnp.where(
        t_at >= s_at, jnp.exp(jnp.where(t_at >= s_at, gap, 0.0)), 0.0
    )                                                    # [B, nc, Hk, r, C, C]
    kk = dot("bcthd,bcshd->bchts", kc, kc)[:, :, :, None]
    qk = dot("bcthd,bcshd->bchts", qc, kc)[:, :, :, None]
    a = jnp.where(t_at > s_at, kk * decay * over(beta)[..., :, None], 0.0)
    t = lo(_unit_lower_inverse(a) * over(beta)[..., None, :])
    m = lo(qk * decay)
    by_chunk = lambda u: jnp.moveaxis(u, 1, 0)

    def step(state, xs):  # state [B, Hk, r, d_k, d_v] float32
        qj, kj, vj, gj, tj, mj = xs
        eg = jnp.exp(gj)[..., None]                      # [B, C, Hk, r, 1]
        last = gj[:, -1:]
        sb = lo(state)
        rest = lo(vj.astype(f32) - eg * dot("bthk,bhrkv->bthrv", kj, sb))
        vn = lo(dot("bhrts,bshrv->bthrv", tj, rest))
        o = eg * dot("bthk,bhrkv->bthrv", qj, sb) + dot(
            "bhrts,bshrv->bthrv", mj, vn
        )
        out = lo(vn.astype(f32) * jnp.exp(last - gj)[..., None])
        state = state * jnp.exp(last)[:, 0, :, :, None, None] + dot(
            "bthk,bthrv->bhrkv", kj, out
        )
        return state, o

    _, o = lax.scan(
        step, jnp.zeros((bsz, hk, r, d, d), f32),
        (by_chunk(qc), by_chunk(kc),
         by_chunk(v.reshape(bsz, nc, chunk, hk, r, d)), by_chunk(cum),
         by_chunk(t), by_chunk(m)),
    )
    return jnp.moveaxis(o, 0, 1).reshape(bsz, s, width)


def _to_row(col, eye):
    """A column [C, 1] as the row [1, C], exactly (no transpose unit: a
    select against the diagonal and a sum along the sublanes)."""
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _to_col(row, eye):
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _gdn_chunks(q_ref, k_ref, cols_ref, d: int, r: int, lo):
    """What each head's chunk needs before the state. Lists over the
    program's KEY heads (`q`, `k`, the raw products) and over its value
    heads (the rest); `key[j]` is value head j's key head."""
    size = q_ref.shape[1]
    ks = q_ref.shape[2] // d
    hs = ks * r
    row, col = _iotas(size)
    eye = row == col
    ones = jnp.where(row >= col, 1.0, 0.0).astype(jnp.bfloat16)
    cols = cols_ref[0, 0, 0]                             # [C, 2 hs]: g, then b
    cum = _running(cols[:, :hs], _dot_nn, ones)
    c = dict(
        ones=ones, eye=eye, key=[j // r for j in range(hs)],
        kcols=[slice(i * d, (i + 1) * d) for i in range(ks)],
        vcols=[slice(j * d, (j + 1) * d) for j in range(hs)],
    )
    c["q"] = [q_ref[0, :, at] for at in c["kcols"]]
    c["k"] = [k_ref[0, :, at] for at in c["kcols"]]
    c["raw_k"] = _each(_dot_nt, c["k"], c["k"])
    c["raw_q"] = _each(_dot_nt, c["q"], c["k"])
    c["g"] = [cum[:, j:j + 1] for j in range(hs)]
    c["b_col"] = [cols[:, hs + j:hs + j + 1] for j in range(hs)]
    c["b_row"] = [_to_row(u, eye) for u in c["b_col"]]
    c["decay"] = [
        jnp.where(row >= col, jnp.exp(jnp.minimum(gj - _to_row(gj, eye), 0.0)), 0.0)
        for gj in c["g"]
    ]
    c["a"] = [
        jnp.where(row > col, c["raw_k"][i] * dj * bj, 0.0)
        for i, dj, bj in zip(c["key"], c["decay"], c["b_col"])
    ]
    c["x"] = _inverses(c["a"], lo)
    c["t"] = _each(lambda x, bj: lo(x * bj), c["x"], c["b_row"])
    c["m32"] = [c["raw_q"][i] * dj for i, dj in zip(c["key"], c["decay"])]
    c["m"] = _each(lo, c["m32"])
    c["eg"] = _each(jnp.exp, c["g"])
    c["last"] = [gj[size - 1:, :] for gj in c["g"]]
    c["out"] = _each(lambda last, gj: jnp.exp(last - gj), c["last"], c["g"])
    c["keep"] = _each(jnp.exp, c["last"])
    # the same along a state's lanes ([1, 1] broadcasts one way at a time)
    c["keep_row"] = [
        jnp.exp(jnp.broadcast_to(gj, (size, d))[size - 1:, :]) for gj in c["g"]
    ]
    return c


def _gdn_fwd_kernel(
    q_ref, k_ref, v_ref, cols_ref, o_ref, st_ref, state, *, d: int, r: int,
):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    f32 = jnp.float32
    lo = lambda u: u.astype(q_ref.dtype)
    c = _gdn_chunks(q_ref, k_ref, cols_ref, d, r, lo)
    cols = c["vcols"]
    q = [c["q"][i] for i in c["key"]]
    k = [c["k"][i] for i in c["key"]]
    old = [state[:, at] for at in cols]          # S^T [d_v, d_k] float32
    sb = _each(lo, old)
    rest = _each(
        lambda at, kj, s, eg: lo(v_ref[0, :, at].astype(f32) - eg * _dot_nt(kj, s)),
        cols, k, sb, c["eg"],
    )
    vn = _each(lambda t, u: lo(_dot_nn(t, u)), c["t"], rest)
    o = _each(
        lambda qj, s, eg, m, u: eg * _dot_nt(qj, s) + _dot_nn(m, u),
        q, sb, c["eg"], c["m"], vn,
    )
    new = _each(
        lambda s, keep, u, out, kj: s * keep + _dot_tn(lo(u.astype(f32) * out), kj),
        old, c["keep_row"], vn, c["out"], k,
    )
    for at, s, u, n in zip(cols, sb, o, new):
        st_ref[0, 0, :, at] = s
        o_ref[0, :, at] = u.astype(o_ref.dtype)
        state[:, at] = n


def _gdn_bwd_kernel(
    q_ref, k_ref, v_ref, cols_ref, st_ref, do_ref,
    dq_ref, dk_ref, dv_ref, dcols_ref, dstate, *, d: int, r: int,
):
    size = q_ref.shape[1]
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _init():
        dstate[...] = jnp.zeros_like(dstate)

    lo = lambda u: u.astype(q_ref.dtype)
    c = _gdn_chunks(q_ref, k_ref, cols_ref, d, r, lo)
    cols, eye, key = c["vcols"], c["eye"], c["key"]
    row, col = _iotas(size)
    at_last = lax.broadcasted_iota(jnp.int32, (size, 1), 0) == size - 1
    q = [c["q"][i] for i in key]
    k = [c["k"][i] for i in key]
    sb = [st_ref[0, 0, :, at] for at in cols]    # S^T entering the chunk
    do = [do_ref[0, :, at] for at in cols]
    d_new = [dstate[:, at] for at in cols]       # dS'^T [d_v, d_k] float32
    d_new_lo = _each(lo, d_new)
    # Vn again, from the saved state.
    ks_ = _each(_dot_nt, k, sb)
    qs_ = _each(_dot_nt, q, sb)
    rest = _each(
        lambda at, u, eg: lo(v_ref[0, :, at].astype(f32) - eg * u),
        cols, ks_, c["eg"],
    )
    vn = _each(lambda t, u: lo(_dot_nn(t, u)), c["t"], rest)
    vo = _each(lambda u, out: lo(u.astype(f32) * out), vn, c["out"])
    dvo = _each(_dot_nt, k, d_new_lo)
    dvn = _each(
        lambda m, u, out, w: lo(_dot_tn(m, u) + out * w),
        c["m"], do, c["out"], dvo,
    )
    dm = [jnp.where(row >= col, _dot_nt(u, w), 0.0) for u, w in zip(do, vn)]
    dt = _each(_dot_nt, dvn, rest)
    dr = _each(_dot_tn, c["t"], dvn)             # = dV [C, d_v]
    do32 = [u.astype(f32) for u in do]
    dqs = _each(lambda eg, u: lo(eg * u), c["eg"], do32)
    dks = _each(lambda eg, u: lo(-eg * u), c["eg"], dr)
    # The running decay's gradient a row: through e^G, e^(G_C - G), e^(G_C).
    through = _each(
        lambda w, u, out: jnp.sum(w * u.astype(f32), axis=1, keepdims=True) * out,
        dvo, vn, c["out"],
    )
    dg = _each(
        lambda eg, u, a, w, b_, th: eg * jnp.sum(
            w * b_ - u * a, axis=1, keepdims=True
        ) - th,
        c["eg"], dr, ks_, do32, qs_, through,
    )
    tail = _each(
        lambda th, keep, dn, s: jnp.sum(th, axis=0, keepdims=True) + keep * jnp.sum(
            jnp.sum(dn * s.astype(f32), axis=1, keepdims=True), axis=0,
            keepdims=True,
        ),
        through, c["keep"], d_new, sb,
    )
    d_old = _each(
        lambda dn, keep, u, qj, w, kj: dn * keep + _dot_tn(u, qj) + _dot_tn(w, kj),
        d_new, c["keep_row"], dqs, q, dks, k,
    )
    # Through T = X diag(b), X = (I + A)^-1, A = diag(b) strict(K K^T * D)
    # and M = lower(Q K^T * D). What a pair (t, s) adds to G at its row it
    # takes at its column, from ONE array: the two cancel past the pair.
    x_lo = _each(lo, c["x"])
    db_row = _each(lambda u, x: jnp.sum(u * x, axis=0, keepdims=True), dt, c["x"])
    da = _each(lambda x, u, bj: lo(_dot_tn(x, lo(u * bj))), x_lo, dt, c["b_row"])
    da = _each(lambda u, x: jnp.where(row > col, -_dot_nt(u, x), 0.0), da, x_lo)
    pairs = _each(
        lambda u, a, w, m: u * a + w * m, da, c["a"], dm, c["m32"]
    )
    dg = _each(
        lambda u, p: u + jnp.sum(p, axis=1, keepdims=True) - _to_col(
            jnp.sum(p, axis=0, keepdims=True), eye
        ),
        dg, pairs,
    )
    db = [
        jnp.sum(u * c["raw_k"][i] * dj, axis=1, keepdims=True) + _to_col(w, eye)
        for u, i, dj, w in zip(da, key, c["decay"], db_row)
    ]
    draw_k = _each(lambda u, dj, bj: u * dj * bj, da, c["decay"], c["b_col"])
    draw_q = _each(jnp.multiply, dm, c["decay"])
    dq_heads = _each(_dot_nn, dqs, sb)
    dk_heads = _each(
        lambda u, s, w, dn: _dot_nn(u, s) + _dot_nn(w, dn), dks, sb, vo, d_new_lo
    )
    for i, at in enumerate(c["kcols"]):
        mine = [j for j, of in enumerate(key) if of == i]
        of_k = lo(sum(draw_k[j] for j in mine))
        of_q = lo(sum(draw_q[j] for j in mine))
        dq_ref[0, :, at] = (
            sum(dq_heads[j] for j in mine) + _dot_nn(of_q, c["k"][i])
        ).astype(dq_ref.dtype)
        dk_ref[0, :, at] = (
            sum(dk_heads[j] for j in mine) + _dot_tn(of_q, c["q"][i])
            + _dot_nn(of_k, c["k"][i]) + _dot_tn(of_k, c["k"][i])
        ).astype(dk_ref.dtype)
    for j, at in enumerate(cols):
        dstate[:, at] = d_old[j]
        dv_ref[0, :, at] = dr[j].astype(dv_ref.dtype)
    # The gradient of the running sum, summed back from each row to the
    # chunk's last: g's own.
    dg = jnp.concatenate(
        [jnp.where(at_last, u + tl, u) for u, tl in zip(dg, tail)], axis=1
    )
    dcols_ref[0, 0, 0] = jnp.concatenate(
        [_running(dg, _dot_tn, c["ones"]), *db], axis=1
    )


def _gdn_specs(c: int, ks: int, hs: int, d: int, chunk_of):
    return {
        "key": pl.BlockSpec((1, c, ks * d), lambda b, h, i: (b, chunk_of(i), h)),
        "value": pl.BlockSpec((1, c, hs * d), lambda b, h, i: (b, chunk_of(i), h)),
        "col": pl.BlockSpec(
            (1, 1, 1, c, 2 * hs), lambda b, h, i: (b, h, chunk_of(i), 0, 0)
        ),
        "state": pl.BlockSpec(
            (1, 1, d, hs * d), lambda b, h, i: (b, chunk_of(i), 0, h)
        ),
    }


@functools.partial(jax.jit, static_argnames=("d", "chunk", "interpret"))
def _gdn_fwd(q, k, v, cols, *, d, chunk, interpret):
    bsz, s, width = v.shape
    steps, hs = cols.shape[1], cols.shape[4] // 2
    ks = q.shape[2] // (steps * d)
    nc = s // chunk
    spec = _gdn_specs(chunk, ks, hs, d, lambda i: i)
    return pl.pallas_call(
        functools.partial(_gdn_fwd_kernel, d=d, r=hs // ks),
        grid=(bsz, steps, nc),
        in_specs=[spec[x] for x in ("key", "key", "value", "col")],
        out_specs=[spec["value"], spec["state"]],
        out_shape=[
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct((bsz, nc, d, width), q.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((d, hs * d), jnp.float32)],
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name="gdn_fwd",
    )(q, k, v, cols)


@functools.partial(jax.jit, static_argnames=("d", "chunk", "interpret"))
def _gdn_bwd(q, k, v, cols, states, do, *, d, chunk, interpret):
    bsz, s, _ = v.shape
    steps, hs = cols.shape[1], cols.shape[4] // 2
    ks = q.shape[2] // (steps * d)
    nc = s // chunk
    spec = _gdn_specs(chunk, ks, hs, d, lambda i: nc - 1 - i)
    like = lambda u: jax.ShapeDtypeStruct(u.shape, u.dtype)
    return pl.pallas_call(
        functools.partial(_gdn_bwd_kernel, d=d, r=hs // ks),
        grid=(bsz, steps, nc),
        in_specs=[spec[x] for x in (
            "key", "key", "value", "col", "state", "value"
        )],
        out_specs=[spec[x] for x in ("key", "key", "value", "col")],
        out_shape=[like(q), like(k), like(v), like(cols)],
        scratch_shapes=[pltpu.VMEM((d, hs * d), jnp.float32)],
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name="gdn_bwd",
    )(q, k, v, cols, states, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _gdn_core(q, k, v, cols, d, chunk, interpret):
    """(o, states) of the head form, as `_kda_core`'s: `cols` [B, steps,
    chunks, C, 2·hs] float32 is g then b of a grid step's value heads, time
    along the sublanes (one layout: the kernels turn a column to a row
    themselves)."""
    return _gdn_vjp_fwd(q, k, v, cols, d, chunk, interpret)[0]


def _gdn_vjp_fwd(q, k, v, cols, d, chunk, interpret):
    o, states = _gdn_fwd(q, k, v, cols, d=d, chunk=chunk, interpret=interpret)
    o = checkpoint_name(o, CHECKPOINT_OUT_NAME)
    states = checkpoint_name(states, CHECKPOINT_STATES_NAME)
    return (o, states), (q, k, v, cols, states)


def _gdn_vjp_bwd(d, chunk, interpret, residuals, cts):
    do, _ = cts
    return tuple(_gdn_bwd(
        *residuals, do, d=d, chunk=chunk, interpret=interpret
    ))


_gdn_core.defvjp(_gdn_vjp_fwd, _gdn_vjp_bwd)


def _gdn_kernels(q, k, v, g, b, *, chunk, interpret):
    """The head form's kernels over a sequence of whole chunks; g and b
    side by side a grid step's heads are XLA's (two [tokens, H] arrays)."""
    bsz, s, width = v.shape
    h = b.shape[-1]
    d = width // h
    _, hs = _group_heads(h, k.shape[-1] // d)
    by_step = lambda u: u.reshape(bsz, s // chunk, chunk, h // hs, hs)
    cols = jnp.concatenate([by_step(g), by_step(b)], axis=-1)
    o, _ = _gdn_core(
        q, k, v, cols.transpose(0, 3, 1, 2, 4), d, chunk, interpret
    )
    return o


def _kda_plain(q, k, v, g, b, *, chunk):
    """`kda_chunked`, o named as the kernels'."""
    return checkpoint_name(
        kda_chunked(q, k, v, g, b, chunk=chunk).astype(q.dtype),
        CHECKPOINT_OUT_NAME,
    )


def _head_form_kernels(v, mesh: Mesh | None) -> bool:
    """Whether the head form runs `gdn_fwd` / `gdn_bwd`: where kernels
    compile, over bfloat16 operands, on one device (no `shard_map` of it
    is written: a mesh of several devices runs `gdn_chunked`)."""
    return (
        kernels_compiled() and v.dtype == jnp.bfloat16
        and (mesh is None or mesh.size == 1)
    )


def kda_scan(
    q, k, v, g, b, *, chunk: int, mesh: Mesh | None = None,
    interpret: bool | None = None,
):
    """o [B, S, H·d] of the gated delta rule over v [B, S, H·d] and b
    [B, S, H] (float32, in (0, 1)), in the form the decay's shape says:

    - g [B, S, H·d] (float32 log decay a key CHANNEL, negative), q and k
      [B, S, H·d]: the channel form. The kernels `kda_fwd` / `kda_bwd`
      wherever they compile (or under the interpreter when `interpret` is
      True), `kda_chunked` on the CPU. A Pallas call does not partition
      itself under `jit`, so with a mesh the kernels run in `shard_map`
      over the batch axes and, where `tp` divides the heads, whole heads
      over `tp`.
    - g [B, S, H] (ONE decay a value head), q and k [B, S, H_k·d] with H_k
      dividing H, value head h reading key head h // (H / H_k): the head
      form. The kernels `gdn_fwd` / `gdn_bwd` where `_head_form_kernels`
      says so (or under the interpreter), `gdn_chunked` on the CPU, in
      float32 and on a mesh of several devices.

    Either way o and the chunk states carry `CHECKPOINT_OUT_NAME` /
    `CHECKPOINT_STATES_NAME` where the kernels ran."""
    h = b.shape[-1]
    by_head = g.shape == b.shape
    d = max(v.shape[-1] // h, 1)
    if by_head:  # q and k of key heads that divide the value heads
        fits = (
            q.shape[:2] == v.shape[:2] and q.shape[-1] % d == 0
            and h % max(q.shape[-1] // d, 1) == 0
        )
    else:
        fits = q.shape == v.shape == g.shape
    if v.shape[-1] % h or q.shape != k.shape or not fits:
        raise ValueError(
            f"q {q.shape}, k {k.shape}, v {v.shape}, g {g.shape} over {h} "
            "heads: one shape, its last axis whole heads (a decay a channel), "
            "or g as b and q, k of key heads that divide the value heads (a "
            "decay a head)"
        )
    g, b = g.astype(jnp.float32), b.astype(jnp.float32)
    s = q.shape[1]
    pad = -s % chunk
    if pad:
        # g = 0 and b = 0 past the end: the state neither decays nor is
        # corrected nor takes input.
        grow = lambda u: jnp.pad(u, ((0, 0), (0, pad), (0, 0)))
        q, k, v, g, b = grow(q), grow(k), grow(v), grow(g), grow(b)
    if by_head:
        if interpret is None and not _head_form_kernels(v, mesh):
            o = checkpoint_name(
                gdn_chunked(q, k, v, g, b, chunk=chunk).astype(v.dtype),
                CHECKPOINT_OUT_NAME,
            )
        else:
            o = _gdn_kernels(
                q, k, v, g, b, chunk=chunk, interpret=bool(interpret)
            )
    elif interpret is None and not kernels_compiled():
        o = _kda_plain(q, k, v, g, b, chunk=chunk)
    elif mesh is None:
        o = _kda_kernels(q, k, v, g, b, chunk=chunk, interpret=bool(interpret))
    else:
        rows = batch_axes(mesh)
        bsz = math.prod(mesh.shape[ax] for ax in rows)
        tp = mesh.shape.get("tp", 1)
        if q.shape[0] % bsz or h % tp:
            raise ValueError(
                f"the delta rule on mesh {dict(mesh.shape)} needs batch "
                f"{q.shape[0]} to divide over dp·fsdp and {h} heads over tp"
            )
        wide = P(rows, None, "tp" if tp > 1 else None)
        o = jax.shard_map(
            functools.partial(
                _kda_kernels, chunk=chunk, interpret=bool(interpret)
            ),
            mesh=mesh, in_specs=(wide,) * 5, out_specs=wide, check_vma=False,
        )(q, k, v, g, b)
    return o[:, :s] if pad else o
