"""The expert layer's router as one op: its float32 product, and the chosen
scores taken and spread without a gather or a scatter (Pallas TPU).

What `models/transformer.ExpertLayer` runs under the scope `moe.route`
between a layer's normed input x [B, S, d] and the k weights a token
hands its experts, with the ids `expert` [B, S, k] chosen in between
(forced, or `top_k` of the scores: the choice is the model's):

    logits = x W_r              float32, every bit of both operands
    p      = softmax(logits)    over all N experts, or sigmoid(logits)
    chosen = p[expert]          k a token, all different
    gate   = scaling * chosen / (sum of the chosen + 1e-20)

**The product** (`exact_dot`) is `precision=HIGHEST` over `x.astype(
float32)`, on both sides, as it was. `HIGHEST` splits a float32 operand
in three bfloat16 pieces and runs the six passes of the MXU whose partial
products carry bits; of a bfloat16 x the second and third piece are zero,
and XLA's TPU compiler, which fuses the widening into the product, already
leaves their passes out: at [16,384, 2,048] x [2,048, 512] on the v5e the
forward takes 0.549 ms and the weight's gradient 0.542, three times one
bfloat16 pass (0.183), where x's gradient, float32 on both sides, takes
1.156, six (PERF.md §6, PR 47). The same three passes written out (W
split by `ops/streams.split3` along the output's columns, ONE bfloat16
matmul `[T, d] x [d, 3N]`, the thirds added; the cotangent split for the
weight's gradient) were measured beside it and are SLOWER, 0.82 and 0.71
ms: the thirds' sum and the cotangent's split are passes of their own
over `[T, 3N]`. So the op states the product once, for the one-matmul
routers and the router MLP's first product alike, and
`router_schedule()["product_passes"]` says what the chip runs of it: 3 /
3 / 6 for a bfloat16 x, 6 / 6 / 6 for a float32 one. No operand is
rounded; plain XLA, on a mesh too.

**The scores, the chosen ones and the weights** (`route_weights`).
`jnp.take_along_axis(p, expert)` gathers `T x k` single elements and its
transpose scatter-adds as many: 7.15 and 4.64 ns an element on the v5e,
1.9 ms a layer at 16,384 x 10 (PERF.md §6, PR 46) for what a compare and
a select over a tile in VMEM do at the vector unit's rate. Two kernels:

- `route_weights_fwd` reads a block of the logits `[rows, N]` and the
  block's ids, turns 128 tokens' logits at a time so that the experts lie
  along the sublanes and the tokens along the lanes (a token's few
  numbers are then rows `[1, 128]`, and a sum over experts is the vector
  unit's adds, not a reduction across lanes), picks each of the k chosen
  logits by a compare with the experts' numbers, a select and a sum,
  forms their scores (softmax: the max and the sum of the exponentials
  over all N; sigmoid: of the k chosen alone) and writes the chosen scores
  and the weights.
- `route_weights_bwd` reads the ids, the chosen scores and the weights'
  cotangent (softmax: the logits too, to form p again) and WRITES the
  logits' cotangent `[rows, N]` densely, a select a chosen slot: no
  scatter-add and no zero-filled buffer. (sigmoid: `dp p (1 - p)` is
  non-zero at the chosen alone, so it reads no logits at all.)

A token's k numbers travel between XLA and the kernels as `[B, S / 128,
k, 128]`, the tokens in the lanes (a trailing axis of k pads a tile 12
times). What the backward reads is the logits and the ids, which the
caller names, and the chosen scores; `name=` puts a `checkpoint_name` on
those and on the weights, so a policy that keeps the name runs no kernel
again. The calls' names do not start with `moe_`: `moe_time_pct.train`
sums the expert kernels.

`route_weights` is the one entry and decides which form runs, from what
the program can see (`kernels_apply`): the pair where kernels compile, on
one device, over whole blocks of 128 rows, N whole lane tiles and k > 1;
anywhere else `route_weights_plain`, today's `take_along_axis`, which the
CPU, a mesh of several devices, N = 64 (xing) and one expert a token run
and the tests hold the kernels to. `router_schedule()` says which, and
what each costs.
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
from jax.sharding import Mesh

from kubeflow_tpu.ops import flash

_LANES = flash._LANES
# The most rows of a block: [512, 512] float32 is 1 MiB, in VMEM twice over
# forward and four times backward.
_ROWS = 512
# What the chosen's sum is kept off zero by (a sigmoid router's scores may
# all underflow): the published routers' own constant.
_TINY = 1e-20

SCORES = {
    "softmax": functools.partial(jax.nn.softmax, axis=-1),
    "sigmoid": jax.nn.sigmoid,
}


# -- the product ---------------------------------------------------------------


def exact_dot(x, w):
    """`x @ w` in float32 with every bit of both operands, x [..., d] in
    the model's dtype and w [d, N] float32: `HIGHEST` over the widened x,
    of which the chip's compiler runs the passes that carry bits (module
    docstring)."""
    return jnp.dot(
        x.astype(jnp.float32), w, precision=lax.Precision.HIGHEST
    )


# -- the plain form ------------------------------------------------------------


def _weights(chosen, scaling: float):
    return scaling * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + _TINY)


def route_weights_plain(
    logits, expert, scoring: str, scaling: float = 1.0, name: str | None = None,
):
    """`route_weights` as XLA's passes: the scores over all N, a gather of
    the chosen, their normalisation."""
    chosen = jnp.take_along_axis(SCORES[scoring](logits), expert, axis=-1)
    # The gather is what a backward would form again (1.8 ms a layer at 22
    # of 512, PERF.md §6 PR 38): its result is named too.
    if name is not None:
        chosen = checkpoint_name(chosen, name)
    return _weights(chosen, scaling)


# -- which form runs -----------------------------------------------------------


def _fit(logits, expert) -> bool:
    """The shapes the kernels take: float32 logits [B, S, N] of whole
    blocks of 128 rows and whole lane tiles of experts, ids [B, S, k] of
    more than one expert a token (one is a vector of ids, the `[N]` path
    of `ops/moe.py`)."""
    return (
        logits.ndim == 3
        and logits.dtype == jnp.float32
        and expert.shape[:-1] == logits.shape[:-1]
        and expert.ndim == 3
        and expert.shape[-1] > 1
        and logits.shape[1] % _LANES == 0
        and logits.shape[2] % _LANES == 0
    )


def kernels_apply(
    logits, expert, mesh: Mesh | None, compiled: bool | None = None
) -> bool:
    """Whether the weights over logits [B, S, N] and ids [B, S, k] come
    from the kernel pair: shapes the kernels take (`_fit`) on
    `flash.row_blocks_apply`'s terms (kernels compile, one device; its
    dtype is the model's, the logits here are float32)."""
    return _fit(logits, expert) and flash.row_blocks_apply(
        jax.ShapeDtypeStruct(logits.shape, jnp.bfloat16), _LANES, mesh,
        compiled,
    )


def router_schedule(
    tokens: int, d: int, n: int, k: int, x_dtype, *,
    mesh: Mesh | None = None, compiled: bool | None = None,
) -> dict:
    """What a router over `tokens` tokens of width d, N experts and k a
    token runs, from the shapes: static, for tests and for reading a
    trace. `product_passes`: the MXU's bfloat16 passes the chip's compiler
    runs of each of the product's three matmuls, `2 tokens d N` FLOP a
    pass (a widened bfloat16 x has one piece, a float32 operand three);
    `gathered_elements` / `scattered_elements`: single elements the chosen
    scores are moved by, forward / backward; `form`: who takes and spreads
    them (one sequence of `tokens` stands for the batch's)."""
    exact = jnp.dtype(x_dtype) == jnp.bfloat16
    passes = {
        "forward": 3 if exact else 6,
        "weight_gradient": 3 if exact else 6,
        "input_gradient": 6,
    }
    kernels = kernels_apply(
        jax.ShapeDtypeStruct((1, tokens, n), jnp.float32),
        jax.ShapeDtypeStruct((1, tokens, k), jnp.int32), mesh, compiled,
    )
    singly = 0 if kernels else tokens * k
    return {
        "product_passes": passes,
        "form": "kernels" if kernels else "plain",
        "gathered_elements": singly,
        "scattered_elements": singly,
    }


# -- kernels -------------------------------------------------------------------


def _eighths(t, fold=jnp.add):
    """[128, 128] folded down to its eight sublanes, [8, 128]: the vector
    unit's adds (or maxima); across them it is taken once a token."""
    return functools.reduce(fold, [t[r:r + 8] for r in range(0, t.shape[0], 8)])


def _turned(logits_ref, first, n: int):
    """Rows `first`.. + 128 of a block of logits as N / 128 tiles [128
    experts, 128 tokens]."""
    rows = pl.ds(first, _LANES)
    return [
        logits_ref[rows, a * _LANES:(a + 1) * _LANES].T
        for a in range(n // _LANES)
    ]


def _softmax_parts(tiles):
    """(the max, 1 / the sum of the exponentials), [1, 128] each, over all
    of 128 tokens' logits."""
    top = functools.reduce(
        jnp.maximum, [_eighths(t, jnp.maximum) for t in tiles]
    )
    top = jnp.max(top, axis=0, keepdims=True)
    total = sum(_eighths(jnp.exp(t - top)) for t in tiles)
    return top, 1.0 / jnp.sum(total, axis=0, keepdims=True)


def _fwd_kernel(*refs, n, k, scoring, scaling):
    logits_ref, ids_ref, chosen_ref, gate_ref = refs
    expert = lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)

    def body(c, carry):
        ids = ids_ref[c]
        tiles = _turned(logits_ref, pl.multiple_of(c * _LANES, _LANES), n)
        picked = [jnp.zeros((8, _LANES), jnp.float32)] * k
        for a, t in enumerate(tiles):
            here = ids - a * _LANES  # the ids as this tile numbers its experts
            picked = [
                acc + _eighths(jnp.where(expert == here[j:j + 1], t, 0.0))
                for j, acc in enumerate(picked)
            ]
        z = [jnp.sum(acc, axis=0, keepdims=True) for acc in picked]
        if scoring == "softmax":
            top, inv = _softmax_parts(tiles)
            chosen = [jnp.exp(zj - top) * inv for zj in z]
        else:
            chosen = [1.0 / (1.0 + jnp.exp(-zj)) for zj in z]
        scale = scaling / (sum(chosen) + _TINY)
        for j, cj in enumerate(chosen):
            chosen_ref[c, j:j + 1, :] = cj
            gate_ref[c, j:j + 1, :] = cj * scale
        return carry

    lax.fori_loop(0, ids_ref.shape[0], body, None)


def _bwd_kernel(*refs, n, k, scoring, scaling):
    if scoring == "softmax":
        logits_ref, ids_ref, chosen_ref, dgate_ref, out_ref = refs
    else:
        ids_ref, chosen_ref, dgate_ref, out_ref = refs
    expert = lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)

    def body(c, carry):
        first = pl.multiple_of(c * _LANES, _LANES)
        ids, chosen, dgate = ids_ref[c], chosen_ref[c], dgate_ref[c]
        # gate = scaling chosen / s: dchosen = scaling / s (dgate - <dgate,
        # chosen> / s), a token's k numbers as rows [1, 128]
        inv = 1.0 / (jnp.sum(chosen, axis=0, keepdims=True) + _TINY)
        dot = jnp.sum(dgate * chosen, axis=0, keepdims=True) * inv
        dchosen = (scaling * inv) * (dgate - dot)
        if scoring == "softmax":
            # dlogits = p (dp - <dp, p>), dp the chosen's alone
            inner = jnp.sum(dchosen * chosen, axis=0, keepdims=True)
            tiles = _turned(logits_ref, first, n)
            top, z_inv = _softmax_parts(tiles)
            spread = dchosen
        else:
            # dlogits = dp p (1 - p): the chosen's alone, no logits read
            spread = dchosen * chosen * (1.0 - chosen)
        for a in range(n // _LANES):
            here = ids - a * _LANES
            placed = jnp.zeros((_LANES, _LANES), jnp.float32)
            for j in range(k):
                placed = jnp.where(expert == here[j:j + 1], spread[j:j + 1], placed)
            if scoring == "softmax":
                placed = jnp.exp(tiles[a] - top) * z_inv * (placed - inner)
            out_ref[pl.ds(first, _LANES), a * _LANES:(a + 1) * _LANES] = placed.T
        return carry

    lax.fori_loop(0, ids_ref.shape[0], body, None)


def _specs(logits_shape, k: int):
    batch, seq, n = logits_shape
    rows = math.gcd(seq, _ROWS)
    wide = pl.BlockSpec((None, rows, n), lambda b, i: (b, i, 0))
    few = pl.BlockSpec(
        (None, rows // _LANES, k, _LANES), lambda b, i: (b, i, 0, 0)
    )
    return (batch, seq // rows), wide, few


_PARALLEL = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"))
# Under `jit`: a stack's layers trace and lower each body once a program.
_STATIC = ("scoring", "scaling", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _fwd(logits, ids, *, scoring, scaling, interpret):
    """-> (the chosen scores, the weights), as the ids lie."""
    k = ids.shape[2]
    grid, wide, few = _specs(logits.shape, k)
    out = jax.ShapeDtypeStruct(ids.shape, jnp.float32)
    return pl.pallas_call(
        functools.partial(
            _fwd_kernel, n=logits.shape[-1], k=k, scoring=scoring,
            scaling=scaling,
        ),
        grid=grid, in_specs=[wide, few], out_specs=[few, few],
        out_shape=[out, out], compiler_params=_PARALLEL,
        interpret=interpret, name="route_weights_fwd",
    )(logits, ids)


@functools.partial(jax.jit, static_argnames=("n", *_STATIC))
def _bwd(logits, ids, chosen, dgate, *, n, scoring, scaling, interpret):
    """-> the logits' cotangent [B, S, N]; `logits` None under sigmoid."""
    batch, blocks, k, _ = ids.shape
    shape = (batch, blocks * _LANES, n)
    grid, wide, few = _specs(shape, k)
    read = [] if logits is None else [logits]
    return pl.pallas_call(
        functools.partial(
            _bwd_kernel, n=n, k=k, scoring=scoring, scaling=scaling,
        ),
        grid=grid, in_specs=[wide] * len(read) + [few] * 3, out_specs=wide,
        out_shape=jax.ShapeDtypeStruct(shape, jnp.float32),
        compiler_params=_PARALLEL, interpret=interpret,
        name="route_weights_bwd",
    )(*read, ids, chosen, dgate)


def _lanes_last(few):
    """[B, S, k] -> [B, S / 128, k, 128], the tokens in the lanes."""
    batch, seq, k = few.shape
    return few.reshape(batch, seq // _LANES, _LANES, k).swapaxes(2, 3)


def _lanes_first(few):
    """... and back."""
    batch, blocks, k, _ = few.shape
    return few.swapaxes(2, 3).reshape(batch, blocks * _LANES, k)


def route_weights(
    logits, expert, *, scoring: str, scaling: float = 1.0,
    name: str | None = None, mesh: Mesh | None = None,
    interpret: bool | None = None,
):
    """The k weights [..., k] a token hands the experts `expert` [..., k]
    int32 (all different) from its logits [..., N] float32: `scaling *
    p[expert] / (their sum + 1e-20)`, p the `scoring` of the logits,
    "softmax" over all N or "sigmoid". The kernel pair where
    `kernels_apply` says so (or, over shapes they take, whenever `interpret`
    is given: True interprets, False compiles), `route_weights_plain`
    anywhere else.
    `name`: a `checkpoint_name` on what this op forms and its backward
    reads, the chosen scores, and on the weights (the logits and the ids
    are the caller's to name)."""
    if scoring not in SCORES:
        raise ValueError(
            f"the router's scoring {scoring!r}: expected one of {sorted(SCORES)}"
        )
    if interpret is None:
        kernels = kernels_apply(logits, expert, mesh)
    else:
        kernels = _fit(logits, expert)
    if kernels:
        how = _How(
            logits.shape[-1], scoring, float(scaling), name,
            flash._auto_interpret(interpret),
        )
        gate = _lanes_first(_route_weights(logits, _lanes_last(expert), how))
    else:
        gate = route_weights_plain(logits, expert, scoring, scaling, name)
    return gate if name is None else checkpoint_name(gate, name)


class _How(NamedTuple):
    """What the pair is built from: static."""

    n: int
    scoring: str
    scaling: float
    name: str | None
    interpret: bool


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _route_weights(logits, ids, how: _How):
    return _route_weights_fwd(logits, ids, how)[0]


def _route_weights_fwd(logits, ids, how):
    chosen, gate = _fwd(
        logits, ids, scoring=how.scoring, scaling=how.scaling,
        interpret=how.interpret,
    )
    if how.name is not None:
        chosen = checkpoint_name(chosen, how.name)
    # a sigmoid's slope is its chosen scores': it keeps no logits
    return gate, (logits if how.scoring == "softmax" else None, ids, chosen)


def _route_weights_bwd(how, residuals, dgate):
    logits, ids, chosen = residuals
    return _bwd(
        logits, ids, chosen, dgate, n=how.n, scoring=how.scoring,
        scaling=how.scaling, interpret=how.interpret,
    ), None


_route_weights.defvjp(_route_weights_fwd, _route_weights_bwd)
