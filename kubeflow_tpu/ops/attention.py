"""Attention: the model's one entry point (`attend`), the dense reference
and the ring (sequence-parallel) implementation.

Long context is first-class here where the reference had nothing (SURVEY.md
§5 "Long-context / sequence parallelism: Absent"). The design is blockwise
ring attention: the sequence axis is sharded over the mesh's `sp` axis; K/V
chunks rotate around the sp ring via `ppermute` (nearest-neighbor ICI hops)
while each device's Q stays put, and softmax is accumulated online
(flash-attention style running max/sum) so no device ever materializes the
full [S, S] score matrix or the full K/V.

Memory per device: O(S/n · S/n) scores, O(S/n) K/V — sequence length scales
linearly with the sp ring size.
"""

from __future__ import annotations

import functools
import math
import warnings

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from kubeflow_tpu.ops.flash import (
    flash_attention,
    flash_kernel_tileable,
    kernels_compiled,
    ring_flash_attention,
)
from kubeflow_tpu.parallel.collectives import axis_size
from kubeflow_tpu.parallel.sharding import batch_axes


def dense_attention(
    q, k, v, *, causal: bool = True, window: int | None = None,
    q_rope=None, k_rope=None, scale: float | None = None,
):
    """Reference attention. q,k,v: [B, S, H, D] (or [B,S,G,H,D] grouped);
    v may be of another width than q and k. `window` (causal only): a
    query sees the last `window` keys, its own among them, the band mask.
    With `q_rope` [B, S, H, R] and `k_rope` [B, S, R] the scores are
    `q·kᵀ + q_rope·k_ropeᵀ`, the rope key one for all heads (latent
    attention); `scale` is their factor, (D + R)^-1/2 unless given."""
    d = q.shape[-1]
    if q_rope is None:
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    else:
        d += q_rope.shape[-1]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) + jnp.einsum(
            "bqhr,bkr->bhqk", q_rope, k_rope
        )
    scores = scores / math.sqrt(d) if scale is None else scores * scale
    if causal:
        s_q, s_k = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((s_q, s_k), bool), k=s_k - s_q)
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((s_q, s_k), bool), k=s_k - s_q - window)
        scores = jnp.where(mask, scores, -jnp.inf)
    weights = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights.astype(q.dtype), v)


def _ring_body(q, k, v, *, axis: str, causal: bool):
    """Per-shard ring attention. q,k,v: local [B, C, H, D] chunks.

    The ring has a static size, so the loop is unrolled at trace time:
    the step index is static (letting the causal mask specialize per hop)
    and the final hop skips its rotation — n-1 ppermutes, not n.
    """
    n = axis_size(axis)
    my = lax.axis_index(axis)
    b, c, h, d = q.shape
    scale = 1.0 / math.sqrt(d)
    q32 = q.astype(jnp.float32)

    q_pos = my * c + lax.broadcasted_iota(jnp.int32, (c, c), 0)

    o = jnp.zeros((b, c, h, d), jnp.float32)
    m = jnp.full((b, h, c), -jnp.inf, jnp.float32)
    l = jnp.zeros((b, h, c), jnp.float32)
    k_cur, v_cur = k, v
    for i in range(n):
        src = (my - i) % n  # ring position this K/V chunk originated from

        def accumulate(o, m, l, k_blk=k_cur, v_blk=v_cur, src_=src):
            s = jnp.einsum(
                "bqhd,bkhd->bhqk", q32, k_blk.astype(jnp.float32)
            ) * scale
            if causal:
                k_pos = src_ * c + lax.broadcasted_iota(
                    jnp.int32, (c, c), 1
                )
                s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
            m_blk = jnp.max(s, axis=-1)
            m_new = jnp.maximum(m, m_blk)
            # Rows with no unmasked key yet keep m=-inf; exp(-inf - -inf)
            # is nan, so guard the correction factor.
            corr = jnp.where(m == -jnp.inf, 0.0, jnp.exp(m - m_new))
            p = jnp.exp(s - m_new[..., None])
            p = jnp.where(jnp.isfinite(s), p, 0.0)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum(
                "bhqk,bkhd->bqhd", p, v_blk.astype(jnp.float32)
            )
            o_new = o * corr.transpose(0, 2, 1)[..., None] + pv
            return o_new, m_new, l_new

        if causal:
            # A K/V chunk from a LATER ring position is entirely masked
            # for this device's queries — skip both einsums (half the
            # ring's attention FLOPs on average). Devices legitimately
            # diverge here: the cond body has no collectives, the
            # rotation below is unconditional.
            o, m, l = lax.cond(
                src <= my, accumulate, lambda o, m, l: (o, m, l), o, m, l
            )
        else:
            o, m, l = accumulate(o, m, l)
        if i + 1 < n:
            k_cur = _rotate(k_cur, axis, n)
            v_cur = _rotate(v_cur, axis, n)
    l = jnp.where(l == 0.0, 1.0, l)
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def _rotate(x, axis: str, n: int):
    return lax.ppermute(x, axis, perm=[(i, (i + 1) % n) for i in range(n)])


def ring_attention(
    q,
    k,
    v,
    mesh: Mesh,
    *,
    causal: bool = True,
    sp_axis: str = "sp",
    heads_axis: str | None = "tp",
):
    """Sequence-parallel attention over `mesh`'s sp ring.

    q,k,v: global [B, S, H, D]; S must divide by the sp ring size, H by the
    tp size. Falls back to dense attention when the ring is trivial.
    """
    if mesh.shape.get(sp_axis, 1) == 1:
        return dense_attention(q, k, v, causal=causal)

    ring = mesh.shape[sp_axis]
    if q.shape[1] % ring:
        raise ValueError(
            f"ring attention requires the sequence length ({q.shape[1]}) to "
            f"be divisible by the {sp_axis!r} ring size ({ring})"
        )
    bspec = batch_axes(mesh)
    spec = P(bspec, sp_axis, heads_axis, None)
    body = functools.partial(_ring_body, axis=sp_axis, causal=causal)
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)


def latent_form(own: int, rope: int, wide: int) -> str:
    """Which calls run latent attention whose heads have `own` dims of
    their own beside `rope` against the one rope key, over values `wide`
    wide — from the widths alone, so a model can lay its projections out
    for them:

    - "two_part": v as wide as the own part. The `flash_*_mla*` calls,
      which read the rope key once a K block and never repeated a head.
    - "joined": v as wide as a head's two parts together. The ONE-part
      calls at `own + rope` lanes a head, over q's parts side by side and
      k's own part beside the rope key repeated a head (8,192 x 20 x 64
      bfloat16 is 21 MB a pass more than the own parts' 63, where heads of
      192 lanes, no whole tiles, would cost the two-part kernels two
      head-major transposes of 63-84 MB each).
    - "dense": any other width. No kernel has a head of that shape.
    """
    if wide == own:
        return "two_part"
    return "joined" if wide == own + rope else "dense"


def attend(
    q, k, v, *, mesh: Mesh | None, impl: str, window: int | None = None,
    q_rope=None, k_rope=None, scale: float | None = None,
):
    """Causal self-attention of q [B, S, H, D] over k, v [B, S, Hkv, D]
    (H a multiple of Hkv): ring when the mesh's `sp` axis is real, else
    the flash kernels or the dense reference, as `impl` says (`auto`:
    the kernels wherever they compile; `flash` / `dense` force one).
    With `window`, position i sees keys i - window < j <= i: the band
    in the kernels' compact grid, the band mask on the dense path; a
    window that reaches every key is the causal call. The ring refuses
    one: a band crosses few of its hops, and nothing has run that.

    With `q_rope` [B, S, H, R] and `k_rope` [B, S, R] (latent attention)
    the scores have two parts, D dims a head and R against one rope key
    for all heads; `scale` is the scores' factor ((D + R)^-1/2, or D^-1/2
    without a rope part, unless given). The ring, a window and a `tp` axis
    refuse the rope part with their numbers: the one key's gradient is a
    sum over the heads, which nothing has run across shards. The values'
    width picks the calls (`latent_form`): D wide, the two-part kernels;
    D + R wide, the parts are joined here, a head's q as `[q_rope | q]`
    and its k as `[k_rope | k]` with the rope key repeated a head (whose
    gradient the broadcast's transpose sums over the heads), and the
    one-part kernels run at D + R (a model that projects q and k joined
    hands them in as one part and spares the concatenation:
    `models/transformer.Attention._joined_qkv`); any other width runs
    dense, and where the kernels would have run says so.

    The flash kernel is a Pallas call, which does not auto-partition under
    pjit — with a mesh it runs inside shard_map over the batch/tp axes
    (embarrassingly parallel: each shard attends over its own batch rows and
    heads; the sequence axis is unsharded on this path).
    """
    if impl not in ("auto", "flash", "dense"):
        raise ValueError(
            f"unknown attention_impl {impl!r}; expected 'auto', 'flash', "
            "or 'dense'"
        )
    if window is not None and window < 1:
        raise ValueError(
            f"attend: a window of {window} key(s); it counts the query's "
            "own position, so it is at least 1"
        )
    if window is not None and window >= k.shape[1]:
        window = None  # every earlier key: the causal call
    group = q.shape[2] // k.shape[2]
    # Only the flash kernels pick a query head's kv head themselves; the
    # ring and dense paths get K and V repeated over the group.
    repeat = lambda x: x if group == 1 else jnp.repeat(x, group, axis=2)
    two_part = q_rope is not None
    shape = dict(mesh.shape) if mesh is not None else {}
    if two_part and (
        window is not None or shape.get("sp", 1) > 1 or shape.get("tp", 1) > 1
    ):
        raise ValueError(
            f"attend: a rope part of {q_rope.shape[-1]} dims with "
            f"window={window} on a mesh {shape}: two-part scores run the "
            "causal triangle on shards of the batch only"
        )
    if two_part and q.shape[-1] != v.shape[-1]:
        form = latent_form(q.shape[-1], q_rope.shape[-1], v.shape[-1])
        if form == "joined":
            if scale is None:
                scale = 1.0 / math.sqrt(q.shape[-1] + q_rope.shape[-1])
            q = jnp.concatenate([q_rope, q], axis=-1)
            k = jnp.concatenate([
                jnp.broadcast_to(
                    k_rope[:, :, None, :], (*k.shape[:3], k_rope.shape[-1])
                ), k,
            ], axis=-1)
            two_part = False
        elif impl == "flash":
            raise ValueError(
                f"attention_impl='flash': a head of {q.shape[-1]} + "
                f"{q_rope.shape[-1]} dims over values {v.shape[-1]} wide "
                "has no kernel (values as wide as the own part, or as both "
                "parts together)"
            )
        else:
            if impl == "auto" and kernels_compiled():
                warnings.warn(
                    f"attention_impl='auto': a head of {q.shape[-1]} + "
                    f"{q_rope.shape[-1]} dims over values {v.shape[-1]} "
                    "wide has no kernel; running DENSE O(S²) attention "
                    "instead of the flash kernels",
                    RuntimeWarning,
                    stacklevel=2,
                )
            impl = "dense"
    # What the rope part adds to a call: nothing to one without it.
    rope = {} if scale is None else dict(scale=scale)
    if two_part:
        rope.update(q_rope=q_rope, k_rope=k_rope)
    if mesh is not None and mesh.shape.get("sp", 1) > 1:
        # Ring (sequence-parallel) path. Where the kernels compile (any
        # backend but the CPU) and the local chunks are flash-tileable,
        # every ring hop runs the Pallas kernel (ring flash: per-device
        # attention memory O(C·D), not O(C²)) — the long-context
        # composition; otherwise the dense-hop ring.
        if window is not None:
            raise ValueError(
                f"attend: window={window} on a mesh whose 'sp' axis is "
                f"{mesh.shape['sp']}: the ring path has no window"
            )
        chunk = q.shape[1] // mesh.shape["sp"]
        if (
            impl in ("auto", "flash")
            and kernels_compiled()
            and flash_kernel_tileable(chunk)
        ):
            return ring_flash_attention(
                q, repeat(k), repeat(v), mesh, causal=True
            )
        return ring_attention(q, repeat(k), repeat(v), mesh, causal=True)
    use_flash = impl == "flash" or (impl == "auto" and kernels_compiled())
    if use_flash and mesh is not None:
        # The shard_map wrapper needs batch % (dp·fsdp) == 0 and
        # heads % tp == 0 — stricter than pjit auto-partitioning, so the
        # auto path falls back to dense rather than erroring, and says
        # so: O(S²) attention on an accelerator is never silent.
        bsz = math.prod(mesh.shape[a] for a in batch_axes(mesh))
        tp = mesh.shape.get("tp", 1)
        if q.shape[0] % bsz or k.shape[2] % tp:
            if impl == "flash":
                raise ValueError(
                    f"attention_impl='flash' on a mesh requires batch "
                    f"({q.shape[0]}) divisible by dp·fsdp ({bsz}) and heads "
                    f"({k.shape[2]}) divisible by tp ({tp})"
                )
            warnings.warn(
                f"attention_impl='auto': batch ({q.shape[0]}) does not "
                f"divide dp·fsdp ({bsz}) or heads ({k.shape[2]}) do not "
                f"divide tp ({tp}); running DENSE O(S²) attention instead "
                "of the flash kernels",
                RuntimeWarning,
                stacklevel=2,
            )
            use_flash = False
    if not use_flash:
        return dense_attention(
            q, repeat(k), repeat(v), causal=True, window=window, **rope
        )
    if mesh is None:
        return flash_attention(q, k, v, causal=True, window=window, **rope)
    # The shards' boundary is crossed with the heads folded into the last
    # axis, [B, S, H·D], as the projections write q, k and v and as the
    # kernels read them (`ops/flash.py`): a [B, S, H, D] array there is
    # given a layout of its own on the TPU and costs a relayout each way.
    d = q.shape[-1]
    fold = lambda x: x.reshape(*x.shape[:2], -1)
    heads = lambda x, width: x.reshape(*x.shape[:2], -1, width)

    def shard(q, k, v, *pair):
        how = {} if scale is None else dict(scale=scale)
        if pair:  # the rope part: q_rope folded like q, the one key as it is
            how.update(q_rope=heads(pair[0], pair[1].shape[-1]), k_rope=pair[1])
        return fold(flash_attention(
            heads(q, d), heads(k, d), heads(v, d), causal=True, window=window,
            **how,
        ))

    spec = P(
        batch_axes(mesh), None, "tp" if mesh.shape.get("tp", 1) > 1 else None
    )
    pair = (fold(q_rope), k_rope) if two_part else ()
    return jax.shard_map(
        shard,
        mesh=mesh,
        in_specs=(spec,) * (3 + len(pair)),
        out_specs=spec,
        check_vma=False,
    )(fold(q), fold(k), fold(v), *pair).reshape(q.shape)
