"""Decoder-only Transformer LM, TPU-first.

The multi-axis showcase: every parallelism strategy the reference lacked
(SURVEY.md §2.2 — TP, SP, EP all "Absent") is expressed here through logical
axis names and resolved by the rules table:

- attention heads and MLP hidden shard over ``tp`` (XLA inserts the two
  all-reduces per block);
- the sequence axis shards over ``sp`` and attention runs on the ring
  (`kubeflow_tpu.ops.ring_attention`);
- an optional dropless expert layer holds a range of the experts, shards
  them over ``ep`` and sums the shards' partial results;
- a stack may be written as a pattern of single sublayers (a state-space
  mixer, an expert layer or attention alone) in place of attention + MLP
  blocks (`TransformerConfig.layer_pattern`);
- embed-dim weight shards over ``fsdp`` (ZeRO-3).

Blocks are rematerialized (`nn.remat`) — recompute beats HBM traffic on
TPU for long sequences.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kubeflow_tpu.ops import gatenorm
from kubeflow_tpu.ops import router as router_ops
from kubeflow_tpu.ops import shortconv
from kubeflow_tpu.ops import streams as streams_ops
from kubeflow_tpu.ops.attention import attend, latent_form
from kubeflow_tpu.ops.flash import CHECKPOINT_LSE_NAME, CHECKPOINT_OUT_NAME
from kubeflow_tpu.ops.kda import (
    CHECKPOINT_OUT_NAME as KDA_OUT_NAME,
    CHECKPOINT_STATES_NAME as KDA_STATES_NAME,
    kda_scan,
)
from kubeflow_tpu.ops.moe import (
    BLOCK_ROWS, CHECKPOINT_ROWS_NAME as MOE_ROWS_NAME, expert_mlp_on_mesh,
    row_tiles, tiles_in_use,
)
from kubeflow_tpu.ops.rope import rope, yarn_inv_freq
from kubeflow_tpu.ops.ssd import (
    CHECKPOINT_OUT_NAME as SSD_OUT_NAME,
    CHECKPOINT_STATES_NAME as SSD_STATES_NAME,
    ssd_scan,
)
from kubeflow_tpu.parallel.sharding import batch_axes, batch_shard_count
from kubeflow_tpu.utils import memory


@dataclasses.dataclass(frozen=True)
class AttentionKind:
    """One kind of mixer layer of a stack that mixes them
    (`TransformerConfig.attention_kinds`): its query heads (over the
    stack's `n_kv_heads` K/V heads of `head_dim`), its window (None: every
    earlier key; W: the last W keys, the query's own among them), and its
    rope: `rope_theta` over the first `rope_fraction` of a head (0: no
    turn, of the latent pair's rope part either), plain or,
    with `rope_yarn` = (factor, original_max, beta_fast, beta_slow,
    attention_factor), yarn's blended frequencies with cos and sin times
    the attention factor (`ops/rope.yarn_inv_freq`). `mixer` "delta": the
    layer's mixer is no attention but the gated delta rule over `n_heads`
    value heads (`DeltaMixer`), which knows no window and no rope. Such a
    row also says: `key_heads`, the heads of q and k under its value heads
    (None: as many; else a divisor, value head h reading key head
    h // (n_heads / key_heads)); `head_dim`, its heads' width where that is
    not the stack's; `decay`, whether the state forgets by a key CHANNEL
    ("channel": the decay and the norm's gate each through a bottleneck of
    `head_dim`, Kimi's) or by ONE factor a value head ("head": both straight
    from the layer's input, Gated DeltaNet's); and `gate_act`, the
    activation that gates its norm, "sigmoid" or "silu"."""

    n_heads: int
    window: int | None = None
    rope_theta: float = 10_000.0
    rope_fraction: float = 1.0
    rope_yarn: tuple[float, int, float, float, float] | None = None
    mixer: str = "attention"
    key_heads: int | None = None
    head_dim: int | None = None
    decay: str = "channel"
    gate_act: str = "sigmoid"


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32_000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    head_dim: int = 64
    d_ff: int = 2048
    rope_theta: float = 10_000.0
    dtype: Any = jnp.bfloat16
    # What the backward recomputes (`_block_cls`), by what a cell shows:
    #   "none"  — nothing: every activation saved. Fastest wherever it
    #             fits (the benchmark's three dense cells run it).
    #   "flash" — the layer, but for the results its kernels name
    #             (attention's output and log-sum-exp, the scan's output
    #             and chunk states: no forward kernel runs again) and as
    #             many of the results the layers name (`SAVED_RESULTS`:
    #             the router's, the gate's, the residual after attention,
    #             q, k and v, the projections into the mixer, the latent
    #             and the MLPs' hidden axis) as the device's free memory
    #             admits, which `remat_plan` reckons from the shapes and
    #             what the trainer states (`utils/memory.py`). The
    #             benchmark's three sparse cells run it. With nothing
    #             stated (serving, `eval`, the CPU) the kernels' results
    #             alone; under dense attention those are not named: the
    #             same program as "full".
    #   "mlp"   — the MLP half only; attention's residuals stay saved.
    #             No cell: less memory than "none", more than "flash".
    #   "full"  — the whole block from its input. No cell: least memory.
    remat_policy: str = "full"
    # "auto" runs the flash kernels wherever they compile (any backend
    # but the CPU), else the dense reference; "flash" / "dense" force
    # one (`ops/attention.attend`). Tiles and the backward's schedule
    # are `ops/flash.py`'s, from the shapes.
    attention_impl: str = "auto"
    # Grouped K/V heads: query head h attends over kv head
    # h // (n_heads / n_kv_heads). None = as many as query heads.
    n_kv_heads: int | None = None
    # The share of each head's dims (the first ones) that rope turns.
    rope_fraction: float = 1.0
    norm_eps: float = 1e-6
    # Every RMS norm with a learned scale but a recurrent mixer's gated one
    # scales by (1 + w), w zero at the seed, in place of w.
    norm_unit_offset: bool = False
    # A head's RMS norm, with a learned scale, on q and on k before rope.
    qk_norm: bool = False
    # CCA (compressed convolutional attention): q and k are mixed along
    # the sequence by two causal convolutions (kernel sizes below: a
    # depthwise one, then one grouped by head), get the mean of the
    # pre-convolution q and k of their group added, and are L2-normalised
    # (k times a learned temperature); the second half of the kv heads
    # take their value from the token before. The latent is n_heads x
    # head_dim wide, whatever d_model is.
    cca: bool = False
    cca_kernels: tuple[int, int] = (2, 2)
    # Experts: 0 = the dense MLP. Otherwise a dropless layer of experts of
    # width d_ff, `experts_per_token` of them a token. `router` "mlp" is a
    # softmax router MLP whose hidden state is carried from layer to
    # layer (top-1); "sigmoid" scores every expert by a sigmoid of one
    # matmul and weighs the chosen by their scores normalised to
    # `routed_scaling`; "softmax" the same under a softmax over all the
    # experts (and no correction). `experts_held` = (first, count) is the contiguous
    # range this program holds (None = all): the router still routes over
    # `num_experts`, a token's rows to experts held elsewhere add nothing.
    num_experts: int = 0
    experts_held: tuple[int, int] | None = None
    router_hidden: int = 256
    experts_per_token: int = 1
    router: str = "mlp"
    routed_scaling: float = 1.0
    # The experts: "swiglu" is silu(x Wg) * (x Wu) Wd, three matrices;
    # "relu2" is relu(x W1)^2 W2, two.
    mlp_act: str = "swiglu"
    # A latent expert space: the experts work in `moe_latent` dims between
    # a projection into it and one back (0 = in d_model). A shared expert
    # of width `moe_shared_ff` that every token passes (0 = none), under a
    # sigmoid gate a token of its own (`moe_shared_gate`) or unweighted.
    moe_latent: int = 0
    moe_shared_ff: int = 0
    moe_shared_gate: bool = False
    # For measuring an untrained model: every token's experts are drawn
    # evenly at random, by position and layer and the same in every run,
    # in place of the router's choice; the weights stay the router's
    # (`ExpertLayer`).
    router_force_balance: bool = False
    # A stack of single sublayers, one letter a layer, each `x + f(norm(x))`:
    # "M" a state-space mixer, "E" the expert layer, "*" attention. None =
    # `n_layers` blocks of attention then MLP (or experts).
    layer_pattern: str | None = None
    # The head's own matrix where the published model does not tie it to
    # the embedding.
    tie_embeddings: bool = True
    # The state-space mixer (Mamba-2): `ssm_heads` heads of `ssm_head_dim`
    # channels, a state of `ssm_state` a channel, B and C shared by the
    # heads of each of `ssm_groups` groups, a causal depthwise convolution
    # of `ssm_conv` taps, the scan in chunks of `ssm_chunk` positions.
    # `ssm_dt` = (min, max, floor) of the time steps the bias is drawn for.
    # The last three are the delta-rule mixer's too (`DeltaMixer`: a layer
    # whose `AttentionKind.mixer` is "delta"); its heads, their width and
    # the kind of its decay are its row's.
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 128
    ssm_dt: tuple[float, float, float] = (1e-3, 1e-1, 1e-4)
    # Layers that differ, in one stack of `Block`s. `attention_kinds` is
    # the table of attention layers the stack has and `attention_pattern`
    # says which of them each layer is (an index a layer; () with one kind
    # or none: every layer the same). With no table the one kind is the
    # stack's `n_heads`, `rope_theta`, `rope_fraction`, no window.
    attention_kinds: tuple[AttentionKind, ...] = ()
    attention_pattern: tuple[int, ...] = ()
    # A sigmoid gate on attention's output before the output projection,
    # from the layer's normed input. True (or "head"): one a query head and
    # position, by a matrix of its own. "channel": one an output CHANNEL,
    # projected beside q by q's own matrix (a head's q and gate side by
    # side in it).
    attention_gate: bool | str = False
    # Leading layers whose feed-forward half is the dense MLP, of width
    # `dense_d_ff`, in a stack whose other layers have experts.
    dense_layers: int = 0
    dense_d_ff: int = 0
    # Latent attention (DeepSeek-V2's MLA; `kv_latent` > 0 turns it on, in
    # every attention layer of the stack): q comes through a bottleneck of
    # `q_latent` with a norm in it (0: one matrix from the layer's input,
    # no norm), k and v through one of `kv_latent`; a head's q and k are
    # `head_dim` dims of their own plus `rope_head_dim` that rope turns (or,
    # at a `rope_fraction` of 0, does not), and the rope part of
    # k is ONE key for all heads, projected beside the latent; v is
    # `v_head_dim` wide (0: `head_dim`). Which calls run is
    # `ops/attention.latent_form`'s to say, from the three widths: v as
    # wide as the head's own part, the two-part flash calls; as wide as
    # own + rope, the one-part calls at that width over q and k projected
    # with a head's two parts side by side; any other, dense.
    # The layer's `AttentionKind` gives the rope (yarn too) over the whole
    # rope part. `softmax_scale`: the scores' factor where it is not
    # (width of q·k)^-1/2 (yarn's mscale squared times it).
    q_latent: int = 0
    kv_latent: int = 0
    rope_head_dim: int = 0
    v_head_dim: int = 0
    softmax_scale: float | None = None
    # Residual streams (manifold-constrained hyper-connections, arXiv
    # 2512.24880; 0 = the plain `x + f(norm(x))`): a `Block` carries
    # `residual_streams` streams [B, S, n·d] and round each sublayer mixes
    # them by three maps of the normed streams: into the sublayer's input
    # (a sigmoid a stream), its output back onto each stream (twice a
    # sigmoid) and stream to stream, an n x n matrix made doubly
    # stochastic by `hc_iters` Sinkhorn iterations (rows, then columns,
    # each sum + `hc_eps`) of exp of the product clipped to +-`hc_clamp`
    # (`StreamMaps`). Every stream enters as the embedding's row; their
    # sum leaves to the final norm.
    residual_streams: int = 0
    hc_iters: int = 20
    hc_clamp: float = 30.0
    hc_eps: float = 1e-6
    # The multi-token module (DeepSeek-V3's report, arXiv 2412.19437 §2.2;
    # 0 = none, 1 = one further token, the only depth built): outside the
    # stack, ONE further `Block` of the stack's own class with parameters
    # of its own over `[norm(Emb(t_(i+1))) | norm(h_i)] W_eh`, h the main
    # stack's normed output, then a norm and the SHARED head: position i
    # predicts t_(i+2). It is part of the objective, not of the logits: a
    # model given `labels` returns `main + mtp_weight * mtp`
    # (`TransformerLM`), one given none its main logits, the module unrun.
    mtp_layers: int = 0
    mtp_weight: float = 0.3


def _gate_kind(cfg: TransformerConfig) -> str | None:
    """None, "head" or "channel": what `attention_gate` asks for."""
    gate = {False: None, True: "head"}.get(cfg.attention_gate, cfg.attention_gate)
    if gate not in (None, "head", "channel"):
        raise ValueError(
            f"attention_gate {cfg.attention_gate!r}: expected False, True "
            "(or 'head') or 'channel'"
        )
    return gate


def _own_kind(cfg: TransformerConfig) -> AttentionKind:
    """The one attention kind of a stack that names none."""
    return AttentionKind(cfg.n_heads, None, cfg.rope_theta, cfg.rope_fraction)


def _attention_kinds(cfg: TransformerConfig) -> list[AttentionKind]:
    """The attention kind of each of the stack's layers, checked: a
    configuration that cannot be built is refused with its numbers."""
    kinds = cfg.attention_kinds or (_own_kind(cfg),)
    pattern = cfg.attention_pattern or (0,) * cfg.n_layers
    if len(pattern) != cfg.n_layers or not all(
        0 <= k < len(kinds) for k in pattern
    ):
        raise ValueError(
            f"attention_pattern {pattern} names {len(pattern)} layers of "
            f"{len(kinds)} kind(s); n_layers is {cfg.n_layers}"
        )
    if len(kinds) > 1 and not cfg.attention_pattern:
        raise ValueError(
            f"{len(kinds)} attention kinds and no attention_pattern to say "
            "which layer is which"
        )
    hk = cfg.n_kv_heads
    for kind in kinds:
        if kind.n_heads < 1 or (hk and kind.n_heads % hk):
            raise ValueError(
                f"{kind.n_heads} query heads are not a multiple of the "
                f"{hk} K/V heads"
            )
        if kind.window is not None and kind.window < 1:
            raise ValueError(
                f"a window of {kind.window} key(s): it counts the query's "
                "own position, so it is at least 1"
            )
        if kind.mixer not in ("attention", "delta") or (
            kind.mixer == "delta" and (
                kind.window is not None or cfg.layer_pattern is not None
                or cfg.ssm_chunk & (cfg.ssm_chunk - 1)
            )
        ):
            raise ValueError(
                f"mixer {kind.mixer!r} with a window of {kind.window} in "
                f"chunks of {cfg.ssm_chunk}: expected 'attention' or, in a "
                "stack of blocks, 'delta' (no window, chunks a power of two)"
            )
        if kind.mixer == "delta" and (
            kind.decay not in ("channel", "head")
            or kind.gate_act not in gatenorm.ACTIVATIONS
            or (kind.key_heads or kind.n_heads) < 1
            or kind.n_heads % (kind.key_heads or kind.n_heads)
            or (kind.decay == "channel" and kind.key_heads not in (
                None, kind.n_heads
            ))
        ):
            raise ValueError(
                f"a delta row of {kind.n_heads} value heads over "
                f"{kind.key_heads} key heads with a decay a {kind.decay!r} "
                f"gated by {kind.gate_act!r}: the key heads divide the "
                "value heads (and equal them where the decay is a "
                "'channel''s, else a 'head''s), the gate is "
                f"{sorted(gatenorm.ACTIVATIONS)}"
            )
        if kind.mixer == "attention" and (
            kind.key_heads is not None or kind.head_dim is not None
        ):
            raise ValueError(
                f"an attention row with key_heads {kind.key_heads} and a "
                f"head_dim of {kind.head_dim} of its own: both are a delta "
                "row's (attention's are the stack's `n_kv_heads`, `head_dim`)"
            )
    gate = _gate_kind(cfg)
    if gate == "channel" and (cfg.kv_latent or cfg.cca or any(
        k.window is not None for k in kinds if k.mixer == "attention"
    )):
        raise ValueError(
            "attention_gate 'channel' (projected beside q, a head's q and "
            "gate side by side in one matrix) with a window, latent "
            "attention or CCA: it is built for plain full attention"
        )
    if cfg.qk_norm and (cfg.kv_latent or cfg.cca):
        raise ValueError(
            "qk_norm with latent attention or CCA: they norm q and k "
            "themselves"
        )
    if cfg.kv_latent or cfg.q_latent or cfg.rope_head_dim:
        attention = [k for k in kinds if k.mixer == "attention"]
        if (
            min(cfg.kv_latent, cfg.rope_head_dim) < 1 or cfg.q_latent < 0
            or cfg.rope_head_dim % 2 or cfg.cca or (hk and hk != cfg.n_heads)
            or any(
                k.window is not None or k.n_heads != cfg.n_heads
                for k in attention
            )
        ):
            raise ValueError(
                f"latent attention with ranks {cfg.q_latent} / "
                f"{cfg.kv_latent} and a rope part of {cfg.rope_head_dim}: it "
                "takes a K/V rank (a q rank of 0 projects q directly), an "
                "even part beside the head's own (turned or, at a "
                "rope_fraction of 0, not), equal heads in every attention "
                f"row ({cfg.n_heads} over {hk}), no window and no CCA; v "
                f"({cfg.v_head_dim or cfg.head_dim}) as wide as the own "
                f"part ({cfg.head_dim}) or as own + rope runs the flash "
                "kernels, any other width dense"
            )
    if cfg.residual_streams < 0 or cfg.hc_iters < 1 or (
        cfg.residual_streams and cfg.layer_pattern is not None
    ):
        raise ValueError(
            f"{cfg.residual_streams} residual streams mixed by "
            f"{cfg.hc_iters} iterations: streams are carried by a stack of "
            "blocks, not by a layer_pattern"
        )
    if not 0 <= cfg.dense_layers <= cfg.n_layers or (
        cfg.dense_layers and not (cfg.num_experts and cfg.dense_d_ff > 0)
    ):
        raise ValueError(
            f"{cfg.dense_layers} leading dense layer(s) of width "
            f"{cfg.dense_d_ff} in a stack of {cfg.n_layers} with "
            f"{cfg.num_experts} experts"
        )
    if cfg.mtp_layers not in (0, 1) or (cfg.mtp_layers and (
        cfg.residual_streams or cfg.layer_pattern is not None
    )):
        raise ValueError(
            f"{cfg.mtp_layers} multi-token module(s) with "
            f"{cfg.residual_streams} residual streams and a layer_pattern "
            f"of {cfg.layer_pattern!r}: one module of one further block is "
            "built, behind a stack of blocks with one residual stream"
        )
    return [kinds[k] for k in pattern]


def _block_kinds(cfg: TransformerConfig) -> list[AttentionKind]:
    """`_attention_kinds` and, behind them, the kind of the multi-token
    module's block: the last layer's."""
    kinds = _attention_kinds(cfg)
    return kinds + kinds[-1:] * cfg.mtp_layers


# What `remat_policy="flash"` always keeps: the results of the attention,
# scan and delta-rule kernels, which name them themselves (`ops/flash.py`,
# `ops/ssd.py`, `ops/kda.py`), so that no forward kernel runs again in the
# backward, and the expert layer's rows' tokens (`ops/moe.py`: int32, a
# row each, 0.7 MB a layer in the widest cell; the grouped matmuls' and
# the movers' results, the worst-case row buffer, are formed again).
KERNEL_RESULTS = (
    CHECKPOINT_OUT_NAME, CHECKPOINT_LSE_NAME, SSD_OUT_NAME, SSD_STATES_NAME,
    KDA_OUT_NAME, KDA_STATES_NAME, MOE_ROWS_NAME,
)
# Results the layers name where they form them (`checkpoint_name`: a name
# lowers to no operation), for `remat_plan` to keep as many of as the
# device's free memory admits. A name sits on the value the backward
# READS: the product under a sigmoid, not the sigmoid (whose slope reads
# its own result). q, k and v are named as the kernels read them, after
# rope, and not at all under CCA: its mixing's backward reads the
# projections' results, so they are formed again whatever is kept behind
# it (zaya: ~0 ms spared for 0.40 GB; kept where the projections leave
# them, -2.9 %: the [B, S, H, D] mixing pays a relayout of the kept
# [B, S, H·D] arrays, forward and again; my chip runs, PR 38).
ROUTE_RESULT = "moe_route"        # the router's product (or the router MLP's
                                  # state and logits), the chosen experts,
                                  # their scores and their weights
GATE_RESULT = "attn_gate"         # the attention gate's product
RESIDUAL_RESULT = "attn_residual"  # a `Block`'s stream after attention
LATENT_RESULT = "moe_latent_in"   # the experts' input in the latent
IN_PROJ_RESULT = "ssm_in_proj"    # the mixer's in-projection
HIDDEN_RESULT = "mlp_hidden"      # a dense or shared MLP's hidden results
QKV_RESULT = "attn_qkv"           # q, k and v as the kernels read them
                                  # (latent attention: q's and k's two
                                  # parts and v)
CONV_RESULT = "ssm_conv"          # the mixer's convolved x, B and C
ATTN_LATENT_RESULT = "attn_latent"  # latent attention's down-projections
STREAM_OUT_RESULT = "hc_out"      # a sublayer's output under residual streams
HC_RESULT = streams_ops.CHECKPOINT_MAPS_NAME  # the residual streams' maps'
                                  # raw products and the norm's scalar
KDA_PROJ_RESULT = "kda_proj"      # the delta mixer's q, k and v projections
KDA_CONV_RESULT = "kda_conv"      # their convolutions' results, under silu
KDA_DECAY_RESULT = "kda_decay"    # the decay's product, under softplus (a
                                  # decay a head: b's product beside it)
GATED_RESULT = "mixer_gated"      # a recurrent mixer's gated norm's result,
                                  # which its out-projection reads
KDA_GATE_RESULT = "kda_gate"      # the delta mixer's gate's product, under
                                  # sigmoid
# The order they are admitted in: milliseconds of the backward's second
# forward spared a GB held, the small ones first. Timed on the v5e in the
# benchmark's three `flash` cells: `r` of the `[scopes]` line of a traced
# run at the parent commit and with the name kept (my chip runs, PR 36
# and PR 38; PERF.md §5, §6), ms a step spared / GB held:
#   attn_gate      laguna 0.6 / 0.021 (the product; the widening stays)
#   moe_route      nemotron 12.0 / 0.147, laguna 4.0 / 0.084, zaya 2.7 / 0.20
#   attn_residual  laguna 10.5 / 0.25 (`wo`, K = 9,216); zaya 3.4 / 0.54
#   moe_latent_in  nemotron 2.0 / 0.084
#   ssm_in_proj    nemotron 18.0 / 0.77
#   mlp_hidden     nemotron 10.1 / 0.44 (the shared expert), laguna 9.7 / 0.54
#   attn_qkv       laguna 12.5 / 0.82 (rope's turn with it); none under CCA
#   ssm_conv       nemotron 3.6-5.8 / 0.42 (`silu`'s slope still forms the
#                  float32 pre-activation again)
#   hc_maps        xing: the RAW product of the streams with `phi` and the
#                  norm's scalar, (24 + 1) x 4 = 100 bytes a token a
#                  sublayer (PR 39 named the product TIMES the scalar, and
#                  the multiply's backward formed the raw product again:
#                  3.5 ms / 0.008 GB). Where the mixes are the row-block
#                  kernels `hc_pre_fwd` runs again whatever is kept (h is
#                  no candidate: 59 MB a sublayer), so the name spares
#                  only the maps' wait on that pass (PERF.md §6, PR 40)
#   attn_latent    xing: 1,344 dims a token, the cheapest thing of the
#                  layer to keep: 2.2 ms / 0.11 GB (PERF.md §6, PR 39)
#   hc_out         xing: the streams' map of a sublayer's output onto them
#                  has that output in its gradient, so the output
#                  projections (`attn/wo`, `mlp/wo`, the shared expert's, the
#                  experts' combine) run again for it alone: ~22 / 0.59
#   kda_proj       kimi: three products of K = 2,304 onto 4,096 lanes that
#                  the convolutions' weight gradients read whatever else is
#                  kept: see PERF.md §6, PR 41 for the cell's reading with
#                  and without the three `kda_*` names
#   kda_conv       kimi: the convolutions' results as `silu` reads them
#                  (bfloat16), so neither the taps nor the float32 sums run
#                  again; after `kda_proj`, which its input is
#   mixer_gated    a recurrent mixer's gated norm's result, which the
#                  out-projection's weight gradient reads: kept, no
#                  `gatenorm_fwd` runs again: nemotron 3.0 / 0.34, kimi
#                  0.43 / 0.27 (there `jax.checkpoint`'s `reduce_precision`
#                  pass behind the kept kernel result eats most of it;
#                  PERF.md §6, PR 43); behind the convolutions' results,
#                  whose kernels' backward READS them
#   kda_decay      kimi: two thin products (K = 2,304 then 128) spared for a
#                  [tokens, 4,096] array: the dearest to hold, so the last
#   kda_gate       kimi: the gate's two thin products likewise, which the
#                  gated norm's backward reads: 0.77 / 0.27 with the kernels
#                  (`wg_b` and `wg_a` under `recompute`, PERF.md §5, PR 43)
SAVED_RESULTS = (
    HC_RESULT, GATE_RESULT, ROUTE_RESULT, RESIDUAL_RESULT, LATENT_RESULT,
    IN_PROJ_RESULT, KDA_PROJ_RESULT, HIDDEN_RESULT, ATTN_LATENT_RESULT,
    STREAM_OUT_RESULT, QKV_RESULT, CONV_RESULT, KDA_CONV_RESULT,
    GATED_RESULT, KDA_DECAY_RESULT, KDA_GATE_RESULT,
)
# What a delta-rule layer's second forward and backward hold at once beyond
# the named results, in [tokens, heads x head_dim] float32 arrays (a decay a
# head: [tokens, heads]): the log
# decay and its gradient (held so that the peak stays an upper bound of the
# chip's compiler's figure for the kimi cell's step: 13.16 GB against
# 13.60). Where `ops/shortconv.py`'s and `ops/gatenorm.py`'s plain forms run
# in place of their kernels (float32 streams, a mesh of several devices) a
# layer holds ten more than is counted here; no cell runs `flash` there
# (PERF.md §7).
KDA_WORK_ARRAYS = 2


def _block_cls(cfg: "TransformerConfig", cls=None, keep: tuple[str, ...] = ()):
    """Block (or `cls`, a layer of a pattern), wrapped per the config's
    remat policy. Under "flash" the checkpoint saves `KERNEL_RESULTS`,
    so the backward's partial eval dead-codes the forward kernels (any
    other checkpoint whose boundary crosses the flash custom_vjp re-runs
    the forward kernel to rebuild lse), and `keep`, the names of
    `SAVED_RESULTS` the plan admitted (`remat_plan`); everything else of
    the layer is formed again from its input."""
    cls = cls or Block
    if cfg.remat_policy in ("none", "mlp"):
        # No checkpoint round the block ("mlp": `Block` and `Sublayer`
        # remat their MLP themselves), so attention's residuals (q/k/v,
        # o, lse) are saved.
        return cls
    if cfg.remat_policy == "full":
        return nn.remat(cls, static_argnums=())
    if cfg.remat_policy == "flash":
        return nn.remat(
            cls,
            static_argnums=(),
            policy=jax.checkpoint_policies.save_only_these_names(
                *KERNEL_RESULTS, *keep
            ),
        )
    raise ValueError(
        f"unknown remat_policy {cfg.remat_policy!r}; expected 'none', "
        "'full', 'mlp' or 'flash'"
    )


@dataclasses.dataclass(frozen=True)
class RematPlan:
    """What `remat_policy="flash"` keeps beside `KERNEL_RESULTS`
    (`remat_plan`): the names admitted, in `SAVED_RESULTS`' order;
    per-device bytes of every name the configuration forms, over the
    layers that form it (those not admitted were `refused`); the bytes
    held by the admitted; and the step's predicted peak with them (None
    where no limit was known)."""

    names: tuple[str, ...] = ()
    bytes: tuple[tuple[str, int], ...] = ()
    saved_bytes: int = 0
    predicted_peak: int | None = None

    @property
    def refused(self) -> tuple[str, ...]:
        return tuple(n for n, _ in self.bytes if n not in self.names)


def _lanes(width: int) -> int:
    """What a minor dimension of `width` takes on the chip, where an
    array's last axis lies in tiles of 128 lanes."""
    return -(-width // 128) * 128


def _result_bytes(cfg: "TransformerConfig", tokens: int) -> list[dict]:
    """Bytes of each of `SAVED_RESULTS` as a layer forms it, named there
    or not, a dict a layer, for `tokens` tokens a device, every width
    whole: where a `tp` axis splits heads or the MLP's hidden axis a
    device holds less (no cell runs `flash` there: an upper bound,
    PERF.md §7)."""
    act = jnp.dtype(cfg.dtype).itemsize

    def attention(out: dict, kind: AttentionKind):
        hk = cfg.n_kv_heads or kind.n_heads
        out[QKV_RESULT] = tokens * act * (
            _lanes(kind.n_heads * cfg.head_dim) + 2 * _lanes(hk * cfg.head_dim)
        )
        if cfg.kv_latent:  # q's and k's two parts and v; the two latents
            h, r = kind.n_heads, cfg.rope_head_dim
            wide = cfg.v_head_dim or cfg.head_dim
            out[QKV_RESULT] = tokens * act * (
                # a head's two parts side by side in q and in k
                2 * _lanes(h * (cfg.head_dim + r))
                if latent_form(cfg.head_dim, r, wide) == "joined"
                else 2 * _lanes(h * cfg.head_dim) + _lanes(h * r) + _lanes(r)
            ) + tokens * act * _lanes(h * wide)
            out[ATTN_LATENT_RESULT] = tokens * act * (
                (cfg.q_latent and _lanes(cfg.q_latent))
                + _lanes(cfg.kv_latent + r)
            )
        gate = _gate_kind(cfg)
        if gate == "channel":  # the product beside q's, as wide
            out[GATE_RESULT] = tokens * _lanes(kind.n_heads * cfg.head_dim) * act
        elif gate:
            out[GATE_RESULT] = tokens * _lanes(kind.n_heads) * 4

    def mlp(out: dict, d_ff: int):
        arrays = 2 if _mlp_cls(cfg) is SwiGLU else 1
        out[HIDDEN_RESULT] = arrays * tokens * _lanes(d_ff) * act

    def experts(out: dict):
        k = _lanes(cfg.experts_per_token)
        out[ROUTE_RESULT] = tokens * 4 * (
            # the carried state and the logits; or the product, the chosen
            # experts, their scores and their weights
            _lanes(cfg.router_hidden) + _lanes(cfg.num_experts)
            if cfg.router == "mlp" else _lanes(cfg.num_experts) + 3 * k
        )
        if cfg.moe_latent:
            out[LATENT_RESULT] = tokens * _lanes(cfg.moe_latent) * act
        if cfg.moe_shared_ff:
            mlp(out, cfg.moe_shared_ff)

    def delta(out: dict, kind: AttentionKind):
        d = kind.head_dim or cfg.head_dim
        wide = tokens * _lanes(kind.n_heads * d)
        keys = tokens * _lanes((kind.key_heads or kind.n_heads) * d)
        out[KDA_PROJ_RESULT] = out[KDA_CONV_RESULT] = (2 * keys + wide) * act
        out[GATED_RESULT] = out[KDA_GATE_RESULT] = wide * act
        # the log decay in float32: a key channel's, or ONE a value head
        decay = (
            tokens * _lanes(kind.n_heads) if kind.decay == "head" else wide
        ) * 4
        # its product as the layer names it: float32 beside b's a head, the
        # activations' dtype a channel
        out[KDA_DECAY_RESULT] = decay if kind.decay == "head" else wide * act
        # never a candidate (no name): alive where the layer is formed again
        out["kda_work"] = KDA_WORK_ARRAYS * decay

    def mixer(out: dict):
        d_in = cfg.ssm_heads * cfg.ssm_head_dim
        xbc = d_in + 2 * cfg.ssm_groups * cfg.ssm_state
        out[IN_PROJ_RESULT] = tokens * _lanes(d_in + xbc + cfg.ssm_heads) * act
        out[CONV_RESULT] = tokens * _lanes(xbc) * act
        out[GATED_RESULT] = tokens * _lanes(d_in) * act

    layers = []
    if cfg.layer_pattern is None:
        n = cfg.residual_streams
        # the multi-token module's block last: one more expert layer
        for i, kind in enumerate(_block_kinds(cfg)):
            out = {RESIDUAL_RESULT: tokens * _stream_lanes(cfg) * act}
            if n:  # n² + 2n raw products and the norm's scalar a token,
                # float32, round both sublayers
                out[HC_RESULT] = 2 * tokens * (n * n + 2 * n + 1) * 4
                out[STREAM_OUT_RESULT] = 2 * tokens * _lanes(cfg.d_model) * act
            (delta if kind.mixer == "delta" else attention)(out, kind)
            if cfg.num_experts > 0 and i >= cfg.dense_layers:
                experts(out)
            else:
                mlp(out, cfg.dense_d_ff if i < cfg.dense_layers else cfg.d_ff)
            layers.append(out)
    else:
        form = {
            "M": mixer, "E": experts,
            "*": lambda out: attention(out, _own_kind(cfg)),
        }
        for kind in cfg.layer_pattern:
            layers.append({})
            form[kind](layers[-1])
    return layers


# Memory the plan leaves free under the device's limit beyond its own
# prediction (the allocator's fragmentation, another program's code), and
# what it counts for the step's own code (the chip's compiler's figure
# for the three cells: 0.21-0.45 GB, PERF.md §6, PR 38).
REMAT_MARGIN_BYTES = 1 << 29
REMAT_CODE_BYTES = 1 << 28


def _stream_lanes(cfg: "TransformerConfig") -> int:
    """Lanes of what a `Block` takes and returns: the residual stream, or
    `residual_streams` of them side by side."""
    return _lanes(max(cfg.residual_streams, 1) * cfg.d_model)


def _kept_always_bytes(cfg: "TransformerConfig", tokens: int) -> int:
    """Bytes every layer's checkpoint holds whatever the plan:
    its inputs (the residual stream; the router's carried state) and
    `KERNEL_RESULTS` (attention's output and log-sum-exp, the scan's or
    the delta rule's output and chunk states; not the expert layer's rows'
    tokens, 4 bytes a row of its buffer: under a MB a layer)."""
    act = jnp.dtype(cfg.dtype).itemsize
    stream = tokens * _stream_lanes(cfg) * act
    if cfg.num_experts > 0 and cfg.router == "mlp":
        stream += tokens * _lanes(cfg.router_hidden) * 4

    def attention(kind: AttentionKind) -> int:
        if kind.mixer == "delta":  # o, and a [d, H·d] state a chunk
            d = kind.head_dim or cfg.head_dim
            wide = _lanes(kind.n_heads * d) * act
            return (tokens + -(-tokens // cfg.ssm_chunk) * d) * wide
        width = (cfg.kv_latent and cfg.v_head_dim) or cfg.head_dim
        return tokens * (
            _lanes(kind.n_heads * width) * act + _lanes(kind.n_heads) * 4
        )

    if cfg.layer_pattern is None:
        # The module's block beside the layers, and what its projection
        # reads: the two normed halves side by side.
        return sum(stream + attention(k) for k in _block_kinds(cfg)) + (
            cfg.mtp_layers * tokens * _lanes(2 * cfg.d_model) * act
        )
    d_in = cfg.ssm_heads * cfg.ssm_head_dim
    scan = (tokens + -(-tokens // cfg.ssm_chunk) * cfg.ssm_state) * d_in * act
    return sum(
        stream + {"M": scan, "*": attention(_own_kind(cfg))}.get(kind, 0)
        for kind in cfg.layer_pattern
    )


def _peak_bytes(
    cfg: "TransformerConfig", tokens: int, layers: list[dict],
    stated: memory.StepMemory, names: tuple[str, ...] = (),
) -> int:
    """Per-device bytes of a step that keeps `names`, at its fullest: an
    UPPER bound of the chip's compiler's `memory_analysis()` (arguments,
    temporaries and code) in the benchmark's four `flash` cells, by
    0.2-1.1 GB, and of the stream family's cuts to two and three layers
    (PERF.md §6, PR 38 and PR 39). Both moments hold the state, the code,
    one worst-case row buffer of the expert layer at its widest and what
    the widest layer forms again (kept or not, a result is alive there
    once; with residual streams, the streams in float32 for the mixes and
    their float32 cotangent). The larger of the two. The top layer's
    backward: what every checkpoint holds, every result kept, and the
    loss's float32 logits and their cotangent, which may not be freed
    yet. The bottom layer's: every gradient (as if no update were fused
    into a gradient's matmul) and the results the other layers kept (as
    if none were freed yet)."""
    act = jnp.dtype(cfg.dtype).itemsize
    logits = tokens * _lanes(cfg.vocab_size) * 4
    rows = 0
    if cfg.num_experts > 0:
        _, held = cfg.experts_held or (0, cfg.num_experts)
        rows = (
            row_tiles(tokens, cfg.experts_per_token, held) * BLOCK_ROWS
            * max(cfg.moe_latent or cfg.d_model, cfg.d_ff) * act
        )
    widest = max(layers, key=lambda layer: sum(layer.values()), default={})
    again = sum(widest.values())
    if cfg.residual_streams:
        again += 2 * tokens * _stream_lanes(cfg) * 4
    kept = lambda layer: sum(layer.get(name, 0) for name in names)
    kept_all = sum(kept(layer) for layer in layers)
    # a multi-token module's logits and their cotangent beside the main's
    at_top = _kept_always_bytes(cfg, tokens) + kept_all + (
        2 * (1 + cfg.mtp_layers) * logits
    )
    at_bottom = stated.grad_bytes + kept_all - kept(widest)
    return (
        stated.state_bytes + REMAT_CODE_BYTES + rows + again
        + max(at_top, at_bottom)
    )


def remat_plan(
    cfg: "TransformerConfig",
    tokens: int,
    stated: memory.StepMemory | None,
) -> RematPlan:
    """What a step over `tokens` tokens a device keeps of `SAVED_RESULTS`
    under `remat_policy="flash"`, beside `KERNEL_RESULTS`: each name in
    its order that, with the names admitted so far, still leaves the
    predicted peak (`_peak_bytes`) `REMAT_MARGIN_BYTES` under the
    device's limit; a name that does not fit is refused and the next one
    tried.
    Arithmetic over shapes and constants: the same plan on every trace.
    Nothing stated or no limit known (the CPU; a model applied outside a
    trainer): no name, the policy as it was."""
    if cfg.remat_policy != "flash" or stated is None or stated.limit_bytes is None:
        return RematPlan()
    layers = _result_bytes(cfg, tokens)
    costs = {
        name: cost for name in SAVED_RESULTS
        # Under CCA q, k and v carry no name (`Attention`): no candidate.
        if not (cfg.cca and name == QKV_RESULT)
        and (cost := sum(layer.get(name, 0) for layer in layers))
    }
    peak = lambda names: _peak_bytes(cfg, tokens, layers, stated, tuple(names))
    names = []
    for name in costs:
        if peak([*names, name]) <= stated.limit_bytes - REMAT_MARGIN_BYTES:
            names.append(name)
    return RematPlan(
        tuple(names), tuple(costs.items()), sum(costs[n] for n in names),
        peak(names),
    )


def _dot_folded(x, kernel, dimension_numbers, precision=None):
    """`DenseGeneral`'s contraction as one plain matmul: the contracted
    axes (x's last, the kernel's first) folded into one and the kernel's
    feature axes into one. A projection onto heads, [E] x [E, H, D],
    leaves as [..., H·D], and one from heads, [H, D] x [H, D, E], reads
    its [..., H, D] argument as [..., H·D]: the kernels keep the shapes
    (and the sharding names) they are stored under, and no
    [B, S, H, D] array is formed on the way to or from the attention
    kernels, which read [B, S, H·D] (`ops/flash.py`)."""
    n = len(dimension_numbers[0][1])
    kernel = kernel.reshape(math.prod(kernel.shape[:n]), -1)
    x = x.reshape(*x.shape[: x.ndim - n], kernel.shape[0])
    return jnp.dot(x, kernel, precision=precision)


def _dense(features, names, name=None, dtype=jnp.bfloat16, axis=-1):
    return nn.DenseGeneral(
        features,
        axis=axis,
        use_bias=False,
        dtype=dtype,
        param_dtype=jnp.float32,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.variance_scaling(1.0, "fan_in", "normal"), names
        ),
        dot_general=_dot_folded,
        name=name,
    )


def lm_head(x, embed, *, dtype):
    """Output head over `embed` [V, d] (the tied embedding, or the head's
    own matrix): bf16 operands, f32 accumulation, stated
    explicitly rather than via an f32×f32 einsum. XLA's
    allow_excess_precision can demote the latter to the same MXU path
    (measured neutral on v5e with that flag set), but the flag is
    environment-dependent — don't leave ~11% of the model's FLOPs
    relying on it. ONE definition shared by the flat model, the
    pipelined logits path, and the pipelined last-stage loss — the
    three must stay numerically identical (the grad-parity tests pin
    it), so the contract lives in exactly one place."""
    return jnp.einsum(
        "bsd,vd->bsv",
        x.astype(dtype),
        embed.astype(dtype),
        preferred_element_type=jnp.float32,
    )


def rms_norm(x, scale, *, dtype, eps: float = 1e-6):
    """Module-free RMSNorm — the math `RMSNorm` wraps, shared with the
    pipelined loss path (which applies the final norm from a raw param
    value inside `spmd_pipeline`'s per-microbatch objective)."""
    x32 = x.astype(jnp.float32)
    norm = x32 * jax.lax.rsqrt(
        jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps
    )
    return (norm * scale).astype(dtype)


def _scale_init(offset: bool):
    """A norm's learned w at the seed: zero where it scales by (1 + w)."""
    return nn.initializers.zeros if offset else nn.initializers.ones


class RMSNorm(nn.Module):
    """`offset`: the learned w scales by (1 + w) and starts at zero
    (`TransformerConfig.norm_unit_offset`), in place of w from one."""

    dtype: Any = jnp.bfloat16
    eps: float = 1e-6
    offset: bool = False

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale",
            nn.with_logical_partitioning(_scale_init(self.offset), ("norm",)),
            (x.shape[-1],), jnp.float32,
        )
        if self.offset:
            scale = 1.0 + scale
        return rms_norm(x, scale, dtype=self.dtype, eps=self.eps)


def _norm_cls(cfg: TransformerConfig):
    """`RMSNorm` as the stack's configuration has it."""
    return functools.partial(
        RMSNorm, cfg.dtype, cfg.norm_eps, cfg.norm_unit_offset
    )


def _replicated(init, rank: int):
    return nn.with_logical_partitioning(init, (None,) * rank)


class Attention(nn.Module):
    """Causal self-attention from the configuration's numbers: `n_heads`
    query heads over `n_kv_heads` K/V heads in a latent of n_heads x
    head_dim, rope over `rope_fraction` of a head (0: none), and CCA's mixing of q,
    k and v along the sequence when `cca` is on (OLMo's case is all of
    them off and equal heads); with `kv_latent`, latent attention
    (`_latent_qkv`): low-rank q and k/v with a norm between, two-part
    scores over values of their own width. With `qk_norm` a head's RMS
    norm on q and k before rope; with `attention_gate` a sigmoid gate on
    the output, a head's (`_gate`) or a channel's (`_channel_gate`)."""

    config: TransformerConfig
    mesh: Mesh | None = None
    # Which of the stack's attention kinds this layer is (None: the one
    # the configuration's own numbers give).
    kind: AttentionKind | None = None

    def _conv_mix(self, u, name: str):
        """Two causal convolutions along the sequence over u [B, S, H, d]:
        depthwise, then grouped by head. Tap j multiplies the value j
        tokens back. float32 in and out."""
        cfg = self.config
        k0, k1 = cfg.cca_kernels
        heads, d = u.shape[2:]
        w0 = self.param(
            f"conv0_{name}",
            nn.with_logical_partitioning(
                nn.initializers.normal(k0 ** -0.5), (None, "heads", "kv")
            ),
            (k0, heads, d), jnp.float32,
        )
        w1 = self.param(
            f"conv1_{name}",
            nn.with_logical_partitioning(
                nn.initializers.normal((k1 * d) ** -0.5),
                (None, "heads", "kv", None),
            ),
            (k1, heads, d, d), jnp.float32,
        )
        c0 = sum(w0[j] * shortconv.shift(u, j) for j in range(k0))
        c0 = c0.astype(cfg.dtype)
        # The products leave in cfg.dtype like every other projection's
        # (the CPU backend has no batched bf16 dot that leaves in f32).
        return sum(
            jnp.einsum(
                "bshd,hde->bshe", shortconv.shift(c0, j), w1[j].astype(cfg.dtype)
            ).astype(jnp.float32)
            for j in range(k1)
        )

    def _cca_mix(self, q, k, v):
        cfg = self.config
        hk = k.shape[2]
        group = q.shape[2] // hk
        q32, k32 = q.astype(jnp.float32), k.astype(jnp.float32)
        q_mean = q32.reshape(*q.shape[:2], hk, group, -1).mean(axis=3)
        q_mix = self._conv_mix(q32, "q") + 0.5 * (
            q32 + jnp.repeat(k32, group, axis=2)
        )
        k_mix = self._conv_mix(k32, "k") + 0.5 * (q_mean + k32)
        tau = self.param(
            "tau", nn.with_logical_partitioning(nn.initializers.ones, ("heads",)),
            (hk,), jnp.float32,
        )
        # sqrt(d) * x / |x|: the RMS norm without a scale.
        unit = functools.partial(
            rms_norm, scale=1.0, dtype=jnp.float32, eps=cfg.norm_eps
        )
        q = unit(q_mix).astype(cfg.dtype)
        k = (unit(k_mix) * tau[:, None]).astype(cfg.dtype)
        # The second half of the kv heads carry the token before.
        now = hk - hk // 2
        v = jnp.concatenate(
            [v[:, :, :now], shortconv.shift(v[:, :, now:], 1)], axis=2
        )
        return q, k, v

    def _gate(self, x, out, heads: int):
        """`out` [B, S, H, D] times a sigmoid gate a head and position,
        `sigmoid(x wg)` in float32 at full precision. The gate is widened
        to a head's lanes by a matmul with a constant [H, H·D] of ones, so
        the product is taken on [B, S, H·D], where the kernel left its
        output: neither the gate nor `out` is formed as [B, S, H, D]
        (a relayout on the chip: PERF.md §6, PR 31)."""
        cfg = self.config
        wg = self.param(
            "wg",
            nn.with_logical_partitioning(
                nn.initializers.variance_scaling(1.0, "fan_in", "normal"),
                ("embed", "heads"),
            ),
            (x.shape[-1], heads), jnp.float32,
        )
        # The product is what is named (`SAVED_RESULTS`).
        gate = jax.nn.sigmoid(checkpoint_name(jnp.dot(
            x.astype(jnp.float32), wg, precision=jax.lax.Precision.HIGHEST
        ), GATE_RESULT))
        self._sow_gate_mean(gate)
        width = heads * out.shape[-1]
        lanes_of = (
            jax.lax.broadcasted_iota(jnp.int32, (heads, width), 1)
            // out.shape[-1]
            == jax.lax.broadcasted_iota(jnp.int32, (heads, width), 0)
        ).astype(cfg.dtype)
        wide = jnp.dot(gate.astype(cfg.dtype), lanes_of)  # [B, S, H·D]
        return (out.reshape(wide.shape) * wide).reshape(out.shape)

    def _sow_gate_mean(self, gate):
        """A gate stuck at 0 or 1 is the failure this shows; the step sums
        a counter over the attention layers, which sow it."""
        layers = sum(
            kind.mixer == "attention" for kind in _block_kinds(self.config)
        )
        self.sow(
            "counters", "attn_gate_mean", jnp.mean(gate) / layers,
            reduce_fn=lambda _, new: new, init_fn=lambda: 0.0,
        )

    def _channel_gate(self, out, gate):
        """`out` [B, S, H, D] times `sigmoid(gate)`, gate [B, S, H·D] the
        product q's own matrix gave beside q: a gate an output channel, the
        sigmoid and the product in float32 on [B, S, H·D]."""
        # The product is what is named (`SAVED_RESULTS`).
        gate = jax.nn.sigmoid(
            checkpoint_name(gate, GATE_RESULT).astype(jnp.float32)
        )
        self._sow_gate_mean(gate)
        gated = out.reshape(gate.shape).astype(jnp.float32) * gate
        return gated.astype(out.dtype).reshape(out.shape)

    def _head_norm(self, u, name: str, d: int):
        """u [B, S, H·d] under a head's RMS norm with ONE learned scale [d]
        (`norm_unit_offset`: 1 + w), float32, on [B, S, H·d] where the
        projection left it (`shortconv.head_sums`: no [B, S, H, d])."""
        cfg = self.config
        scale = _vector(
            self, name, _scale_init(cfg.norm_unit_offset), d
        ) + float(cfg.norm_unit_offset)
        u32 = u.astype(jnp.float32)
        r = jax.lax.rsqrt(shortconv.head_sums(u32 * u32, d) / d + cfg.norm_eps)
        return (u32 * r * jnp.tile(scale, u.shape[-1] // d)).astype(u.dtype)

    @nn.nowrap
    def _by_parts(self, x, name: str, heads: int, widths: tuple[int, ...]):
        """x [B, S, E] times ONE stored matrix [E, H, sum(widths)] (the
        published layout: a head's parts side by side), as one product a
        part, each [B, S, H·w]: the parts leave the matmuls apart, as the
        kernels read them, where splitting a head's lanes of the joint
        product would be a relayout of the activation (192 = 128 + 64 is
        no whole tile; PERF.md §6, PR 31). The slices are of the weight."""
        cfg = self.config
        kernel = self._heads_kernel(x.shape[-1], name, heads, sum(widths))
        out, at = [], 0
        for w in widths:
            part = kernel[:, :, at:at + w].reshape(x.shape[-1], heads * w)
            out.append(jnp.dot(x, part.astype(cfg.dtype)))
            at += w
        return out

    @nn.nowrap
    def _heads_kernel(self, rows: int, name: str, heads: int, width: int):
        """The stored matrix [rows, H, width] of a projection onto heads
        whose parts lie side by side (`_by_parts`, `_joined_qkv`)."""
        init = nn.initializers.variance_scaling(1.0, "fan_in", "normal")
        return self.param(
            name,
            nn.with_logical_partitioning(
                # drawn as the [E, H·w] matrix it is
                lambda key, shape, dtype: init(
                    key, (shape[0], shape[1] * shape[2]), dtype
                ).reshape(shape),
                (None, "heads", "kv"),
            ),
            (rows, heads, width), jnp.float32,
        )

    @nn.nowrap
    def _joined_qkv(self, c_q, name_q: str, c_kv, k_rope, turn, h: int):
        """Latent attention's operands for the ONE-part kernels at a head
        of r + d lanes, where v is that wide (`latent_form` "joined"):
        a head's `[q_rope | q]` and `[k_rope | k]` side by side, rope part
        first (rope turns a head's FIRST lanes; a dot product does not
        mind the order), each as ONE product [B, S, H·(r + d)] and no
        concatenation of activations by head (192 + 64 lanes a head split
        no tile: a relayout each way). q: the stored matrix with a head's
        columns in that order, then `turn` (rope, or None) over the rope
        lanes. k: `[c_kv | turn(k_rope)] W`, W the own part's columns of
        `wkv_b` under zeros in a head's rope lanes, over an identity that
        copies the turned `k_rope` [B, S, r] into every head's (a product
        by 1.0 is exact, and its transpose sums the one key's gradient over
        the heads). -> q, k [B, S, H·(r + d)], v [B, S, H·Dv]."""
        cfg = self.config
        d, r, kvl = cfg.head_dim, cfg.rope_head_dim, cfg.kv_latent
        if turn is not None:
            with jax.named_scope("rope"):
                k_rope = turn(k_rope)
        wq = self._heads_kernel(c_q.shape[-1], name_q, h, d + r)
        wq = jnp.concatenate([wq[:, :, d:], wq[:, :, :d]], axis=-1)
        q = jnp.dot(c_q, wq.reshape(-1, h * (d + r)).astype(cfg.dtype))
        wkv = self._heads_kernel(kvl, "wkv_b", h, d + cfg.v_head_dim)
        place = jnp.broadcast_to(
            jnp.eye(r, dtype=wkv.dtype)[:, None, :], (r, h, r)
        )
        wk = jnp.concatenate([
            jnp.pad(wkv[:, :, :d], ((0, 0), (0, 0), (r, 0))),
            jnp.pad(place, ((0, 0), (0, 0), (0, d))),
        ], axis=0).reshape(kvl + r, h * (d + r))
        k = jnp.dot(
            jnp.concatenate([c_kv, k_rope], axis=-1), wk.astype(cfg.dtype)
        )
        v = jnp.dot(c_kv, wkv[:, :, d:].reshape(kvl, -1).astype(cfg.dtype))
        if turn is not None:
            with jax.named_scope("rope"):
                # + 0.5: `int(head_dim * fraction)` is r whatever the
                # division rounds to
                q = turn(q, fraction=(r + 0.5) / (d + r), head_dim=d + r)
        return q, k, v

    @nn.nowrap
    def _latent_qkv(self, x, positions, kind: AttentionKind):
        """Latent attention's operands (DeepSeek-V2's equations, whose
        keys the configuration carries): `c_q = norm(x wq_a)`, a head's
        `[q | q_rope] = c_q wq_b` (with no q rank, `x wq` directly);
        `[c | k_rope] = x wkv_a`, `c_kv = norm(c)`, a head's `[k | v] =
        c_kv wkv_b`; rope turns `q_rope` and the ONE `k_rope` all heads
        share, unless the layer's kind has a `rope_fraction` of 0. -> q, k
        [B, S, H·D], v [B, S, H·Dv], q_rope [B, S, H·R], k_rope [B, S, R];
        or, where `ops/attention.latent_form` says the one-part calls run
        ("joined": v as wide as a head's two parts together), q, k
        [B, S, H·(R + D)] with the parts side by side (`_joined_qkv`), v,
        and no pair."""
        cfg = self.config
        h, d, r = kind.n_heads, cfg.head_dim, cfg.rope_head_dim
        joined = latent_form(d, r, cfg.v_head_dim or d) == "joined"
        norm = _norm_cls(cfg)
        # The products are what is named: a norm's backward reads its input.
        named = lambda u: checkpoint_name(u, ATTN_LATENT_RESULT)

        def turn():
            """rope over the pair's second part, as the layer's kind has
            it; None at a `rope_fraction` of 0: not turned."""
            if kind.rope_fraction == 0:
                return None
            how = {}
            if kind.rope_yarn is not None:
                factor, original, fast, slow, attention_factor = kind.rope_yarn
                how = dict(
                    inv_freq=yarn_inv_freq(
                        kind.rope_theta, r, factor=factor,
                        original_max=original, beta_fast=fast, beta_slow=slow,
                    ),
                    scale=attention_factor,
                )
            return functools.partial(
                rope, positions=positions, theta=kind.rope_theta, head_dim=r,
                mesh=self.mesh, **how,
            )

        with jax.named_scope("attn.latent_q"):
            c_q, name_q = x, "wq"
            if cfg.q_latent:
                c_q, name_q = norm(name="q_norm")(named(_dense(
                    cfg.q_latent, ("embed", None), "wq_a", cfg.dtype
                )(x))), "wq_b"
            if not joined:
                q, q_rope = self._by_parts(c_q, name_q, h, (d, r))
        with jax.named_scope("attn.latent_kv"):
            joint = named(_dense(
                cfg.kv_latent + r, ("embed", None), "wkv_a", cfg.dtype
            )(x))
            c_kv = norm(name="kv_norm")(joint[..., : cfg.kv_latent])
            k_rope = joint[..., cfg.kv_latent:]
            if not joined:
                k, v = self._by_parts(
                    c_kv, "wkv_b", h, (d, cfg.v_head_dim or d)
                )
        turn = turn()
        if joined:
            return self._joined_qkv(c_q, name_q, c_kv, k_rope, turn, h)
        if turn is not None:
            with jax.named_scope("rope"):
                q_rope, k_rope = turn(q_rope), turn(k_rope)
        return q, k, v, q_rope, k_rope

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        kind = self.kind or _own_kind(cfg)
        h, d = kind.n_heads, cfg.head_dim
        hk = cfg.n_kv_heads or h
        heads = lambda u, width=d: u.reshape(*u.shape[:2], -1, width)
        if cfg.kv_latent:
            q, k, v, *pair = self._latent_qkv(x, positions, kind)
            q, k, v, *pair = (
                checkpoint_name(u, QKV_RESULT) for u in (q, k, v, *pair)
            )
            with jax.named_scope("attend"):
                out = attend(
                    heads(q, q.shape[-1] // h), heads(k, k.shape[-1] // h),
                    heads(v, v.shape[-1] // h), mesh=self.mesh,
                    impl=cfg.attention_impl, scale=cfg.softmax_scale,
                    # no pair where a head's parts lie side by side in q, k
                    **(dict(
                        q_rope=heads(pair[0], cfg.rope_head_dim),
                        k_rope=pair[1],
                    ) if pair else {}),
                )
            return self._out(x, out, h)
        # q, k and v stay [B, S, H·d] from the projections' matmuls to the
        # attention kernels (`_dot_folded`); only CCA's mixing splits the
        # heads out, and `attend` takes them as a reshape.
        gate = None
        if _gate_kind(cfg) == "channel":  # a head's q and gate, one matrix
            q, gate = self._by_parts(x, "wq", h, (d, d))
        else:
            q = _dense((h, d), ("embed", "heads", "kv"), "wq", cfg.dtype)(x)
        k = _dense((hk, d), ("embed", "heads", "kv"), "wk", cfg.dtype)(x)
        v = _dense((hk, d), ("embed", "heads", "kv"), "wv", cfg.dtype)(x)
        if cfg.cca:
            with jax.named_scope("cca.mix"):
                q, k, v = self._cca_mix(heads(q), heads(k), heads(v))
            q, k, v = (u.reshape(*u.shape[:2], -1) for u in (q, k, v))
        if cfg.qk_norm:
            with jax.named_scope("attn.qk_norm"):
                q = self._head_norm(q, "q_norm", d)
                k = self._head_norm(k, "k_norm", d)
        if kind.rope_fraction > 0:  # 0: no rotation (Nemotron-H's attention)
            how = {}
            if kind.rope_yarn is not None:
                factor, original, fast, slow, attention_factor = kind.rope_yarn
                how = dict(
                    inv_freq=yarn_inv_freq(
                        kind.rope_theta, int(d * kind.rope_fraction),
                        factor=factor, original_max=original, beta_fast=fast,
                        beta_slow=slow,
                    ),
                    scale=attention_factor,
                )
            turn = functools.partial(
                rope, positions=positions, theta=kind.rope_theta,
                fraction=kind.rope_fraction, head_dim=d, mesh=self.mesh,
                **how,
            )
            with jax.named_scope("rope"):
                q, k = turn(q), turn(k)
        if not cfg.cca:  # CCA's backward forms them again whatever is kept
            q, k, v = (checkpoint_name(u, QKV_RESULT) for u in (q, k, v))
        scope = "cca.attend" if cfg.cca else "attend"
        if cfg.attention_kinds:  # a stack that mixes them tells them apart
            scope = "attend.full" if kind.window is None else "attend.window"
        with jax.named_scope(scope):
            out = attend(
                heads(q), heads(k), heads(v), mesh=self.mesh,
                impl=cfg.attention_impl, window=kind.window,
            )
        return self._out(x, out, h, gate)

    @nn.nowrap
    def _out(self, x, out, h: int, gate=None):
        """Attention's output [B, S, H, D] through the gate (where the
        stack has one; `gate`: a channel gate's product) and the output
        projection."""
        cfg = self.config
        if _gate_kind(cfg):
            with jax.named_scope("attn.gate"):
                out = (
                    self._gate(x, out, h) if gate is None
                    else self._channel_gate(out, gate)
                )
        return _dense(
            cfg.d_model, ("heads", "kv", "embed"), "wo", cfg.dtype,
            axis=(-2, -1),
        )(out)


class SwiGLU(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        gate = _dense(cfg.d_ff, ("embed", "mlp"), "wi_gate", cfg.dtype)(x)
        up = _dense(cfg.d_ff, ("embed", "mlp"), "wi_up", cfg.dtype)(x)
        gate, up = (checkpoint_name(u, HIDDEN_RESULT) for u in (gate, up))
        return _dense(cfg.d_model, ("mlp", "embed"), "wo", cfg.dtype)(
            nn.silu(gate) * up
        )


class ReluSquaredMLP(nn.Module):
    """`relu(x W1)^2 W2`, no gate matrix (Nemotron-H's `relu2`)."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        hidden = checkpoint_name(
            _dense(cfg.d_ff, ("embed", "mlp"), "wi", cfg.dtype)(x),
            HIDDEN_RESULT,
        )
        return _dense(cfg.d_model, ("mlp", "embed"), "wo", cfg.dtype)(
            jnp.square(nn.relu(hidden))
        )


def _mlp_cls(cfg: TransformerConfig):
    if cfg.mlp_act not in ("swiglu", "relu2"):
        raise ValueError(
            f"unknown mlp_act {cfg.mlp_act!r}; expected 'swiglu' or 'relu2'"
        )
    return SwiGLU if cfg.mlp_act == "swiglu" else ReluSquaredMLP


FORCED_ROUTING_SEED = 42


def forced_experts(layer: int, seq_len: int, num_experts: int, k: int = 1):
    """[seq_len] int32 (k = 1) or [seq_len, k]: the experts
    `router_force_balance` gives each position in `layer`, the k largest
    of standard normal scores from a fixed key (the argmax at k = 1)."""
    key = jax.random.fold_in(jax.random.PRNGKey(FORCED_ROUTING_SEED), layer)
    scores = jax.random.normal(key, (seq_len, num_experts), jnp.float32)
    if k == 1:
        return jnp.argmax(scores, axis=-1).astype(jnp.int32)
    return jax.lax.top_k(scores, k)[1].astype(jnp.int32)


class ExpertLayer(nn.Module):
    """Dropless layer of experts over the experts held here.

    `config.router` "mlp": the router is an MLP over a hidden state that
    is carried from one layer's router to the next (`router_state` in,
    the new state out): `r = x W_in + carry * r_prev`, `p = softmax(W3
    gelu(W2 gelu(W1 norm(r))))`, in float32 at full matmul precision. Each
    token goes to its best expert e with gate `p[e]`; there is no
    capacity, no dropped token, no balancing bias and no auxiliary loss.

    "sigmoid": `p = sigmoid(x W_r)` in float32 at full precision, the
    `experts_per_token` largest of `p + b` are chosen (`b`, `router_bias`,
    a per-expert correction that gets no gradient: what a balancing rule
    would move, and nothing here does) and weigh `routed_scaling * p_e /
    sum of the chosen p`. "softmax": the same with `p = softmax(x W_r)`
    over all `num_experts` and no correction. No state is carried
    (`router_state` passes through). With `moe_latent` the experts read `x W_latent_in` and their
    sum goes through `W_latent_out`; with `moe_shared_ff` a shared expert
    of that width, which every token passes, is added, under
    `moe_shared_gate` times `sigmoid(x w_s)`, `w_s` [d, 1], float32 at
    full precision (sows `shared_gate_mean`). `mlp_act` says
    which expert: gated, or `relu(.)^2`.

    Nothing therefore keeps an untrained router even, and a dropless
    layer's work follows its tokens. `config.router_force_balance` is for
    measuring such a model at the load a trained one has (what
    Megatron-Core's `--moe-router-force-load-balancing` is for): e is
    then the argmax (the k largest) of standard normal scores drawn for
    (position, expert) from a fixed key and `layer`, the same for every
    row of the batch, in every step and every run, so each expert gets
    about tokens x k / num_experts whatever the weights are; the router
    still runs and still learns through the weights.

    `config.experts_held` = (first, count) says which experts this program
    holds: only their weights exist, the router still scores all
    `num_experts`, and a token routed to an absent expert gets zeros. The
    held experts have the logical axis "expert" (-> `ep`): on a mesh every
    `ep` shard computes what its own experts add for the tokens routed to
    them and the shards' partial results are summed
    (`ops/moe.expert_mlp_on_mesh`: rows ordered by expert in a buffer
    sized for the worst case, of which every pass touches the row tiles
    in use and no more; the form of the expert, `mlp_act`, and
    `experts_per_token` pick the kernels, `ops/moe.py`'s docstring); with
    one shard there is no exchange. Sows, under "counters":
    `moe_tokens_held`, `moe_load_max`, `moe_load_mean`, which count ROWS,
    token-expert pairs routed to a held expert (in all, on the fullest
    held expert, the mean over them): tokens at one expert a token, k
    times as many at most at k; `moe_row_tiles`, the row tiles of that
    buffer, and `moe_row_tiles_live`, those in use (both as one shard
    holding all of `experts_held` would count them): their ratio is the
    share of the buffer the layer's passes touch; and under
    "intermediates" `expert`, each token's choice.
    """

    config: TransformerConfig
    mesh: Mesh | None = None
    layer: int = 0

    def _route(self, x, router_state):
        cfg = self.config
        rh, hi = cfg.router_hidden, jax.lax.Precision.HIGHEST
        mat = lambda name, rows, cols, names: self.param(
            name,
            nn.with_logical_partitioning(
                nn.initializers.variance_scaling(1.0, "fan_in", "normal"),
                names,
            ),
            (rows, cols), jnp.float32,
        )
        carry = self.param(
            "router_carry",
            _replicated(nn.initializers.constant(0.5), 1), (rh,), jnp.float32,
        )
        scale = self.param(
            "router_norm", _replicated(nn.initializers.ones, 1), (rh,),
            jnp.float32,
        )
        r = router_ops.exact_dot(
            x, mat("router_in", x.shape[-1], rh, ("embed", None))
        ) + carry * router_state
        r = checkpoint_name(r, ROUTE_RESULT)
        z = rms_norm(r, scale, dtype=jnp.float32, eps=cfg.norm_eps)
        z = nn.gelu(jnp.dot(z, mat("router_w1", rh, rh, (None, None)),
                            precision=hi))
        z = nn.gelu(jnp.dot(z, mat("router_w2", rh, rh, (None, None)),
                            precision=hi))
        logits = checkpoint_name(jnp.dot(
            z, mat("router_out", rh, cfg.num_experts, (None, None)),
            precision=hi,
        ), ROUTE_RESULT)
        probs = jax.nn.softmax(logits, axis=-1)
        if cfg.router_force_balance:
            expert = forced_experts(self.layer, x.shape[-2], cfg.num_experts)
            expert = jnp.broadcast_to(expert, probs.shape[:-1])
        else:
            expert = jnp.argmax(probs, axis=-1).astype(jnp.int32)
        gate = jnp.take_along_axis(probs, expert[..., None], axis=-1)[..., 0]
        return expert, gate, r

    def _route_sigmoid(self, x):
        """The one-matmul top-k routers, "sigmoid" and "softmax" (the name
        is a frame of the accepted cells' scope tables: kept)."""
        cfg = self.config
        k, n = cfg.experts_per_token, cfg.num_experts
        w = self.param(
            "router",
            nn.with_logical_partitioning(
                nn.initializers.variance_scaling(1.0, "fan_in", "normal"),
                ("embed", None),
            ),
            (x.shape[-1], n), jnp.float32,
        )
        bias = None  # a softmax router has no correction
        if cfg.router == "sigmoid":
            bias = self.param(
                "router_bias", _replicated(nn.initializers.zeros, 1), (n,),
                jnp.float32,
            )
        logits = checkpoint_name(router_ops.exact_dot(x, w), ROUTE_RESULT)
        if cfg.router_force_balance:
            expert = forced_experts(self.layer, x.shape[-2], n, k)
            expert = jnp.broadcast_to(
                expert.reshape(x.shape[-2], k), (*logits.shape[:-1], k)
            )
        else:
            probs = router_ops.SCORES[cfg.router](logits)
            _, expert = jax.lax.top_k(
                probs if bias is None else probs + jax.lax.stop_gradient(bias),
                k,
            )
        expert = checkpoint_name(expert.astype(jnp.int32), ROUTE_RESULT)
        # The chosen scores and the weights carry the name too: no gather
        # (plain form) and no kernel runs again in the backward.
        return expert, router_ops.route_weights(
            logits, expert, scoring=cfg.router, scaling=cfg.routed_scaling,
            name=ROUTE_RESULT, mesh=self.mesh,
        )

    @nn.compact
    def __call__(self, x, router_state):
        cfg = self.config
        first, held = cfg.experts_held or (0, cfg.num_experts)
        if first < 0 or held < 1 or first + held > cfg.num_experts:
            raise ValueError(
                f"experts_held {cfg.experts_held} is not a range of the "
                f"{cfg.num_experts} experts"
            )
        if cfg.router not in ("mlp", "sigmoid", "softmax") or (
            cfg.router == "mlp" and cfg.experts_per_token != 1
        ):
            raise ValueError(
                f"router {cfg.router!r} with {cfg.experts_per_token} experts "
                "a token: expected 'mlp' (one a token), 'sigmoid' or 'softmax'"
            )
        with jax.named_scope("moe.route"):
            if cfg.router == "mlp":
                expert, gate, router_state = self._route(x, router_state)
            else:
                expert, gate = self._route_sigmoid(x)
            load = jnp.sum(
                expert.reshape(-1)[:, None]
                == first + jnp.arange(held, dtype=jnp.int32)[None, :],
                axis=0,
            ).astype(jnp.float32)
        # For whoever asks for "intermediates": the choice made per token.
        self.sow(
            "intermediates", "expert", expert,
            reduce_fn=lambda _, new: new, init_fn=lambda: 0,
        )
        # One value a layer, however often a remat traces the layer.
        for name, value in (
            ("moe_tokens_held", jnp.sum(load)),
            ("moe_load_max", jnp.max(load)),
            ("moe_load_mean", jnp.mean(load)),
            ("moe_row_tiles", float(row_tiles(
                expert.size // cfg.experts_per_token, cfg.experts_per_token,
                held,
            ))),
            ("moe_row_tiles_live", jnp.sum(tiles_in_use(load))),
        ):
            self.sow(
                "counters", name, value,
                reduce_fn=lambda _, new: new, init_fn=lambda: 0.0,
            )

        def weight(name, rows, cols, names):
            return self.param(
                name,
                nn.with_logical_partitioning(
                    nn.initializers.variance_scaling(
                        1.0, "fan_in", "normal", in_axis=-2, out_axis=-1,
                        batch_axis=(0,),
                    ),
                    ("expert", *names),
                ),
                (held, rows, cols), jnp.float32,
            )

        inner = x
        if cfg.moe_latent:
            with jax.named_scope("moe.latent_in"):
                inner = checkpoint_name(_dense(
                    cfg.moe_latent, ("embed", None), "latent_in", cfg.dtype
                )(x), LATENT_RESULT)
        dm, ff = inner.shape[-1], cfg.d_ff
        into = ("w_gate", "w_up") if _mlp_cls(cfg) is SwiGLU else ("w_in",)
        weights = (
            *(weight(name, dm, ff, ("embed", "mlp")) for name in into),
            weight("w_down", ff, dm, ("mlp", "embed")),
        )
        out = expert_mlp_on_mesh(
            self.mesh, inner, expert, gate.astype(jnp.float32), weights, first
        )
        if cfg.moe_latent:
            with jax.named_scope("moe.latent_out"):
                out = _dense(
                    cfg.d_model, (None, "embed"), "latent_out", cfg.dtype
                )(out)
        if cfg.moe_shared_ff:
            with jax.named_scope("moe.shared"):
                shared = _mlp_cls(cfg)(
                    dataclasses.replace(cfg, d_ff=cfg.moe_shared_ff),
                    name="shared",
                )(x)
                if not cfg.moe_shared_gate:
                    out = out + shared
            if cfg.moe_shared_gate:
                with jax.named_scope("moe.shared_gate"):
                    out = out + self._shared_gate(x, shared)
        return out, router_state

    def _shared_gate(self, x, shared):
        """`shared` times `sigmoid(x w_s)`, one gate a token."""
        cfg = self.config
        w = self.param(
            "shared_gate",
            nn.with_logical_partitioning(
                nn.initializers.variance_scaling(1.0, "fan_in", "normal"),
                ("embed", None),
            ),
            (x.shape[-1], 1), jnp.float32,
        )
        gate = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), w, precision=jax.lax.Precision.HIGHEST
        ))
        layers = (
            cfg.layer_pattern.count("E") if cfg.layer_pattern is not None
            else cfg.n_layers - cfg.dense_layers + cfg.mtp_layers
        )
        self.sow(
            "counters", "shared_gate_mean", jnp.mean(gate) / layers,
            reduce_fn=lambda _, new: new, init_fn=lambda: 0.0,
        )
        return (shared.astype(jnp.float32) * gate).astype(shared.dtype)


def _inverse_softplus(x):
    return x + jnp.log(-jnp.expm1(-x))


def _vector(module: nn.Module, name: str, init, size: int):
    """A replicated float32 vector of `module`'s parameters."""
    return module.param(name, _replicated(init, 1), (size,), jnp.float32)


def _steps(ssm_dt):
    """`dt_bias`'s initialiser: steps drawn log-uniform in `ssm_dt`'s
    (lo, hi), no smaller than its floor, through softplus's inverse."""
    lo, hi, floor = ssm_dt

    def steps(key, shape, dtype):
        drawn = jnp.exp(jax.random.uniform(
            key, shape, dtype, math.log(lo), math.log(hi)
        ))
        return _inverse_softplus(jnp.maximum(drawn, floor))

    return steps


def _a_log(key, shape, dtype):
    """`A_log`'s initialiser: the heads' rates 1 to 16, evenly."""
    return jnp.log(jnp.linspace(1.0, 16.0, shape[0], dtype=dtype))


class StateSpaceMixer(nn.Module):
    """Mamba-2's mixer over u [B, S, d_model], from the configuration's
    numbers: H = `ssm_heads` heads of P = `ssm_head_dim`, G = `ssm_groups`
    groups, a state of N = `ssm_state`, `d_in = H P`.

    `[z | xBC | dt] = u W_in` (one matrix, [d_model, 2 d_in + 2 G N + H],
    no bias); `xBC <- silu(conv(xBC))`, a causal depthwise convolution of
    `ssm_conv` taps with a bias (tap j multiplies the value j tokens
    back); `dt <- softplus(dt + dt_bias)`, `a = -exp(A_log)`; the scan
    (`ops/ssd.ssd_scan`: `s_t = exp(dt_t a) s_(t-1) + dt_t x_t (x) B_t`,
    `y_t = s_t C_t`) plus the skip `D x`; `y <- RMSNorm_group(y silu(z))`
    over each group's `d_in / G` channels with a learned scale; `y W_out`.
    x, B, C and y stay [B, S, heads·dims], as the scan's kernels read
    them. `W_in` is one matrix as published, so on a mesh it is not split
    over `tp` (the scan is, by whole groups)."""

    config: TransformerConfig
    mesh: Mesh | None = None

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        h, p, g, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state
        if h < 1 or h % g:
            raise ValueError(f"{h} state-space heads do not divide into {g} groups")
        d_in, gn, taps = h * p, g * n, cfg.ssm_conv
        f32 = jnp.float32
        vector = functools.partial(_vector, self)
        with jax.named_scope("ssm.in_proj"):
            proj = checkpoint_name(_dense(
                2 * d_in + 2 * gn + h, ("embed", None), "in_proj", cfg.dtype
            )(u), IN_PROJ_RESULT)
        z, xbc, dt = jnp.split(proj, [d_in, 2 * d_in + 2 * gn], axis=-1)
        with jax.named_scope("ssm.conv"):
            w = self.param(
                "conv_kernel",
                _replicated(nn.initializers.normal(taps ** -0.5), 2),
                (taps, d_in + 2 * gn), f32,
            )
            bias = vector("conv_bias", nn.initializers.zeros, d_in + 2 * gn)
            xbc = checkpoint_name(
                shortconv.short_conv(xbc, w, bias, mesh=self.mesh), CONV_RESULT
            )
        x, b, c = jnp.split(xbc, [d_in, d_in + gn], axis=-1)
        dt = nn.softplus(
            dt.astype(f32) + vector("dt_bias", _steps(cfg.ssm_dt), h)
        )
        a = -jnp.exp(vector("A_log", _a_log, h))
        with jax.named_scope("ssm.scan"):
            y = ssd_scan(
                x, dt, a, b, c, groups=g, chunk=cfg.ssm_chunk, mesh=self.mesh
            )
            skip = jnp.repeat(vector("D", nn.initializers.ones, h), p)
        with jax.named_scope("ssm.gate_norm"):
            # The skip's sum is the gated norm's: float32, never rounded.
            y = checkpoint_name(gatenorm.gated_norm(
                y, z, vector("norm_scale", nn.initializers.ones, d_in),
                group=d_in // g, eps=cfg.norm_eps, gate_first=True,
                skip=(x, skip), mesh=self.mesh,
            ), GATED_RESULT)
        with jax.named_scope("ssm.out_proj"):
            return _dense(cfg.d_model, (None, "embed"), "out_proj", cfg.dtype)(y)


class DeltaMixer(nn.Module):
    """The gated delta rule's mixer over x [B, S, d_model], from the
    configuration's numbers and the layer's row (`AttentionKind`): H =
    `kind.n_heads` value heads over H_k = `kind.key_heads` key heads of d =
    the row's (or the stack's) `head_dim` key and value channels.

    `q~ = x wq`, `k~ = x wk` (H_k heads), `v~ = x wv` (H heads), no bias;
    each through a causal depthwise convolution of `ssm_conv` taps (no
    bias) and `silu`; a head's `q = d^-1/2 q^ / |q^|`, `k = k^ / |k^|`; `b =
    sigmoid(x wb)` a value head; the delta rule (`ops/kda.kda_scan`: `S_t =
    (I - b_t k_t k_t^T) diag(e^(g_t)) S_(t-1) + b_t k_t v_t^T`, `o_t = S_t^T
    q_t`, value head h reading key head h // (H / H_k)); `y =
    RMSNorm_head(o) w_n act(gate)`, the norm over each head's d with ONE
    learned scale [d], `act` the row's `gate_act`; `y wo`. By the row's
    `decay`:

    - "channel" (Kimi Delta Attention; H_k = H): `g = -exp(A_log[h])
      softplus((x wf_a) wf_b + dt_bias)` a key CHANNEL and `gate = (x wg_a)
      wg_b`, each through a bottleneck of d;
    - "head" (Gated DeltaNet): `g = -exp(A_log[h]) softplus(x wa +
      dt_bias[h])`, ONE a value head, beside b in float32 at full
      precision, and `gate = x wg`, one matrix (projected beside q, k and
      v: its product is `kda.proj`'s).

    q, k, v and o stay [B, S, heads·d], as the kernels read them, and g is
    as wide as it is published: [B, S, H·d] or [B, S, H]. Sows
    `kda_decay_mean` (the mean of `e^g`: the share of the state that
    survives a token) and `kda_beta_mean`, each over the count of the
    stack's delta layers so that the step's sum is a mean."""

    config: TransformerConfig
    mesh: Mesh | None = None
    kind: AttentionKind | None = None

    @nn.compact
    def __call__(self, x):
        cfg, kind = self.config, self.kind
        h, d, taps = kind.n_heads, kind.head_dim or cfg.head_dim, cfg.ssm_conv
        hk, by_head = kind.key_heads or h, kind.decay == "head"
        wide, f32 = h * d, jnp.float32
        vector = functools.partial(_vector, self)
        matrix = lambda name, cols: self.param(
            name,
            nn.with_logical_partitioning(
                nn.initializers.variance_scaling(1.0, "fan_in", "normal"),
                ("embed", "heads"),
            ),
            (x.shape[-1], cols), f32,
        )
        with jax.named_scope("kda.proj"):
            q, k, v = (
                checkpoint_name(_dense(
                    (n, d), ("embed", "heads", "kv"), name, cfg.dtype
                )(x), KDA_PROJ_RESULT)
                for name, n in (("wq", hk), ("wk", hk), ("wv", h))
            )
            if by_head:  # What is named is what the gated norm's backward reads.
                gate = checkpoint_name(
                    _dense(wide, ("embed", "heads"), "wg", cfg.dtype)(x),
                    KDA_GATE_RESULT,
                )
        with jax.named_scope("kda.conv"):
            def conv(u, name, scale=None):  # with a scale: a head's u / |u|
                w = self.param(
                    f"conv_{name}",
                    _replicated(nn.initializers.normal(taps ** -0.5), 2),
                    (taps, u.shape[-1]), f32,
                )
                return shortconv.short_conv(
                    u, w, sum_dtype=cfg.dtype, head_dim=d if scale else 0,
                    scale=scale or 1.0, eps=cfg.norm_eps,
                    name=KDA_CONV_RESULT, mesh=self.mesh,
                )

            q, k, v = conv(q, "q", d ** -0.5), conv(k, "k", 1.0), conv(v, "v")
        with jax.named_scope("kda.gates"):
            if by_head:  # b and the decay: one product of 2 H columns
                both = checkpoint_name(jnp.dot(
                    x.astype(f32),
                    jnp.concatenate([matrix("wb", h), matrix("wa", h)], axis=1),
                    precision=jax.lax.Precision.HIGHEST,
                ), KDA_DECAY_RESULT)
                g = -jnp.exp(vector("A_log", _a_log, h)) * nn.softplus(
                    both[..., h:] + vector("dt_bias", _steps(cfg.ssm_dt), h)
                )
                beta = jax.nn.sigmoid(both[..., :h])
            else:
                low = _dense(d, ("embed", None), "wf_a", cfg.dtype)(x)
                decay = checkpoint_name(
                    _dense(wide, (None, "heads"), "wf_b", cfg.dtype)(low),
                    KDA_DECAY_RESULT,
                )
                rate = jnp.repeat(jnp.exp(vector("A_log", _a_log, h)), d)
                g = -rate * nn.softplus(
                    decay.astype(f32)
                    + vector("dt_bias", _steps(cfg.ssm_dt), wide)
                )
                beta = jax.nn.sigmoid(jnp.dot(
                    x.astype(f32), matrix("wb", h),
                    precision=jax.lax.Precision.HIGHEST,
                ))
            layers = sum(kind.mixer == "delta" for kind in _block_kinds(cfg))
            for name, value in (
                ("kda_decay_mean", jnp.mean(jnp.exp(g))),
                ("kda_beta_mean", jnp.mean(beta)),
            ):
                self.sow(
                    "counters", name, value / layers,
                    reduce_fn=lambda _, new: new, init_fn=lambda: 0.0,
                )
        with jax.named_scope("kda.scan"):
            o = kda_scan(q, k, v, g, beta, chunk=cfg.ssm_chunk, mesh=self.mesh)
        with jax.named_scope("kda.gate_norm"):
            if not by_head:
                low = _dense(d, ("embed", None), "wg_a", cfg.dtype)(x)
                # What is named is what the gated norm's backward reads.
                gate = checkpoint_name(
                    _dense(wide, (None, "heads"), "wg_b", cfg.dtype)(low),
                    KDA_GATE_RESULT,
                )
            scale = jnp.tile(vector("norm_scale", nn.initializers.ones, d), h)
            y = checkpoint_name(gatenorm.gated_norm(
                o, gate, scale, group=d, eps=cfg.norm_eps, gate_first=False,
                act=kind.gate_act, mesh=self.mesh,
            ), GATED_RESULT)
        with jax.named_scope("kda.out_proj"):
            return _dense(cfg.d_model, ("heads", "embed"), "wo", cfg.dtype)(y)


class StreamMaps(nn.Module):
    """The three maps round one sublayer of a stack with n =
    `residual_streams` streams X [B, S, n·d] (mHC's symbols), and the mix
    into the sublayer: `x = RMSNorm(X)` over all n·d dims, no learned
    scale; `[t_pre | t_post | t_res] = x phi`, `phi` [n·d, n² + 2n],
    float32 at full precision; `Hp = sigmoid(a_pre t_pre + b_pre)` [n],
    the weights of the sublayer's input `h = sum_i Hp[i] X[i]`; `Ho = 2
    sigmoid(a_post t_post + b_post)` [n], of its output onto each stream;
    `Hr = sinkhorn(exp(clip(a_res t_res + b_res, +-hc_clamp)))` [n, n],
    stream to stream. Returns (h [B, S, d], the streams for the mix out,
    Ho [B, n, S], Hr [B, n, n, S]): the maps float32 with the sequence in
    the lanes (a trailing axis of 4 would pad a tile 32 times over). The
    norm is a scalar a token, so it multiplies the product, not the
    streams: X is read, never written. What is named (`HC_RESULT`) is the
    RAW product and that scalar, so the backward forms neither again.

    The mix is `ops/streams.mix_in`, which decides its form: the streams
    returned are for `ops/streams.mix_out` ALONE (the kernels' two rules
    share one write of dX). Sows `hc_sinkhorn_err` (the largest |row or
    column sum - 1| of Hr) and `hc_res_diag_mean` (Hr's mean diagonal),
    each over the stack's sublayers' count so that the step's sum is a
    mean, and `hc_kernel_sublayers`, 1 where the kernels ran."""

    config: TransformerConfig
    mesh: Mesh | None = None

    @nn.compact
    def __call__(self, streams):
        cfg = self.config
        n, f32 = cfg.residual_streams, jnp.float32
        spec = _stream_maps(cfg)
        width = streams.shape[-1]
        phi = self.param(
            "phi",
            nn.with_logical_partitioning(
                nn.initializers.normal(width ** -0.5), ("embed", None)
            ),
            (width, spec.maps), f32,
        )
        # b_res 2 on the diagonal: the seed's stream-to-stream map leans
        # to the identity without being it.
        bias = self.param(
            "b",
            _replicated(lambda *_: jnp.concatenate(
                [jnp.zeros(2 * n, f32), 2.0 * jnp.eye(n, dtype=f32).reshape(-1)]
            ), 1),
            (spec.maps,), f32,
        )
        a = self.param("a", _replicated(nn.initializers.ones, 1), (3,), f32)
        ran = streams_ops.kernels_apply(streams, spec, self.mesh)  # to report
        h, streams, ho, hr = streams_ops.mix_in(
            streams, phi, a, bias, spec, self.mesh
        )
        with jax.named_scope("hc.maps"):
            sums = jnp.concatenate([hr.sum(axis=2), hr.sum(axis=1)], axis=1)
            sublayers = 2 * cfg.n_layers
            for name, value in (
                ("hc_sinkhorn_err", jnp.max(jnp.abs(sums - 1.0)) / sublayers),
                ("hc_res_diag_mean", jnp.mean(
                    jnp.trace(hr, axis1=1, axis2=2) / n
                ) / sublayers),
                ("hc_kernel_sublayers", float(ran)),
            ):
                self.sow(
                    "counters", name, value,
                    reduce_fn=lambda _, new: new, init_fn=lambda: 0.0,
                )
        return h, streams, ho, hr


def _stream_maps(cfg: "TransformerConfig") -> streams_ops.Maps:
    return streams_ops.Maps(
        cfg.residual_streams, cfg.d_model, cfg.hc_iters, cfg.hc_clamp,
        cfg.hc_eps, cfg.norm_eps,
    )


class Block(nn.Module):
    """One layer: its mixer (attention or, where the layer's row of
    `attention_kinds` says "delta", `DeltaMixer`), then the dense MLP or
    the expert layer.
    Takes and returns the router's carried state beside the residual
    (None where there are no experts). `layer` is its place in the stack,
    which only `router_force_balance` reads. With `residual_streams` the
    residual is that many streams side by side, [B, S, n·d], and each
    sublayer is taken round by their maps (`_mixed`)."""

    config: TransformerConfig
    mesh: Mesh | None = None
    layer: int = 0
    # What `_layer_classes` hands a layer of a stack whose layers differ:
    # its attention kind, and whether its feed-forward half is the dense
    # MLP of `dense_d_ff` where the stack's other layers have experts.
    attention: AttentionKind | None = None
    dense: bool = False

    @nn.nowrap
    def _mixed(self, streams, name: str, sublayer):
        """One sublayer round n streams [B, S, n·d]: its input is
        `sum_i Hp[i] X[i]` (`StreamMaps`), and `X'[i] = sum_j Hr[i, j]
        X[j] + Ho[i] y` with y its output. The sums in float32, the
        streams stored in `dtype`. `sublayer(h)` -> (y, what it returns
        beside). The round's passes over the streams are
        `ops/streams.mix_in` and `mix_out`, which decide their form."""
        cfg = self.config
        h, streams, ho, hr = StreamMaps(cfg, self.mesh, name=f"hc_{name}")(streams)
        y, beside = sublayer(h)
        # `Ho`'s gradient reads y: named, or the sublayer's last product
        # runs again for it alone.
        y = checkpoint_name(y, STREAM_OUT_RESULT)
        mixed = streams_ops.mix_out(
            streams, y, hr, ho, _stream_maps(cfg), self.mesh
        )
        return mixed, beside

    @nn.compact
    def __call__(self, x, positions, router_state=None):
        cfg = self.config
        norm = _norm_cls(cfg)
        # The "mlp" policy's only checkpoint: the MLP half recomputes in
        # the backward, attention's residuals stay saved (the lifted
        # transform keeps the param path, so weights are identical to
        # the unwrapped module's).
        wrap = (
            nn.remat if cfg.remat_policy == "mlp"
            else (lambda cls: cls)
        )

        def attention(x):
            h = norm(name="ln_attn")(x)
            if self.attention is not None and self.attention.mixer == "delta":
                return DeltaMixer(cfg, self.mesh, self.attention, name="kda")(h)
            return Attention(cfg, self.mesh, self.attention, name="attn")(
                h, positions
            )

        def feed_forward(x, router_state):
            h = norm(name="ln_mlp")(x)
            if cfg.num_experts > 0 and not self.dense:
                return wrap(ExpertLayer)(
                    cfg, self.mesh, self.layer, name="moe"
                )(h, router_state)
            dense = (
                dataclasses.replace(cfg, d_ff=cfg.dense_d_ff)
                if self.dense else cfg
            )
            return wrap(SwiGLU)(dense, name="mlp")(h), router_state

        if cfg.residual_streams:
            x, _ = self._mixed(x, "attn", lambda h: (attention(h), None))
            x = checkpoint_name(x, RESIDUAL_RESULT)
            return self._mixed(
                x, "mlp", lambda h: feed_forward(h, router_state)
            )
        x = checkpoint_name(x + attention(x), RESIDUAL_RESULT)
        out, router_state = feed_forward(x, router_state)
        return x + out, router_state


LAYER_KINDS = {
    "M": "a state-space mixer", "E": "the expert layer", "*": "attention",
}


class Sublayer(nn.Module):
    """One layer of a `layer_pattern`: `x + f(RMSNorm(x))`, f by `kind`
    (`LAYER_KINDS`). `Block`'s signature, so one loop builds either
    stack; only the "mlp"-router expert layer touches `router_state`."""

    config: TransformerConfig
    mesh: Mesh | None = None
    layer: int = 0
    kind: str = "*"

    @nn.compact
    def __call__(self, x, positions, router_state=None):
        cfg, kind = self.config, self.kind
        h = _norm_cls(cfg)(name="ln")(x)
        # As in `Block`: the "mlp" policy's only checkpoint.
        wrap = nn.remat if cfg.remat_policy == "mlp" else (lambda cls: cls)
        if kind == "M":
            out = StateSpaceMixer(cfg, self.mesh, name="ssm")(h)
        elif kind == "E":
            out, router_state = wrap(ExpertLayer)(
                cfg, self.mesh, self.layer, name="moe"
            )(h, router_state)
        elif kind == "*":
            out = Attention(cfg, self.mesh, name="attn")(h, positions)
        else:
            raise ValueError(
                f"unknown layer kind {kind!r} in layer_pattern; expected one "
                f"of {sorted(LAYER_KINDS)}"
            )
        return x + out, router_state


def _layer_classes(cfg: TransformerConfig, keep: tuple[str, ...] = ()) -> list:
    """The stack's layers in order, each a class with `Block`'s
    constructor and call, wrapped per the remat policy."""
    if cfg.layer_pattern is None:
        block = _block_cls(cfg, keep=keep)
        if not (cfg.attention_kinds or cfg.dense_layers):
            return [block] * cfg.n_layers
        return [
            functools.partial(
                block, attention=kind, dense=i < cfg.dense_layers
            )
            for i, kind in enumerate(_attention_kinds(cfg))
        ]
    if cfg.attention_kinds or cfg.dense_layers:
        raise ValueError(
            "attention kinds by layer and leading dense layers are built "
            "for a stack of blocks, not for a layer_pattern"
        )
    if len(cfg.layer_pattern) != cfg.n_layers:
        raise ValueError(
            f"layer_pattern {cfg.layer_pattern!r} names "
            f"{len(cfg.layer_pattern)} layers, n_layers is {cfg.n_layers}"
        )
    sublayer = _block_cls(cfg, Sublayer, keep)
    return [functools.partial(sublayer, kind=k) for k in cfg.layer_pattern]


class MultiTokenModule(nn.Module):
    """The multi-token module (`TransformerConfig.mtp_layers`; DeepSeek-V3's
    report, arXiv 2412.19437 §2.2, depth 1; leaves as the published
    checkpoints name them): for position i, `z_i = [norm(Emb(t_(i+1));
    enorm) | norm(h_i; hnorm)] eh_proj` with `h` the main stack's output
    after its final norm and the embedding's half first; `y = block(z)`,
    ONE further `Block` of the stack's own class (an expert layer where
    the stack has experts, the last layer's attention kind, the same
    positions, parameters of its own; `layer` = `n_layers`, so a forced
    selection folds its own index and the routing counters gain its row);
    `logits'_i = norm(y_i; head_norm) head^T`, the embedding and the head
    the main model's own matrices: logits of t_(i+2). Everything of it is
    under the `mtp` frame of a profile's paths (`mtp.proj`, the block's
    usual scopes, `mtp.head`)."""

    config: TransformerConfig
    mesh: Mesh | None = None
    keep: tuple[str, ...] = ()  # the remat plan's names, as the layers'

    @nn.compact
    def __call__(self, h, next_tokens, embed, head, positions, router_state):
        cfg = self.config
        norm = _norm_cls(cfg)
        with jax.named_scope("mtp.proj"):
            e = embed.astype(cfg.dtype)[next_tokens]
            z = _dense(cfg.d_model, (None, "embed"), "eh_proj", cfg.dtype)(
                jnp.concatenate(
                    [norm(name="enorm")(e), norm(name="hnorm")(h)], axis=-1
                )
            )
        block = _block_cls(cfg, keep=self.keep)
        if cfg.attention_kinds or cfg.dense_layers:
            block = functools.partial(
                block, attention=_attention_kinds(cfg)[-1], dense=False
            )
        y, _ = block(cfg, self.mesh, layer=cfg.n_layers, name="block")(
            z, positions, router_state
        )
        with jax.named_scope("mtp.head"):
            return lm_head(norm(name="head_norm")(y), head, dtype=cfg.dtype)


class _PipelineStage(nn.Module):
    """`layers_per_stage` sequential Blocks = one pipeline stage.

    Shared by both pipelined execution paths: the logits path stacks it
    with `nn.vmap` (partition axis "stage"), the loss path initializes
    the same stacked tree functionally and applies one slice per
    `spmd_pipeline` tick — so the two paths can never drift apart in
    weight structure."""

    config: TransformerConfig
    layers_per_stage: int
    mesh: Mesh | None = None

    @nn.compact
    def __call__(self, x, positions):
        block_cls = _block_cls(self.config)
        for i in range(self.layers_per_stage):
            x, _ = block_cls(self.config, self.mesh, name=f"layer_{i}")(
                x, positions
            )
        return x


class PipelinedTransformerLM(nn.Module):
    """TransformerLM with layers split into `n_stages` pipeline stages
    over the `pp` mesh axis, `num_microbatches` deep.

    Two execution paths share one weight tree:

    - **Logits path** (`labels=None`): the GPipe schedule expressed with
      stacked-stage params (`nn.vmap` with a "stage" partition axis →
      the `pp` sharding rule) and a roll of the stage-stacked activation
      buffer each tick — on a pp-sharded mesh XLA lowers the roll to
      collective-permutes between neighbor stages. Returns `[B, S, V]`
      logits (which necessarily replicates the last stage's outputs
      across pp — fine for eval, NOT the training hot path).
    - **Loss path** (`labels=[B, S]` given): the training hot path, run
      as a compiled SPMD program through
      `parallel.pipeline.spmd_pipeline` — supports the interleaved
      (circular) schedule (`interleave=v`, `n_stages = v * pp`) and
      computes each microbatch's cross-entropy on the LAST stage, where
      the logits live, so the only cross-pp collective in fwd+bwd is the
      scalar loss psum (gradients ride the ppermute transposes). Returns
      the scalar mean loss. Wire this up via
      `TrainConfig.loss_in_model=True`.

    The reference has no pipeline parallelism anywhere (SURVEY.md §2.2).

    Weights match `TransformerLM` block-for-block: the stacked params
    live at `params/stages/blocks/layer_<i>` with a leading stage axis,
    and `params/stages/blocks/layer_i[s]` equals the flat model's
    `params/layer_{s * layers_per_stage + i}` (the equivalence test
    restacks one into the other; the interleaved slice-to-rank
    assignment is internal to `spmd_pipeline`, so stacked index `s` is
    pipeline stage `s` under every schedule). Expert layers are not
    supported (the router's state would have to ride the pipeline's
    hand-off beside the residual, and the bubbles would count tokens)."""

    config: TransformerConfig
    n_stages: int
    num_microbatches: int
    mesh: Mesh | None = None
    interleave: int = 1

    @nn.compact
    def __call__(self, tokens, train: bool = False, labels=None):
        cfg = self.config
        if (
            cfg.num_experts > 0 or cfg.layer_pattern or not cfg.tie_embeddings
            or cfg.attention_kinds or cfg.residual_streams or cfg.mtp_layers
        ):
            raise ValueError(
                "pipelined transformer does not support MoE, a layer "
                "pattern, attention kinds by layer, residual streams, an "
                f"untied head or a multi-token module ({cfg.mtp_layers})"
            )
        if cfg.n_layers % self.n_stages:
            raise ValueError(
                f"n_layers ({cfg.n_layers}) must divide into "
                f"{self.n_stages} stages"
            )
        if tokens.shape[0] % self.num_microbatches:
            raise ValueError(
                f"batch ({tokens.shape[0]}) must divide into "
                f"{self.num_microbatches} microbatches"
            )
        if self.interleave < 1 or self.n_stages % self.interleave:
            raise ValueError(
                f"interleave ({self.interleave}) must be >= 1 and divide "
                f"n_stages ({self.n_stages})"
            )

        embed = self.param(
            "embedding",
            nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("vocab", "embed")
            ),
            (cfg.vocab_size, cfg.d_model),
            jnp.float32,
        )
        if labels is not None:
            # The loss path hands RAW TOKENS to the pipeline and embeds
            # at injection (spmd_pipeline's inject_fn): an int batch has
            # no cotangent, so no [B, S, d_model]-sized gradient ever
            # all-reduces across pp at the shard_map boundary — the
            # embedding's own gradient rides the replicated-weight psum.
            return self._pipeline_loss(tokens, labels, embed)
        x = embed.astype(cfg.dtype)[tokens]
        if self.interleave != 1 and not self.is_initializing():
            # Weights are schedule-independent, so init may run through
            # this (GPipe) path regardless; actually COMPUTING logits
            # under the circular schedule is not supported.
            raise ValueError(
                "the logits path runs the plain GPipe schedule; the "
                "interleaved (circular) schedule is a training-schedule "
                "feature — call with labels= for the last-stage loss path"
            )
        if self.mesh is not None:
            pp = dict(self.mesh.shape).get("pp")
            if pp is None or self.n_stages % pp:
                raise ValueError(
                    f"mesh needs a 'pp' axis whose size divides n_stages="
                    f"{self.n_stages}; mesh axes: {dict(self.mesh.shape)}"
                )
        layers_per_stage = cfg.n_layers // self.n_stages
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape
        )

        outer_mesh = self.mesh
        n_mb, n_stages = self.num_microbatches, self.n_stages
        mb_size = tokens.shape[0] // n_mb
        x_mb = x.reshape((n_mb, mb_size) + x.shape[1:])
        pos_mb = positions[:mb_size]
        ticks = n_mb + n_stages - 1  # GPipe: M + S - 1

        def constrain(states):
            if outer_mesh is None:
                return states
            return jax.lax.with_sharding_constraint(
                states,
                NamedSharding(
                    outer_mesh, P("pp", tuple(batch_axes(outer_mesh)))
                ),
            )

        class Tick(nn.Module):
            """One pipeline tick: inject, apply all stages in parallel
            (vmap over the stacked stage axis), emit, rotate."""

            @nn.compact
            def __call__(self, carry, xs):
                states, outputs = carry
                t, inject = xs
                stages = nn.vmap(
                    _PipelineStage,
                    in_axes=(0, None),
                    out_axes=0,
                    variable_axes={"params": 0},
                    split_rngs={"params": True},
                    axis_size=n_stages,
                    metadata_params={nn.meta.PARTITION_NAME: "stage"},
                )(cfg, layers_per_stage, outer_mesh, name="blocks")
                states = states.at[0].set(
                    jnp.where(t < n_mb, inject, states[0])
                )
                states = constrain(stages(states, pos_mb))
                out_idx = jnp.clip(t - (n_stages - 1), 0, n_mb - 1)
                # Single-slot select: masking only the written microbatch
                # keeps output collection O(M) across the scan (a select
                # over the whole buffer per tick would be O(M^2)).
                outputs = outputs.at[out_idx].set(
                    jnp.where(t >= n_stages - 1, states[-1], outputs[out_idx])
                )
                # Neighbor handoff: stage i's output feeds stage i+1.
                states = constrain(jnp.roll(states, 1, axis=0))
                return (states, outputs), None

        # nn.scan over ticks keeps the traced program CONSTANT in the
        # microbatch count (one stage-stack in the jaxpr, not M+S-1
        # copies); params broadcast across ticks = ordinary weight reuse.
        scan_ticks = nn.scan(
            Tick,
            variable_broadcast="params",
            split_rngs={"params": False},
            length=ticks,
        )(name="stages")

        states0 = constrain(
            jnp.zeros((n_stages, mb_size) + x.shape[1:], x.dtype)
        )
        # Per-tick inject stream: microbatch t for the first M ticks, then
        # (masked) repeats of the last microbatch during drain.
        inject_idx = jnp.minimum(jnp.arange(ticks), n_mb - 1)
        (final_states, outputs), _ = scan_ticks(
            (states0, jnp.zeros_like(x_mb)),
            (jnp.arange(ticks), x_mb[inject_idx]),
        )
        del final_states
        x = outputs.reshape(x.shape)
        x = RMSNorm(cfg.dtype, name="ln_final")(x)
        # The pipelined and flat models must stay numerically identical
        # block-for-block AND head-for-head.
        return lm_head(x, embed, dtype=cfg.dtype)

    def _pipeline_loss(self, tokens, labels, embed):
        """The training hot path: `spmd_pipeline` over the pp ring with
        the per-microbatch cross-entropy computed on the last stage.

        Declares the SAME parameter tree the logits path's module
        machinery creates (`stages/blocks/layer_i` stacked on a leading
        "stage" axis, `ln_final/scale`), so one checkpoint serves both
        paths; flax validates the shapes against these declarations on
        every retrieval."""
        from kubeflow_tpu.parallel.pipeline import spmd_pipeline
        from kubeflow_tpu.train.trainer import softmax_cross_entropy

        cfg = self.config
        layers_per_stage = cfg.n_layers // self.n_stages
        template = _PipelineStage(cfg, layers_per_stage, mesh=None)
        seq = tokens.shape[1]

        def init_stages(rng):
            dummy = jnp.zeros((1, seq, cfg.d_model), cfg.dtype)
            dpos = jnp.zeros((1, seq), jnp.int32)
            stacked = jax.vmap(
                lambda r: template.init(r, dummy, dpos)["params"]
            )(jax.random.split(rng, self.n_stages))
            # Tag the new leading axis exactly as nn.vmap's
            # metadata_params would, so init through EITHER path yields
            # identical logical annotations (→ identical shardings).
            return {
                "blocks": jax.tree_util.tree_map(
                    lambda b: b.add_axis(
                        0, {nn.meta.PARTITION_NAME: "stage"}
                    )
                    if isinstance(b, nn.meta.AxisMetadata)
                    else b,
                    stacked,
                    is_leaf=lambda b: isinstance(b, nn.meta.AxisMetadata),
                )
            }

        stages = self.param("stages", init_stages)["blocks"]
        ln_scale = self.param(
            "ln_final",
            lambda rng: {
                "scale": nn.with_logical_partitioning(
                    nn.initializers.ones, ("norm",)
                )(rng, (cfg.d_model,), jnp.float32)
            },
        )["scale"]

        def stage_fn(p, x_mb):
            positions = jnp.broadcast_to(
                jnp.arange(x_mb.shape[1], dtype=jnp.int32), x_mb.shape[:2]
            )
            return template.apply({"params": p}, x_mb, positions)

        def inject_fn(tokens_mb, lp):
            return lp["embed"].astype(cfg.dtype)[tokens_mb]

        def ce_fn(out_mb, labels_mb, lp):
            # Same head contract as the flat model: final RMSNorm, then
            # the shared tied-embedding head.
            h = rms_norm(out_mb, lp["ln_scale"], dtype=cfg.dtype)
            logits = lm_head(h, lp["embed"], dtype=cfg.dtype)
            return softmax_cross_entropy(logits, labels_mb)

        loss_params = {"embed": embed, "ln_scale": ln_scale}
        if self.mesh is None:
            # No mesh to pipeline over: the sequential reference (stacked
            # index s IS pipeline stage s), same objective.
            x = inject_fn(tokens, loss_params)
            for s in range(self.n_stages):
                x = stage_fn(
                    jax.tree_util.tree_map(lambda p: p[s], stages), x
                )
            return ce_fn(x, labels, loss_params)
        pp = dict(self.mesh.shape).get("pp")
        if pp is None or self.n_stages != self.interleave * pp:
            raise ValueError(
                f"the pipeline loss path needs n_stages "
                f"({self.n_stages}) == interleave ({self.interleave}) x "
                f"pp; mesh axes: {dict(self.mesh.shape)}"
            )
        return spmd_pipeline(
            stage_fn,
            stages,
            tokens,
            mesh=self.mesh,
            num_microbatches=self.num_microbatches,
            interleave=self.interleave,
            loss_fn=ce_fn,
            targets=labels,
            loss_params=loss_params,
            inject_fn=inject_fn,
        )


class TransformerLM(nn.Module):
    """Embed → N layers → norm → logits. apply(tokens[, train]) → [B,S,V].
    The layers are `Block`s, or the single sublayers `layer_pattern`
    names; the head is the embedding's matrix or, untied, its own.

    apply(tokens, labels=[B, S]) → the scalar objective
    (`TrainConfig.loss_in_model`): the mean next-token cross entropy
    `main` and, with a multi-token module (`mtp_layers`:
    `MultiTokenModule`, over the normed output, the embedding read a
    second time at the label and the head multiplied a second time),
    `main + mtp_weight * mtp`, `mtp` the mean over positions i < S - 1 of
    the cross entropy of the module's logits against `labels[i + 1]`: the
    labels shifted by one more and the last position masked, so a batch
    stays [B, S + 1] tokens. `main_loss` and `mtp_loss` are sown under
    "counters". Without labels the module does not run (but to make its
    parameters, at `init`)."""

    config: TransformerConfig
    mesh: Mesh | None = None

    @nn.compact
    def __call__(self, tokens, train: bool = False, labels=None):
        cfg = self.config
        embed = self.param(
            "embedding",
            nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("vocab", "embed")
            ),
            (cfg.vocab_size, cfg.d_model),
            jnp.float32,
        )
        # `embed` and `head` name what runs outside every layer for a
        # profile's reader (`train/profiling.program_scopes`).
        with jax.named_scope("embed"):
            x = embed.astype(cfg.dtype)[tokens]
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape
            )
            # The first layer's router has no state before it: zeros.
            router_state = (
                jnp.zeros((*tokens.shape, cfg.router_hidden), jnp.float32)
                if cfg.num_experts > 0 and cfg.router == "mlp" else None
            )
        # What the trainer stated round this trace decides what the
        # layers' checkpoints keep (`remat_plan`); nothing stated, as in
        # serving or `eval`: the kernels' results alone.
        mesh_shape = dict(self.mesh.shape) if self.mesh is not None else {}
        shards = batch_shard_count(self.mesh) if self.mesh is not None else 1
        plan = remat_plan(
            cfg, tokens.size // (shards * mesh_shape.get("sp", 1)),
            memory.current(),
        )
        if plan.predicted_peak is not None:  # how far it engaged, for `fit()`
            for name, value in (
                ("remat_saved_bytes", plan.saved_bytes),
                ("remat_names", len(plan.names)),
            ):
                self.sow(
                    "counters", name, float(value),
                    reduce_fn=lambda _, new: new, init_fn=lambda: 0.0,
                )
        layers = _layer_classes(cfg, plan.names)  # checked before anything
        n = cfg.residual_streams
        if n:  # every stream enters as the embedding's row (side by side
            # in the lanes: `jnp.tile` goes by [B, S, n, d], a relayout)
            with jax.named_scope("hc.entry"):
                x = jnp.concatenate([x] * n, axis=-1)
        for i, layer_cls in enumerate(layers):
            x, router_state = layer_cls(
                cfg, self.mesh, layer=i, name=f"layer_{i}"
            )(x, positions, router_state)
        if n:  # and their sum leaves
            with jax.named_scope("hc.exit"):
                x = sum(
                    x[..., i * cfg.d_model:(i + 1) * cfg.d_model].astype(
                        jnp.float32
                    ) for i in range(n)
                ).astype(cfg.dtype)
        x = _norm_cls(cfg)(name="ln_final")(x)
        head = embed
        if not cfg.tie_embeddings:
            head = self.param(
                "lm_head",
                nn.with_logical_partitioning(
                    nn.initializers.normal(cfg.d_model ** -0.5),
                    ("vocab", "embed"),
                ),
                (cfg.vocab_size, cfg.d_model),
                jnp.float32,
            )
        with jax.named_scope("head"):
            logits = lm_head(x, head, dtype=cfg.dtype)
        if cfg.mtp_layers and (labels is not None or self.is_initializing()):
            # The module's name is the `mtp` frame of a profile's paths; at
            # `init` it runs to make its parameters, over any tokens.
            further = MultiTokenModule(cfg, self.mesh, plan.names, name="mtp")(
                x, tokens if labels is None else labels, embed, head,
                positions, router_state,
            )
        if labels is None:
            return logits
        from kubeflow_tpu.train.trainer import softmax_cross_entropy

        with jax.named_scope("loss"):
            loss = softmax_cross_entropy(logits, labels)
        if cfg.mtp_layers:
            with jax.named_scope("mtp"), jax.named_scope("mtp.loss"):
                # Position i's second target is labels[i + 1]; the last has
                # none and is masked, over logits left whole.
                mtp = softmax_cross_entropy(
                    further, jnp.roll(labels, -1, axis=1),
                    where=jnp.arange(labels.shape[1]) < labels.shape[1] - 1,
                )
            for name, value in (("main_loss", loss), ("mtp_loss", mtp)):
                self.sow(
                    "counters", name, value,
                    reduce_fn=lambda _, new: new, init_fn=lambda: 0.0,
                )
            loss = loss + cfg.mtp_weight * mtp
        return loss
