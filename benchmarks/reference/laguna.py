"""Plain reference for a Laguna-style decoder (poolside/Laguna-S-2.1) and its
training step.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernels, no band, no sort, no
mixed precision, nothing imported from the program (the helpers shared with
`reference/lm.py` and `reference/zaya.py` — the int8 control's rounding, the
AdamW step that keeps its moments on the host — are the benchmark's own).

`x` is the residual, `n(.)` RMSNorm with a learned scale at `rms_norm_eps`.
**Layer l** is `x <- x + A_l(n(x))`, then `x <- x + F_l(n(x))`; after the
last a final norm and the untied head; the loss is the mean cross-entropy
of the next token.

*Attention `A_l`.* `H_l = num_attention_heads_per_layer[l]` query heads over
`num_key_value_heads` K/V heads of `head_dim`, no bias: `q = h Wq`
[S, H_l, hd], `k = h Wk`, `v = h Wv` [S, Hkv, hd]; q and k turned by the
rope of the layer's type; query head j reads K/V head `j // (H_l / Hkv)`;
scores `q k^T / sqrt(hd)`; position i sees `j <= i` in a `full_attention`
layer and `i - sliding_window < j <= i` in a `sliding_attention` one
(`sliding_window` keys with its own); softmax; `o = P v`; the gate
`g = sigmoid(h Wg)`, `Wg` [d, H_l], one scalar a head and position,
`o_j <- g_j o_j`; out `o Wo`. The band is NOT enumerated here: every
layer's scores are the full row under a mask, a block of queries at a time.

*Rope*, by `rope_parameters[<layer type>]`, on the first
`partial_rotary_factor` of a head (`turned` lanes; the rest pass), lane t
paired with lane t + turned/2. `rope_type` `default`: `inv_freq_t =
theta^(-2t/turned)`, cos and sin unscaled. `yarn`: `extrap_t =
theta^(-2t/turned)`, `interp_t = extrap_t / factor`; `c(r) = turned
ln(original_max_position_embeddings / (2 pi r)) / (2 ln theta)`; `low =
max(floor(c(beta_fast)), 0)`, `high = min(ceil(c(beta_slow)), turned - 1)`;
`ramp_t = clip((t - low) / (high - low), 0, 1)`; `inv_freq_t = interp_t
ramp_t + extrap_t (1 - ramp_t)`; cos and sin times `attention_factor`.

*`F_l`, `mlp_layer_types[l]` `dense`:* SwiGLU, `(silu(h Wg) * (h Wu)) Wd`,
width `intermediate_size`. *`sparse`:* `p = sigmoid(h Wr)` over
`experts_routed`; chosen = the `num_experts_per_tok` largest of `p + b`;
weights `moe_routed_scaling_factor p_e / (sum of the chosen p + 1e-20)`
(`norm_topk_prob`), on the experts' outputs
(`moe_apply_router_weight_on_input` false); expert e is `(silu(h W1_e) *
(h W3_e)) W2_e` of width `moe_intermediate_size`; plus the shared expert,
the same form at `shared_expert_intermediate_size`, added unweighted. Only
`num_experts` experts from `experts_first` on are held: what the others
would add is left out, as in the program. Dense over the held experts with
a mask. With `cfg["router_force_balance"]` the chosen are not the router's:
they are the k largest of standard normal scores drawn for (position,
expert) from `PRNGKey(42)` folded with the layer's index, the same for
every row, step and run; the weights are still the router's p (why:
`reference/zaya.py`'s docstring).

**Assumed** (what `config.json` does not fix; the configuration file lists
the same, each with its reason): the router's scoring is a sigmoid with a
correction `b` (zero at the seed, no gradient, never updated); the gate is a
sigmoid of the layer's normed input, applied before `Wo`; no norm on q or
k and no gate on the shared expert; `silu`; the 1e-20 in the weights'
normalisation; `moe_router_logit_softcapping` 0 is off. Weights: normal,
std 0.02 for the embedding, 1/sqrt(fan_in) for every matrix (the head's
too), scales 1: the plain draw of `reference/lm.py`.
"""

from __future__ import annotations

import math
import sys

import jax
import jax.numpy as jnp

from benchmarks.reference import zaya as _zaya
from benchmarks.reference.lm import _einsum
from benchmarks.reference.zaya import _rms_norm

QUERY_BLOCK = 256  # 72 heads x 256 x 8192 float32 scores are 0.6 GB
FORCED_ROUTING_SEED = 42

# -- weights ---------------------------------------------------------------


def param_specs(cfg: dict) -> dict[str, tuple]:
    """name -> (shape, (std, mean)): the leaf is mean + std * normal."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    hk, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    held, routed = cfg["num_experts"], cfg["experts_routed"]
    ff, sff = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"]
    dense = cfg["intermediate_size"]
    mat = lambda shape, fan_in: (shape, (1 / math.sqrt(fan_in), 0.0))
    const = lambda shape, value: (shape, (0.0, value))
    specs = {"embedding": ((v, d), (0.02, 0.0))}
    for i in range(cfg["num_hidden_layers"]):
        pre, h = f"layer.{i}.", cfg["num_attention_heads_per_layer"][i]
        specs[pre + "ln_attn"] = const((d,), 1.0)
        specs[pre + "wq"] = mat((d, h, hd), d)
        specs[pre + "wk"] = mat((d, hk, hd), d)
        specs[pre + "wv"] = mat((d, hk, hd), d)
        specs[pre + "wg"] = mat((d, h), d)
        specs[pre + "wo"] = mat((h, hd, d), h * hd)
        specs[pre + "ln_mlp"] = const((d,), 1.0)
        if cfg["mlp_layer_types"][i] == "dense":
            specs[pre + "mlp_gate"] = mat((d, dense), d)
            specs[pre + "mlp_up"] = mat((d, dense), d)
            specs[pre + "mlp_down"] = mat((dense, d), dense)
        else:
            specs[pre + "router"] = mat((d, routed), d)
            specs[pre + "router_bias"] = const((routed,), 0.0)
            specs[pre + "w_gate"] = mat((held, d, ff), d)
            specs[pre + "w_up"] = mat((held, d, ff), d)
            specs[pre + "w_down"] = mat((held, ff, d), ff)
            specs[pre + "shared_gate"] = mat((d, sff), d)
            specs[pre + "shared_up"] = mat((d, sff), d)
            specs[pre + "shared_down"] = mat((sff, d), sff)
    specs["ln_final"] = const((d,), 1.0)
    specs["lm_head"] = mat((v, d), d)
    return specs


def init_leaf(key, index: int, shape, how):
    std, mean = how
    leaf = jnp.full(shape, mean, jnp.float32)
    if std:
        leaf = leaf + std * jax.random.normal(
            jax.random.fold_in(key, index), shape, jnp.float32
        )
    return leaf


def init_params(key, cfg: dict) -> dict[str, jax.Array]:
    return {
        name: init_leaf(key, i, *spec)
        for i, (name, spec) in enumerate(param_specs(cfg).items())
    }


# The layers differ in kind, so nothing is stacked: `follow` (zaya's) gets
# the flat tree both ways (`reference/nemotron_h.py` does the same).
stack_layers = lambda flat, cfg: flat
by_layer = lambda tree, cfg: tree

# -- the model ---------------------------------------------------------------


def rope_frequencies(cfg: dict, layer_type: str):
    """(inv_freq [turned / 2], scale of cos and sin, turned lanes) of a
    layer type's rope, by the docstring's equations."""
    p = cfg["rope_parameters"][layer_type]
    turned = int(cfg["head_dim"] * p.get("partial_rotary_factor", 1.0))
    theta = float(p["rope_theta"])
    pair = jnp.arange(0, turned, 2, dtype=jnp.float32)
    extrap = theta ** (-pair / turned)
    if p["rope_type"] == "default":
        return extrap, 1.0, turned
    if p["rope_type"] != "yarn":
        raise ValueError(f"rope_type {p['rope_type']!r}: default and yarn only")
    c = lambda r: turned * math.log(
        p["original_max_position_embeddings"] / (2 * math.pi * r)
    ) / (2 * math.log(theta))
    low = max(math.floor(c(p["beta_fast"])), 0)
    high = min(math.ceil(c(p["beta_slow"])), turned - 1)
    ramp = jnp.clip((pair / 2 - low) / (high - low), 0.0, 1.0)
    inv_freq = (extrap / p["factor"]) * ramp + extrap * (1.0 - ramp)
    return inv_freq, float(p["attention_factor"]), turned


def _rope(x, inv_freq, scale: float, turned: int):
    """x [B, S, H, D]: lane t of the first `turned` with lane t + turned/2."""
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos = scale * jnp.cos(angles)[None, :, None, :]
    sin = scale * jnp.sin(angles)[None, :, None, :]
    x1, x2 = jnp.split(x[..., :turned], 2, axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., turned:]], axis=-1
    )


def _attention(q, k, v, window, quant):
    """Softmax attention of q [B, S, H, d] over k, v [B, S, H, d]: causal,
    and with `window` only the last `window` keys; the whole row of scores
    under a mask, a block of queries at a time."""
    s, hd = q.shape[1], q.shape[-1]
    block = min(QUERY_BLOCK, s)
    if s % block:
        raise ValueError(f"sequence {s} does not divide into blocks of {block}")
    key_pos = jnp.arange(s)

    @jax.checkpoint
    def one(args):
        q_blk, start = args
        scores = _einsum("bqhk,bshk->bhqs", q_blk, k, quant) / math.sqrt(hd)
        query_pos = (start + jnp.arange(block))[:, None]
        seen = query_pos >= key_pos[None, :]
        if window is not None:
            seen &= query_pos - key_pos[None, :] < window
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return _einsum("bhqs,bshk->bqhk", probs, v, quant)

    blocks = q.reshape(q.shape[0], s // block, block, *q.shape[2:])
    out = jax.lax.map(
        one, (jnp.moveaxis(blocks, 1, 0), jnp.arange(0, s, block))
    )
    return jnp.moveaxis(out, 0, 1).reshape(q.shape)


def attention_layer(h, p: dict, cfg: dict, layer: int, quant=None):
    kind = cfg["layer_types"][layer]
    group = p["wq"].shape[1] // cfg["num_key_value_heads"]
    turn = lambda u: _rope(u, *rope_frequencies(cfg, kind))
    q = turn(_einsum("bsd,dhk->bshk", h, p["wq"], quant))
    k = turn(_einsum("bsd,dhk->bshk", h, p["wk"], quant))
    v = _einsum("bsd,dhk->bshk", h, p["wv"], quant)
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    att = _attention(
        q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2), window,
        quant,
    )
    gate = jax.nn.sigmoid(_einsum("bsd,dh->bsh", h, p["wg"], quant))
    return _einsum("bqhk,hkd->bqd", att * gate[..., None], p["wo"], quant)


def _swiglu(h, w_gate, w_up, w_down, quant):
    hidden = jax.nn.silu(_einsum("bsd,df->bsf", h, w_gate, quant)) * _einsum(
        "bsd,df->bsf", h, w_up, quant
    )
    return _einsum("bsf,fd->bsd", hidden, w_down, quant)


def forced_experts(layer: int, seq_len: int, routed: int, k: int):
    scores = jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(FORCED_ROUTING_SEED), layer),
        (seq_len, routed), jnp.float32,
    )
    return jax.lax.top_k(scores, k)[1]


def route(h, p: dict, cfg: dict, layer: int, quant=None):
    """(expert [B, S, k], weight [B, S, k])."""
    k = cfg["num_experts_per_tok"]
    probs = jax.nn.sigmoid(_einsum("bsd,de->bse", h, p["router"], quant))
    if cfg.get("router_force_balance"):
        expert = jnp.broadcast_to(
            forced_experts(layer, h.shape[1], probs.shape[-1], k),
            (*h.shape[:2], k),
        )
    else:
        _, expert = jax.lax.top_k(
            probs + jax.lax.stop_gradient(p["router_bias"]), k
        )
    chosen = jnp.take_along_axis(probs, expert, axis=-1)
    if cfg.get("norm_topk_prob", True):
        chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return expert, cfg["moe_routed_scaling_factor"] * chosen


def expert_layer(h, p: dict, cfg: dict, layer: int, quant=None):
    expert, weight = route(h, p, cfg, layer, quant)
    held = p["w_gate"].shape[0]

    @jax.checkpoint
    def one_expert(acc, args):
        w_gate, w_up, w_down, index = args
        out = _swiglu(h, w_gate, w_up, w_down, quant)
        mine = jnp.sum(jnp.where(expert == index, weight, 0.0), axis=-1)
        return acc + mine[..., None] * out, None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (p["w_gate"], p["w_up"], p["w_down"],
         cfg["experts_first"] + jnp.arange(held)),
    )
    return routed + _swiglu(
        h, p["shared_gate"], p["shared_up"], p["shared_down"], quant
    )


def layer(x, p: dict, cfg: dict, index: int, quant=None):
    eps = cfg["rms_norm_eps"]
    x = x + attention_layer(_rms_norm(x, p["ln_attn"], eps), p, cfg, index, quant)
    h = _rms_norm(x, p["ln_mlp"], eps)
    if cfg["mlp_layer_types"][index] == "dense":
        return x + _swiglu(h, p["mlp_gate"], p["mlp_up"], p["mlp_down"], quant)
    return x + expert_layer(h, p, cfg, index, quant)


def layer_params(params: dict, i: int) -> dict:
    pre = f"layer.{i}."
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def logits(params: dict, tokens, cfg: dict, quant=None):
    x = params["embedding"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        # Save only each layer's input for the backward pass: memory, not
        # arithmetic.
        body = jax.checkpoint(lambda x, p, i=i: layer(x, p, cfg, i, quant))
        x = body(x, layer_params(params, i))
    x = _rms_norm(x, params["ln_final"], cfg["rms_norm_eps"])
    return _einsum("bsd,vd->bsv", x, params["lm_head"], quant)


def summed_loss(params: dict, tokens, labels, cfg: dict, quant=None):
    """Sum over tokens of the next-token cross entropy (divide by the count)."""
    z = logits(params, tokens, cfg, quant)
    log_z = jax.scipy.special.logsumexp(z, axis=-1)
    picked = jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(log_z - picked)


def follow(key, cfg: dict, opt: dict, batches, *, rows_per_block=None, quant=None):
    """The training reference: `reference/zaya.follow` (AdamW leaf by leaf,
    both moments waiting on the host) over this module's model."""
    return _zaya.follow(
        key, cfg, opt, batches, rows_per_block=rows_per_block, quant=quant,
        model=sys.modules[__name__],
    )
