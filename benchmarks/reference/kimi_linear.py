"""Plain reference for a Kimi-Linear-style decoder
(moonshotai/Kimi-Linear-48B-A3B-Instruct, `model_type` `kimi_linear`) and
its training step.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernels, no chunks, no
low-precision storage, nothing imported from the program (the helpers shared
with `reference/lm.py`, `reference/zaya.py` and `reference/xing.py` — the
int8 control's rounding, the AdamW step that keeps its moments on the host,
two-part causal attention a block of queries at a time, the sigmoid router
and the experts dense under a mask — are the benchmark's own).

d = `hidden_size`. Every layer is `x + mixer(RMSNorm(x))`, then `x +
ff(RMSNorm(x))`; `RMSNorm(x; w) = w x / sqrt(mean(x^2) + rms_norm_eps)`.
Layers are numbered from 1 as `linear_attn_config` numbers them.

**KDA mixer** (layers in `linear_attn_config.kda_layers`), on the normed
input x [S, d]; H = `linear_attn_config.num_heads`, c = its `head_dim` (a
head's key AND value channels), D = H c, taps = `short_conv_kernel_size`:

- `q~ = x W_q`, `k~ = x W_k`, `v~ = x W_v`, each [d, H, c], no bias;
- `q^ = silu(conv(q~))`, `k^ = silu(conv(k~))`, `v = silu(conv(v~))`: a
  causal depthwise convolution a channel, `conv(u)_t = sum_j w[j] u_(t-j)`,
  j = 0..taps-1, zeros before the first token, no bias;
- a head's `q = c^-1/2 q^ / sqrt(|q^|^2 + eps)`, `k = k^ / sqrt(|k^|^2 +
  eps)`, the L2 norm over the head's c channels, eps = `rms_norm_eps`;
- the decay a channel: `g = -exp(A_log[h]) softplus((x W_f1) W_f2 +
  dt_bias)`, `W_f1` [d, c], `W_f2` [c, D], `dt_bias` [D]; `a = exp(g)` in
  (0, 1);
- `b = sigmoid(x W_b)`, `W_b` [d, H], one a head;
- a head's state `S_0 = 0` [c, c], a POSITION at a time (a `lax.scan` over
  positions; no chunk, no triangular inverse):
  `S_t = (I - b_t k_t k_t^T) diag(a_t) S_(t-1) + b_t k_t v_t^T`,
  `o_t = S_t^T q_t`;
- `y = (RMSNorm_head(o; w_n) * sigmoid((x W_g1) W_g2)) W_o`: the norm over
  each head's c channels with ONE learned scale `w_n` [c], `W_g1` [d, c],
  `W_g2` [c, D], `W_o` [D, d].

**MLA mixer** (layers in `full_attn_layers`): `reference/xing.py`'s latent
attention with two differences. `[q_n | q_r] = x W_q` directly, `W_q` [d, H,
qk_nope_head_dim + qk_rope_head_dim] (`q_lora_rank` null: no bottleneck, no
q norm); `[c | k_r] = x W_kva`, `[k_n | v] = RMSNorm(c; g_kv) W_kvb`; and
NO rotation of `q_r` and `k_r` (`mla_use_nope`). Scores `(q_n k_n^T + q_r
k_r^T) (nope + rope)^-1/2`, the ONE `k_r` for all heads, causal softmax,
values of `v_head_dim`, `W_o` [H, v, d].

**Feed-forward halves**: layers up to `first_k_dense_replace` a dense SwiGLU
of `intermediate_size`; else `p = sigmoid(u W_r)` over `experts_routed`, the
`num_experts_per_token` largest of `p + b` (`b` zero, no gradient, no
update), weights `routed_scaling_factor p_e / (sum of the chosen p + 1e-20)`
(`moe_renormalize`), experts `(silu(u W_g) * (u W_u)) W_d` of
`moe_intermediate_size`, plus `num_shared_experts` shared experts' width on
the same input, added unweighted. Only `num_experts` experts from
`experts_first` on are held: what the others would add is left out, as in
the program. With `cfg["router_force_balance"]` the chosen are the k largest
of standard normal scores from `PRNGKey(42)` folded with the layer's index
counted from 0 (`reference/zaya.py`'s docstring says why).

**Assumed** (the configuration file lists the same): no bias anywhere; silu
after each convolution; the L2 norm's eps; `A_log` = log of 1..16 spread
evenly over the heads and `dt_bias` the inverse softplus of steps
log-uniform in [0.001, 0.1] (the accepted state-space cell's draw); no bias
on `W_g2`; the output norm's place (before the gate) and the gate a
sigmoid; the un-rotated 64-wide part and the plain 192^-1/2; the router's
zero correction. Weights: normal, std 0.02 for the embedding, 1/sqrt(fan_in)
for every matrix (the head's too), taps^-1/2 for the convolutions, scales 1:
the plain draw of `reference/lm.py`.
"""

from __future__ import annotations

import math
import sys

import jax
import jax.numpy as jnp

from benchmarks.reference import xing as _xing
from benchmarks.reference import zaya as _zaya
from benchmarks.reference.lm import _einsum
from benchmarks.reference.zaya import _rms_norm

# Positions a block of the recurrence's scan: its backward keeps one state
# [H, c, c] a block (2 MB at the published widths), and a block's states
# only while that block is differentiated.
POSITIONS_PER_BLOCK = 64
DT_RANGE = (1e-3, 1e-1, 1e-4)

# -- weights ---------------------------------------------------------------


def is_dense(cfg: dict, layer: int) -> bool:
    """`layer` counted from 0."""
    return layer < cfg["first_k_dense_replace"]


def is_kda(cfg: dict, layer: int) -> bool:
    """`layer` counted from 0; the configuration's lists count from 1."""
    linear = cfg["linear_attn_config"]
    if layer + 1 in linear["kda_layers"]:
        return True
    if layer + 1 in linear["full_attn_layers"]:
        return False
    raise ValueError(f"layer {layer + 1} is in neither list of {linear}")


def param_specs(cfg: dict) -> dict[str, tuple]:
    """name -> (shape, how): `how` = (std, mean), the leaf mean + std *
    normal (a mean is a number or a tuple of the leaf's shape), or ("dt",)
    for the decay's bias."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    linear = cfg["linear_attn_config"]
    hl, c = linear["num_heads"], linear["head_dim"]
    taps = linear["short_conv_kernel_size"]
    h, kvl = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    held, routed = cfg["num_experts"], cfg["experts_routed"]
    ff = cfg["moe_intermediate_size"]
    sff, dense = cfg["num_shared_experts"] * ff, cfg["intermediate_size"]
    mat = lambda shape, fan_in: (shape, (1 / math.sqrt(fan_in), 0.0))
    const = lambda shape, value: (shape, (0.0, value))
    rates = tuple(math.log(1 + 15 * i / max(hl - 1, 1)) for i in range(hl))
    specs = {"embedding": ((v, d), (0.02, 0.0))}
    for i in range(cfg["num_hidden_layers"]):
        pre = f"layer.{i}."
        specs[pre + "ln_attn"] = const((d,), 1.0)
        if is_kda(cfg, i):
            for name in ("q", "k", "v"):
                specs[pre + f"kda_w{name}"] = mat((d, hl, c), d)
                specs[pre + f"kda_conv_{name}"] = ((taps, hl * c), (taps ** -0.5, 0.0))
            specs[pre + "kda_wf_a"] = mat((d, c), d)
            specs[pre + "kda_wf_b"] = mat((c, hl * c), c)
            specs[pre + "kda_A_log"] = const((hl,), rates)
            specs[pre + "kda_dt_bias"] = ((hl * c,), ("dt",))
            specs[pre + "kda_wb"] = mat((d, hl), d)
            specs[pre + "kda_wg_a"] = mat((d, c), d)
            specs[pre + "kda_wg_b"] = mat((c, hl * c), c)
            specs[pre + "kda_norm"] = const((c,), 1.0)
            specs[pre + "kda_wo"] = mat((hl * c, d), hl * c)
        else:
            specs[pre + "wq"] = mat((d, h, dn + dr), d)
            specs[pre + "wkv_a"] = mat((d, kvl + dr), d)
            specs[pre + "kv_norm"] = const((kvl,), 1.0)
            specs[pre + "wkv_b"] = mat((kvl, h, dn + dv), kvl)
            specs[pre + "wo"] = mat((h, dv, d), h * dv)
        specs[pre + "ln_mlp"] = const((d,), 1.0)
        if is_dense(cfg, i):
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
    if how[0] == "dt":
        lo, hi, floor = DT_RANGE
        steps = jnp.maximum(jnp.exp(jax.random.uniform(
            jax.random.fold_in(key, index), shape, jnp.float32,
            math.log(lo), math.log(hi),
        )), floor)
        return steps + jnp.log(-jnp.expm1(-steps))  # softplus's inverse
    return _xing.init_leaf(key, index, shape, how)


def init_params(key, cfg: dict) -> dict[str, jax.Array]:
    return {
        name: init_leaf(key, i, *spec)
        for i, (name, spec) in enumerate(param_specs(cfg).items())
    }


# The layers differ in kind, so nothing is stacked (`reference/xing.py`).
stack_layers = lambda flat, cfg: flat
by_layer = lambda tree, cfg: tree

# -- the model ---------------------------------------------------------------


def causal_conv(u, w):
    """u [B, S, W], w [taps, W]: tap j multiplies the value j tokens back."""
    s = u.shape[1]
    return sum(
        w[j] * jnp.pad(u, ((0, 0), (j, 0), (0, 0)))[:, :s]
        for j in range(w.shape[0])
    )


def delta_rule(q, k, v, g, b):
    """o [B, S, H, c] of the recurrence, a position at a time: q, k, v, g
    [B, S, H, c], b [B, S, H]."""
    bsz, s, h, c = q.shape
    block = math.gcd(s, POSITIONS_PER_BLOCK)

    def position(state, xs):  # state [B, H, c_k, c_v]
        qt, kt, vt, gt, bt = xs
        state = jnp.exp(gt)[..., None] * state
        seen = jnp.sum(kt[..., None] * state, axis=-2)
        state = state + kt[..., None] * (bt[..., None] * (vt - seen))[..., None, :]
        return state, jnp.sum(qt[..., None] * state, axis=-2)

    @jax.checkpoint
    def positions(state, xs):
        return jax.lax.scan(position, state, xs)

    by_block = lambda u: jnp.moveaxis(u, 1, 0).reshape(
        s // block, block, *u.shape[:1], *u.shape[2:]
    )
    _, o = jax.lax.scan(
        positions, jnp.zeros((bsz, h, c, c), jnp.float32),
        tuple(by_block(u) for u in (q, k, v, g, b)),
    )
    return jnp.moveaxis(o.reshape(s, bsz, h, c), 0, 1)


def kda_layer(x, p: dict, cfg: dict, quant=None):
    linear = cfg["linear_attn_config"]
    h, c = linear["num_heads"], linear["head_dim"]
    eps = cfg["rms_norm_eps"]
    heads = lambda u: u.reshape(*u.shape[:2], h, c)

    def mixed(name):
        u = _einsum("bsd,dhc->bshc", x, p[f"kda_w{name}"], quant)
        return heads(jax.nn.silu(causal_conv(
            u.reshape(*u.shape[:2], h * c), p[f"kda_conv_{name}"]
        )))

    unit = lambda u: u * jax.lax.rsqrt(
        jnp.sum(u * u, axis=-1, keepdims=True) + eps
    )
    q, k, v = c ** -0.5 * unit(mixed("q")), unit(mixed("k")), mixed("v")
    low = _einsum("bsd,dc->bsc", x, p["kda_wf_a"], quant)
    g = -jnp.repeat(jnp.exp(p["kda_A_log"]), c) * jax.nn.softplus(
        _einsum("bsc,cw->bsw", low, p["kda_wf_b"], quant) + p["kda_dt_bias"]
    )
    b = jax.nn.sigmoid(_einsum("bsd,dh->bsh", x, p["kda_wb"], quant))
    o = delta_rule(q, k, v, heads(g), b)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    low = _einsum("bsd,dc->bsc", x, p["kda_wg_a"], quant)
    gate = jax.nn.sigmoid(_einsum("bsc,cw->bsw", low, p["kda_wg_b"], quant))
    y = (o * p["kda_norm"]).reshape(gate.shape) * gate
    return _einsum("bsw,wd->bsd", y, p["kda_wo"], quant)


def mla_layer(x, p: dict, cfg: dict, quant=None):
    eps, dn = cfg["rms_norm_eps"], cfg["qk_nope_head_dim"]
    kvl = cfg["kv_lora_rank"]
    q = _einsum("bsd,dhk->bshk", x, p["wq"], quant)
    joint = _einsum("bsd,dl->bsl", x, p["wkv_a"], quant)
    c_kv = _rms_norm(joint[..., :kvl], p["kv_norm"], eps)
    kv = _einsum("bsl,lhk->bshk", c_kv, p["wkv_b"], quant)
    width = dn + cfg["qk_rope_head_dim"]
    att = _xing._attention(
        q[..., :dn], q[..., dn:], kv[..., :dn], joint[..., kvl:], kv[..., dn:],
        width ** -0.5, quant,
    )
    return _einsum("bqhk,hkd->bqd", att, p["wo"], quant)


def _experts_cfg(cfg: dict) -> dict:
    """The keys `reference/xing.py`'s expert layer reads, from this
    family's."""
    return {
        "num_experts_per_tok": cfg["num_experts_per_token"],
        "routed_scaling_factor": cfg["routed_scaling_factor"],
        "norm_topk_prob": cfg["moe_renormalize"],
        "experts_first": cfg["experts_first"],
        "router_force_balance": cfg.get("router_force_balance", False),
    }


def layer(x, p: dict, cfg: dict, index: int, quant=None):
    eps = cfg["rms_norm_eps"]
    mixer = kda_layer if is_kda(cfg, index) else mla_layer
    x = x + mixer(_rms_norm(x, p["ln_attn"], eps), p, cfg, quant)
    u = _rms_norm(x, p["ln_mlp"], eps)
    if is_dense(cfg, index):
        return x + _xing._swiglu(
            u, p["mlp_gate"], p["mlp_up"], p["mlp_down"], quant
        )
    return x + _xing.expert_layer(u, p, _experts_cfg(cfg), index, quant)


def logits(params: dict, tokens, cfg: dict, quant=None):
    x = params["embedding"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        # Save only each layer's input for the backward pass: memory, not
        # arithmetic.
        body = jax.checkpoint(lambda x, p, i=i: layer(x, p, cfg, i, quant))
        x = body(x, _xing.layer_params(params, i))
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
