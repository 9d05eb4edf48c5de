"""Plain reference for a Qwen3-Next-style decoder
(Qwen/Qwen3-Next-80B-A3B-Instruct, `model_type` `qwen3_next`) and its
training step.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernels, no chunks, no
low-precision storage, nothing imported from the program (the helpers shared
with `reference/lm.py`, `reference/zaya.py`, `reference/xing.py` and
`reference/kimi_linear.py` — the int8 control's rounding, the AdamW step that
keeps its moments on the host, causal attention a block of queries at a
time, the half-split rope over a share of a head, the seeded draws, the
causal convolution, the forced selection — are the benchmark's own).

d = `hidden_size`. A norm is `n(x; w) = x / sqrt(mean(x^2) + rms_norm_eps) *
(1 + w)`, w zero at the seed. A layer is `x += mixer(n(x; w_1))`, then `x +=
moe(n(x; w_2))`; counted from 0, layer i mixes by gated attention where `(i
+ 1) % full_attention_interval == 0` and by a Gated DeltaNet elsewhere.

**Gated DeltaNet**, on the normed input x [S, d]; H_k = `linear_num_key_heads`,
H = `linear_num_value_heads`, c = `linear_key_head_dim` =
`linear_value_head_dim`, taps = `linear_conv_kernel_dim`:

- `[q~ | k~ | v~ | z] = x W_qkvz`, held as its four column blocks `W_q`
  [d, H_k, c], `W_k` [d, H_k, c], `W_v` [d, H, c], `W_z` [d, H c] (a product
  with a matrix of blocks is the blocks' products); `[b~ | a~] = x W_ba`,
  held as `W_b` and `W_a` [d, H]; no bias;
- `q^ = silu(conv(q~))`, `k^ = silu(conv(k~))`, `v = silu(conv(v~))`: a causal
  depthwise convolution a channel over the H_k c + H_k c + H c channels,
  `conv(u)_t = sum_j w[j] u_(t-j)`, j = 0..taps-1, zeros before the first
  token, no bias (held as the three blocks of its channels);
- a head's `q = c^-1/2 q^ / sqrt(|q^|^2 + eps)`, `k = k^ / sqrt(|k^|^2 +
  eps)` over its c channels, eps = `rms_norm_eps`; value head h reads key
  head h // (H / H_k);
- `beta = sigmoid(b~)`, `g = -exp(A_log[h]) softplus(a~ + dt_bias[h])`, both
  one a VALUE head;
- a value head's state `S_0 = 0` [c, c], a POSITION at a time (a `lax.scan`
  over positions; no chunk, no triangular inverse): `S_t = e^(g_t) S_(t-1)`,
  then `S_t += k_t (beta_t (v_t - S_t^T k_t))^T`, `o_t = S_t^T q_t`;
- `y = RMSNorm_c(o) w_n * silu(z)` a head, ONE plain scale `w_n` [c] (ones at
  the seed; no 1 + w here), the norm first; out = `y W_o`, `W_o` [H c, d].

**Gated attention**; A = `num_attention_heads`, K = `num_key_value_heads`, D =
`head_dim`: `[q~ | gate]` a head = `x W_q`, `W_q` [d, A, 2 D]; `k~ = x W_k`,
`v = x W_v`, [d, K, D]; no bias; `q = n_D(q~; w_q)`, `k = n_D(k~; w_k)` a
head, (1 + w) scales; rope (the half-split rotation, `rope_theta`) over the
first `partial_rotary_factor` D dims of q and k; causal softmax of `q k^T
D^-1/2`, query head h over K/V head h // (A / K); out = `(o * sigmoid(gate))
W_o`, `W_o` [A, D, d].

**Experts**: `p = softmax(u W_r)` over `experts_routed`; the
`num_experts_per_tok` largest, weights `p_e / sum of the chosen`
(`norm_topk_prob`), no factor and no correction; an expert `(silu(u W1) * (u
W3)) W2` of `moe_intermediate_size`; plus `sigmoid(u w_s) * shared(u)`, `w_s`
[d, 1], `shared` the same SwiGLU of `shared_expert_intermediate_size`. Only
`num_experts` experts from `experts_first` on are held: what the others would
add is left out, as in the program. With `cfg["router_force_balance"]` the
chosen are the k largest of standard normal scores from `PRNGKey(42)` folded
with the layer's index (`reference/zaya.py`'s docstring says why).

The final `n(x; w_f)`, then the untied head.

**Assumed** (the configuration file lists the same): everything above that
`config.json` does not fix; `A_log` = log of 1..16 spread evenly over the
value heads and `dt_bias` the inverse softplus of steps log-uniform in
[0.001, 0.1] (the accepted recurrent cells' draw; NOT the published
initialiser). Weights: normal, std 0.02 for the embedding, 1/sqrt(fan_in) for
every matrix (the head's too), taps^-1/2 for the convolutions, `w_n` 1, every
(1 + w) scale's w 0: the plain draw of `reference/lm.py`.
"""

from __future__ import annotations

import math
import sys

import jax
import jax.numpy as jnp

from benchmarks.reference import kimi_linear as _kimi
from benchmarks.reference import xing as _xing
from benchmarks.reference import zaya as _zaya
from benchmarks.reference.lm import _einsum

POSITIONS_PER_BLOCK = _kimi.POSITIONS_PER_BLOCK

# -- weights ---------------------------------------------------------------


def is_delta(cfg: dict, layer: int) -> bool:
    """`layer` counted from 0."""
    return (layer + 1) % cfg["full_attention_interval"] != 0


def param_specs(cfg: dict) -> dict[str, tuple]:
    """name -> (shape, how): `how` = (std, mean), the leaf mean + std *
    normal, or ("dt",) for the decay's bias (`reference/kimi_linear.py`)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    hk, h = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    c, taps = cfg["linear_key_head_dim"], cfg["linear_conv_kernel_dim"]
    a, kv, hd = (
        cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    )
    held, routed = cfg["num_experts"], cfg["experts_routed"]
    ff, sff = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"]
    mat = lambda shape, fan_in: (shape, (1 / math.sqrt(fan_in), 0.0))
    const = lambda shape, value: (shape, (0.0, value))
    rates = tuple(math.log(1 + 15 * i / max(h - 1, 1)) for i in range(h))
    specs = {"embedding": ((v, d), (0.02, 0.0))}
    for i in range(cfg["num_hidden_layers"]):
        pre = f"layer.{i}."
        specs[pre + "ln_attn"] = const((d,), 0.0)
        if is_delta(cfg, i):
            for name, n in (("q", hk), ("k", hk), ("v", h)):
                specs[pre + f"kda_w{name}"] = mat((d, n, c), d)
                specs[pre + f"kda_conv_{name}"] = ((taps, n * c), (taps ** -0.5, 0.0))
            specs[pre + "kda_wg"] = mat((d, h * c), d)
            specs[pre + "kda_wb"] = mat((d, h), d)
            specs[pre + "kda_wa"] = mat((d, h), d)
            specs[pre + "kda_A_log"] = const((h,), rates)
            specs[pre + "kda_dt_bias"] = ((h,), ("dt",))
            specs[pre + "kda_norm"] = const((c,), 1.0)
            specs[pre + "kda_wo"] = mat((h * c, d), h * c)
        else:
            specs[pre + "wq"] = mat((d, a, 2 * hd), d)
            specs[pre + "wk"] = mat((d, kv, hd), d)
            specs[pre + "wv"] = mat((d, kv, hd), d)
            specs[pre + "q_norm"] = const((hd,), 0.0)
            specs[pre + "k_norm"] = const((hd,), 0.0)
            specs[pre + "wo"] = mat((a, hd, d), a * hd)
        specs[pre + "ln_mlp"] = const((d,), 0.0)
        specs[pre + "router"] = mat((d, routed), d)
        specs[pre + "w_gate"] = mat((held, d, ff), d)
        specs[pre + "w_up"] = mat((held, d, ff), d)
        specs[pre + "w_down"] = mat((held, ff, d), ff)
        specs[pre + "shared_gate"] = mat((d, sff), d)
        specs[pre + "shared_up"] = mat((d, sff), d)
        specs[pre + "shared_down"] = mat((sff, d), sff)
        specs[pre + "shared_expert_gate"] = mat((d, 1), d)
    specs["ln_final"] = const((d,), 0.0)
    specs["lm_head"] = mat((v, d), d)
    return specs


init_leaf = _kimi.init_leaf


def init_params(key, cfg: dict) -> dict[str, jax.Array]:
    return {
        name: init_leaf(key, i, *spec)
        for i, (name, spec) in enumerate(param_specs(cfg).items())
    }


# The layers differ in kind, so nothing is stacked (`reference/xing.py`).
stack_layers = lambda flat, cfg: flat
by_layer = lambda tree, cfg: tree

# -- the model ---------------------------------------------------------------


def norm(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (
        1.0 + w
    )


def delta_rule(q, k, v, g, b):
    """o [B, S, H, c] of the recurrence, a position at a time: q, k, v
    [B, S, H, c] (q and k already a value head each), g, b [B, S, H]."""
    bsz, s, h, c = v.shape
    block = math.gcd(s, POSITIONS_PER_BLOCK)

    def position(state, xs):  # state [B, H, c_k, c_v]
        qt, kt, vt, gt, bt = xs
        state = jnp.exp(gt)[..., None, None] * state
        seen = jnp.sum(kt[..., None] * state, axis=-2)            # S^T k
        state = state + kt[..., None] * (bt[..., None] * (vt - seen))[..., None, :]
        return state, jnp.sum(qt[..., None] * state, axis=-2)     # S^T q

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


def delta_layer(x, p: dict, cfg: dict, quant=None):
    hk, h = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    c, eps = cfg["linear_key_head_dim"], cfg["rms_norm_eps"]

    def mixed(name, n):
        u = _einsum("bsd,dhc->bshc", x, p[f"kda_w{name}"], quant)
        return jax.nn.silu(_kimi.causal_conv(
            u.reshape(*u.shape[:2], n * c), p[f"kda_conv_{name}"]
        )).reshape(*u.shape[:2], n, c)

    unit = lambda u: u * jax.lax.rsqrt(
        jnp.sum(u * u, axis=-1, keepdims=True) + eps
    )
    q, k, v = c ** -0.5 * unit(mixed("q", hk)), unit(mixed("k", hk)), mixed("v", h)
    z = _einsum("bsd,dw->bsw", x, p["kda_wg"], quant)
    beta = jax.nn.sigmoid(_einsum("bsd,dh->bsh", x, p["kda_wb"], quant))
    g = -jnp.exp(p["kda_A_log"]) * jax.nn.softplus(
        _einsum("bsd,dh->bsh", x, p["kda_wa"], quant) + p["kda_dt_bias"]
    )
    of_value_head = lambda u: jnp.repeat(u, h // hk, axis=2)  # h reads h // (H / H_k)
    o = delta_rule(of_value_head(q), of_value_head(k), v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    y = (o * p["kda_norm"]).reshape(z.shape) * jax.nn.silu(z)
    return _einsum("bsw,wd->bsd", y, p["kda_wo"], quant)


def attention_layer(x, p: dict, cfg: dict, quant=None):
    a, kv, hd = (
        cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    )
    eps = cfg["rms_norm_eps"]
    both = _einsum("bsd,dhk->bshk", x, p["wq"], quant)
    q, gate = both[..., :hd], both[..., hd:]
    k = _einsum("bsd,dhk->bshk", x, p["wk"], quant)
    v = _einsum("bsd,dhk->bshk", x, p["wv"], quant)
    turn = lambda u: _zaya._rope(
        u, float(cfg["rope_theta"]), cfg["partial_rotary_factor"]
    )
    q, k = turn(norm(q, p["q_norm"], eps)), turn(norm(k, p["k_norm"], eps))
    over = lambda u: jnp.repeat(u, a // kv, axis=2)  # h over K/V head h // (A / K)
    o = _zaya._attention(q, over(k), over(v), quant)  # scores q k^T D^-1/2
    return _einsum("bqhk,hkd->bqd", o * jax.nn.sigmoid(gate), p["wo"], quant)


def route(u, p: dict, cfg: dict, layer: int, quant=None):
    """(expert [B, S, k], weight [B, S, k])."""
    k = cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(_einsum("bsd,de->bse", u, p["router"], quant), axis=-1)
    if cfg.get("router_force_balance"):
        expert = jnp.broadcast_to(
            _xing.forced_experts(layer, u.shape[1], probs.shape[-1], k),
            (*u.shape[:2], k),
        )
    else:
        _, expert = jax.lax.top_k(probs, k)
    chosen = jnp.take_along_axis(probs, expert, axis=-1)
    if cfg["norm_topk_prob"]:
        chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return expert, chosen


def routed_experts(u, p: dict, cfg: dict, layer: int, quant=None):
    """What the experts held here add: dense over them under a mask."""
    expert, weight = route(u, p, cfg, layer, quant)
    held = p["w_gate"].shape[0]

    @jax.checkpoint
    def one_expert(acc, args):
        w_gate, w_up, w_down, index = args
        out = _xing._swiglu(u, w_gate, w_up, w_down, quant)
        mine = jnp.sum(jnp.where(expert == index, weight, 0.0), axis=-1)
        return acc + mine[..., None] * out, None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(u),
        (p["w_gate"], p["w_up"], p["w_down"],
         cfg["experts_first"] + jnp.arange(held)),
    )
    return out


def shared_expert(u, p: dict, quant=None):
    """`sigmoid(u w_s) * shared(u)`: what every share of a layer computes
    alike."""
    gate = jax.nn.sigmoid(_einsum("bsd,do->bso", u, p["shared_expert_gate"], quant))
    return gate * _xing._swiglu(
        u, p["shared_gate"], p["shared_up"], p["shared_down"], quant
    )


def expert_layer(u, p: dict, cfg: dict, layer: int, quant=None):
    return routed_experts(u, p, cfg, layer, quant) + shared_expert(u, p, quant)


def layer(x, p: dict, cfg: dict, index: int, quant=None):
    eps = cfg["rms_norm_eps"]
    mixer = delta_layer if is_delta(cfg, index) else attention_layer
    x = x + mixer(norm(x, p["ln_attn"], eps), p, cfg, quant)
    return x + expert_layer(norm(x, p["ln_mlp"], eps), p, cfg, index, quant)


def logits(params: dict, tokens, cfg: dict, quant=None):
    x = params["embedding"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        # Save only each layer's input for the backward pass: memory, not
        # arithmetic.
        body = jax.checkpoint(lambda x, p, i=i: layer(x, p, cfg, i, quant))
        x = body(x, _xing.layer_params(params, i))
    x = norm(x, params["ln_final"], cfg["rms_norm_eps"])
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
