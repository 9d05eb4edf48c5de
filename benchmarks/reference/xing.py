"""Plain reference for a Xing4.0-style decoder (XingChen-AGI/Xing4.0-29B-A4B,
`model_type` `xing4_0`) and its training step.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernels, no two-part operands,
no low-precision storage, nothing imported from the program (the helpers
shared with `reference/lm.py` and `reference/zaya.py` — the int8 control's
rounding, the AdamW step that keeps its moments on the host — are the
benchmark's own).

d = `hidden_size`, H = `num_attention_heads`, n = `hc_mult`.
`RMSNorm(x; g) = g x / sqrt(mean(x^2) + rms_norm_eps)`.

**Latent attention** (the DeepSeek-V2/V3 model code's equations, whose keys
the configuration carries), on a sublayer's normed input u [S, d]:
`c_q = RMSNorm(u W_qa; g_q)`, `W_qa` [d, q_lora_rank]; `q = c_q W_qb`,
`W_qb` [q_lora_rank, H, qk_nope_head_dim + qk_rope_head_dim]: a head's
`q = [q_n | q_r]`. `[c | k_r] = u W_kva`, `W_kva` [d, kv_lora_rank +
qk_rope_head_dim]; `c_kv = RMSNorm(c; g_kv)`; `[k_n | v] = c_kv W_kvb`,
`W_kvb` [kv_lora_rank, H, qk_nope_head_dim + v_head_dim]. `k_r` is ONE key
part for all heads. `q_r` and `k_r` are turned by rope over all their
`qk_rope_head_dim` dims (lane t with lane t + half): yarn's blended
frequencies (`rope_scaling`: `extrap_t = theta^(-2t/r)`, `interp_t =
extrap_t / factor`, `c(x) = r ln(original / (2 pi x)) / (2 ln theta)`, `low
= max(floor(c(beta_fast)), 0)`, `high = min(ceil(c(beta_slow)), r - 1)`,
`ramp_t = clip((t - low) / (high - low), 0, 1)`, `inv_freq_t = interp_t
ramp_t + extrap_t (1 - ramp_t)`), cos and sin times `mscale(factor, mscale)
/ mscale(factor, mscale_all_dim)` with `mscale(f, m) = 0.1 m ln f + 1`.
`s_h[i, j] = (q_n,h[i] . k_n,h[j] + q_r,h[i] . k_r[j]) (nope + rope)^-1/2
mscale(factor, mscale_all_dim)^2`, causal softmax, `o_h = P_h v_h`; out =
`[o_1 .. o_H] W_o`, `W_o` [H, v_head_dim, d]. No bias.

**Streams** (manifold-constrained hyper-connections, arXiv 2512.24880). The
stack's state is X [S, n, d]; at the entry every stream is the embedding's
row; at the exit the streams are summed, then the final norm and the untied
head. Round EACH sublayer F (attention; the dense MLP or the expert layer),
with its own `phi` [n d, n^2 + 2n], `b` [n^2 + 2n], `a` = (a_pre, a_post,
a_res): `x = RMSNorm(flatten(X))` over n d dims, no learned scale; `[t_pre |
t_post | t_res] = x phi`; `Hp = sigmoid(a_pre t_pre + b_pre)` [n]; `Ho = 2
sigmoid(a_post t_post + b_post)` [n]; `M = exp(clip(a_res t_res + b_res,
mhc_h_res_clamp_min, mhc_h_res_clamp_max))` [n, n] (row-major);
`hc_sinkhorn_iters` times: every row of M divided by (its sum + `hc_eps`),
then every column by (its sum + `hc_eps`); `Hr = M`. `h = sum_i Hp[i]
X[i]`; `y = F(RMSNorm(h; g_layer))`; `X'[i] = sum_j Hr[i, j] X[j] + Ho[i]
y`.

**Feed-forward halves**: layers below `first_k_dense_replace` a dense
SwiGLU of `intermediate_size`; else `p = sigmoid(u W_r)` over
`experts_routed`, the `num_experts_per_tok` largest of `p + b` (`b` zero, no
gradient, no update), weights `routed_scaling_factor p_e / (sum of the
chosen p + 1e-20)`, experts `(silu(u W_g) * (u W_u)) W_d` of
`moe_intermediate_size`, plus one shared expert of `n_shared_experts x
moe_intermediate_size` on the same input. Only `n_routed_experts` experts
from `experts_first` on are held: what the others would add is left out, as
in the program; dense over the held experts with a mask. With
`cfg["router_force_balance"]` the chosen are the k largest of standard
normal scores from `PRNGKey(42)` folded with the layer's index
(`reference/zaya.py`'s docstring says why).

**Assumed** (the configuration file lists the same): the order of the two
normalisations inside an iteration (rows first); the entry (copies) and the
exit (sum); `hc_eps` is the iteration's and `rms_norm_eps` the norms'; the
seed of the maps (`phi` normal at 1/sqrt(n d), `a` = 1, `b_res` = 2 on the
diagonal, else 0: NOT near the identity at the seed); the router's zero
correction; `num_nextn_predict_layers` 0. Weights: normal, std 0.02 for the
embedding, 1/sqrt(fan_in) for every matrix (the head's too), scales 1: the
plain draw of `reference/lm.py`.
"""

from __future__ import annotations

import math
import sys

import jax
import jax.numpy as jnp

from benchmarks.reference import zaya as _zaya
from benchmarks.reference.lm import _einsum
from benchmarks.reference.zaya import _rms_norm

QUERY_BLOCK = 256  # 32 heads x 256 x 8192 float32 scores are 0.27 GB
FORCED_ROUTING_SEED = 42

# -- weights ---------------------------------------------------------------


def maps_width(cfg: dict) -> int:
    n = cfg["hc_mult"]
    return n * n + 2 * n


def is_dense(cfg: dict, layer: int) -> bool:
    return layer < cfg["first_k_dense_replace"]


def param_specs(cfg: dict) -> dict[str, tuple]:
    """name -> (shape, (std, mean)): the leaf is mean + std * normal; a
    mean is a number or a nested tuple of the leaf's shape."""
    d, v, h = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    ql, kvl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    held, routed = cfg["n_routed_experts"], cfg["experts_routed"]
    ff = cfg["moe_intermediate_size"]
    sff, dense = cfg["n_shared_experts"] * ff, cfg["intermediate_size"]
    n, maps = cfg["hc_mult"], maps_width(cfg)
    mat = lambda shape, fan_in: (shape, (1 / math.sqrt(fan_in), 0.0))
    const = lambda shape, value: (shape, (0.0, value))
    b_seed = (0.0,) * (2 * n) + tuple(
        2.0 if i == j else 0.0 for i in range(n) for j in range(n)
    )
    specs = {"embedding": ((v, d), (0.02, 0.0))}
    for i in range(cfg["num_hidden_layers"]):
        pre = f"layer.{i}."
        for sub in ("attn", "mlp"):
            specs[pre + f"hc_{sub}_phi"] = mat((n * d, maps), n * d)
            specs[pre + f"hc_{sub}_b"] = const((maps,), b_seed)
            specs[pre + f"hc_{sub}_a"] = const((3,), 1.0)
        specs[pre + "ln_attn"] = const((d,), 1.0)
        specs[pre + "wq_a"] = mat((d, ql), d)
        specs[pre + "q_norm"] = const((ql,), 1.0)
        specs[pre + "wq_b"] = mat((ql, h, dn + dr), ql)
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
    std, mean = how
    leaf = jnp.broadcast_to(jnp.asarray(mean, jnp.float32), shape)
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
# the flat tree both ways (`reference/laguna.py` does the same).
stack_layers = lambda flat, cfg: flat
by_layer = lambda tree, cfg: tree

# -- the model ---------------------------------------------------------------


def mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_frequencies(cfg: dict):
    """(inv_freq [r / 2], the factor of cos and sin) by the docstring."""
    r, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    p = cfg["rope_scaling"]
    if p["type"] != "yarn":
        raise ValueError(f"rope_scaling type {p['type']!r}: yarn only")
    pair = jnp.arange(0, r, 2, dtype=jnp.float32)
    extrap = theta ** (-pair / r)
    c = lambda x: r * math.log(
        p["original_max_position_embeddings"] / (2 * math.pi * x)
    ) / (2 * math.log(theta))
    low = max(math.floor(c(p["beta_fast"])), 0)
    high = min(math.ceil(c(p["beta_slow"])), r - 1)
    ramp = jnp.clip((pair / 2 - low) / (high - low), 0.0, 1.0)
    inv_freq = (extrap / p["factor"]) * ramp + extrap * (1.0 - ramp)
    factor = mscale(p["factor"], p["mscale"]) / mscale(
        p["factor"], p["mscale_all_dim"]
    )
    return inv_freq, factor


def softmax_scale(cfg: dict) -> float:
    p = cfg["rope_scaling"]
    width = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return width ** -0.5 * mscale(p["factor"], p["mscale_all_dim"]) ** 2


def _rope(x, inv_freq, factor: float):
    """x [B, S, ..., r], all of it turned: lane t with lane t + r/2."""
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    angles = angles.reshape(1, x.shape[1], *(1,) * (x.ndim - 3), -1)
    cos, sin = factor * jnp.cos(angles), factor * jnp.sin(angles)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _attention(q_n, q_r, k_n, k_r, v, scale: float, quant):
    """Causal softmax attention with two-part scores: q_n, k_n
    [B, S, H, dn], q_r [B, S, H, r], k_r [B, S, r], v [B, S, H, dv]; the
    whole row of scores under a mask, a block of queries at a time."""
    s = q_n.shape[1]
    block = min(QUERY_BLOCK, s)
    if s % block:
        raise ValueError(f"sequence {s} does not divide into blocks of {block}")
    key_pos = jnp.arange(s)

    @jax.checkpoint
    def one(args):
        qn_blk, qr_blk, start = args
        scores = scale * (
            _einsum("bqhk,bshk->bhqs", qn_blk, k_n, quant)
            + _einsum("bqhr,bsr->bhqs", qr_blk, k_r, quant)
        )
        seen = (start + jnp.arange(block))[:, None] >= key_pos[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return _einsum("bhqs,bshk->bqhk", probs, v, quant)

    blocks = lambda u: jnp.moveaxis(
        u.reshape(u.shape[0], s // block, block, *u.shape[2:]), 1, 0
    )
    out = jax.lax.map(one, (blocks(q_n), blocks(q_r), jnp.arange(0, s, block)))
    return jnp.moveaxis(out, 0, 1).reshape(*q_n.shape[:3], v.shape[-1])


def attention_layer(u, p: dict, cfg: dict, quant=None):
    eps, dn = cfg["rms_norm_eps"], cfg["qk_nope_head_dim"]
    kvl = cfg["kv_lora_rank"]
    c_q = _rms_norm(_einsum("bsd,dl->bsl", u, p["wq_a"], quant), p["q_norm"], eps)
    q = _einsum("bsl,lhk->bshk", c_q, p["wq_b"], quant)
    joint = _einsum("bsd,dl->bsl", u, p["wkv_a"], quant)
    c_kv = _rms_norm(joint[..., :kvl], p["kv_norm"], eps)
    kv = _einsum("bsl,lhk->bshk", c_kv, p["wkv_b"], quant)
    turn = lambda x: _rope(x, *rope_frequencies(cfg))
    att = _attention(
        q[..., :dn], turn(q[..., dn:]), kv[..., :dn], turn(joint[..., kvl:]),
        kv[..., dn:], softmax_scale(cfg), quant,
    )
    return _einsum("bqhk,hkd->bqd", att, p["wo"], quant)


def stream_maps(x_streams, phi, b, a, cfg: dict, quant=None):
    """(Hp [B, S, n], Ho [B, S, n], Hr [B, S, n, n]) of X [B, S, n, d]."""
    n = cfg["hc_mult"]
    flat = x_streams.reshape(*x_streams.shape[:2], -1)
    x = flat * jax.lax.rsqrt(
        jnp.mean(flat * flat, axis=-1, keepdims=True) + cfg["rms_norm_eps"]
    )
    t = _einsum("bsk,kc->bsc", x, phi, quant)
    hp = jax.nn.sigmoid(a[0] * t[..., :n] + b[:n])
    ho = 2.0 * jax.nn.sigmoid(a[1] * t[..., n:2 * n] + b[n:2 * n])
    m = jnp.exp(jnp.clip(
        a[2] * t[..., 2 * n:] + b[2 * n:], cfg["mhc_h_res_clamp_min"],
        cfg["mhc_h_res_clamp_max"],
    )).reshape(*t.shape[:2], n, n)
    for _ in range(cfg["hc_sinkhorn_iters"]):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + cfg["hc_eps"])
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + cfg["hc_eps"])
    return hp, ho, m


def mixed(x_streams, p: dict, sub: str, cfg: dict, f, quant=None):
    """One sublayer `f` (of the mixed input [B, S, d]) round the streams."""
    hp, ho, hr = stream_maps(
        x_streams, p[f"hc_{sub}_phi"], p[f"hc_{sub}_b"], p[f"hc_{sub}_a"], cfg,
        quant,
    )
    y = f(jnp.einsum("bsi,bsid->bsd", hp, x_streams))
    return jnp.einsum("bsij,bsjd->bsid", hr, x_streams) + (
        ho[..., None] * y[:, :, None, :]
    )


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
    return expert, cfg["routed_scaling_factor"] * chosen


def routed_experts(h, p: dict, cfg: dict, layer: int, quant=None):
    """What the experts held here add (no shared expert)."""
    expert, weight = route(h, p, cfg, layer, quant)
    held = p["w_gate"].shape[0]

    @jax.checkpoint
    def one_expert(acc, args):
        w_gate, w_up, w_down, index = args
        out = _swiglu(h, w_gate, w_up, w_down, quant)
        mine = jnp.sum(jnp.where(expert == index, weight, 0.0), axis=-1)
        return acc + mine[..., None] * out, None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (p["w_gate"], p["w_up"], p["w_down"],
         cfg["experts_first"] + jnp.arange(held)),
    )
    return out


def expert_layer(h, p: dict, cfg: dict, layer: int, quant=None):
    return routed_experts(h, p, cfg, layer, quant) + _swiglu(
        h, p["shared_gate"], p["shared_up"], p["shared_down"], quant
    )


def layer(x_streams, p: dict, cfg: dict, index: int, quant=None):
    eps = cfg["rms_norm_eps"]
    x_streams = mixed(
        x_streams, p, "attn", cfg,
        lambda h: attention_layer(_rms_norm(h, p["ln_attn"], eps), p, cfg, quant),
        quant,
    )

    def feed_forward(h):
        u = _rms_norm(h, p["ln_mlp"], eps)
        if is_dense(cfg, index):
            return _swiglu(u, p["mlp_gate"], p["mlp_up"], p["mlp_down"], quant)
        return expert_layer(u, p, cfg, index, quant)

    return mixed(x_streams, p, "mlp", cfg, feed_forward, quant)


def layer_params(params: dict, i: int) -> dict:
    pre = f"layer.{i}."
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def logits(params: dict, tokens, cfg: dict, quant=None):
    x = params["embedding"][tokens]
    x = jnp.broadcast_to(x[:, :, None, :], (*x.shape[:2], cfg["hc_mult"], x.shape[-1]))
    for i in range(cfg["num_hidden_layers"]):
        # Save only each layer's input for the backward pass: memory, not
        # arithmetic.
        body = jax.checkpoint(lambda x, p, i=i: layer(x, p, cfg, i, quant))
        x = body(x, layer_params(params, i))
    x = _rms_norm(jnp.sum(x, axis=2), params["ln_final"], cfg["rms_norm_eps"])
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
